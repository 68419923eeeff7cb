"""Share (%) of its roofline that the grouped expert matmul (the Pallas
``gmm`` calls of ``ops/grouped_experts.py``) reached inside the traced
slice: the least time the chip could take for its executions (the larger
of FLOPs / peak and bytes / bandwidth, ``flops.roofline_share`` with
``device.peaks``) over the seconds its operations took on the device
(``obs["trace"]["op_seconds"]``, summed over the operations whose name
matches ``op_pattern``).

The block program ran ``programs[block_pattern]`` times in the slice, each
run does
``obs["model"]["layers"]`` layers of three grouped matmuls over
``slots * block * top_k`` assignments (the whole plane, idle rows too,
which is what the device computes), and touched, per layer, the window's
mean count of distinct experts (``moe_experts_touched`` / (``block_passes``
x layers) of ``obs["counters"]``); FLOPs and bytes by
``flops_moe.grouped_experts_cost``. The prefill programs run the same
kernel a few times a slice: their seconds are in the sum and their least
time is not, so the share UNDER-reads by their part (under 2% in the
block-decode cell). A run without a trace, without the kernel's
operations or without the counters gives nothing.
"""

import re

from benchmark import device, flops, flops_moe


def read(obs: dict, params: dict):
    trace, model = obs.get("trace"), obs.get("model")
    if trace is None or not model:
        return None
    op = re.compile(params["op_pattern"])
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if op.search(name))
    prog = re.compile(params["block_pattern"])
    runs = sum(len(ds) for name, ds in trace["programs"].items()
               if prog.search(name))
    c = obs["counters"]
    passes, touched = c.get("block_passes"), c.get("moe_experts_touched")
    if not seconds or not runs or not passes or not touched:
        return None
    layers = model["layers"]
    per_layer = flops_moe.grouped_experts_cost(
        c["moe_assignments"] / (passes * layers),
        touched / (passes * layers), model["hidden"], model["width"])
    n = runs * layers
    peak_flops, peak_bw = device.peaks(obs["device_kind"])
    share, _ = flops.roofline_share(n * per_layer[0], n * per_layer[1],
                                    seconds, peak_flops, peak_bw)
    return share
