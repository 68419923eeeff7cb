"""Share (%) of its roofline that the operations traced under one
``jax.named_scope`` reached inside the traced slice: the least time the
chip could take for the work the slice's steps needed
(``obs["slice_work"][flops]`` / ``[bytes]``, which the runner computed with
``benchmark/flops_mla.py`` from the step records that fall inside the
slice; ``flops.roofline_share`` with ``device.peaks``) over the seconds
those operations took on the device (``obs["scope_seconds"][scope]``, which
the runner summed over the device plane's operations that belong to the
path: ``runners/serve_latent_cell.py::scope_seconds`` says how it finds
them). A run without a trace, a program without the path (the parent of
the PR that added it) or a slice without such work gives nothing."""

from benchmark import device, flops


def read(obs: dict, params: dict):
    seconds = (obs.get("scope_seconds") or {}).get(params["scope"])
    work = obs.get("slice_work") or {}
    n_flops, n_bytes = work.get(params["flops"]), work.get(params["bytes"])
    if not seconds or not n_flops or not n_bytes:
        return None
    peak_flops, peak_bw = device.peaks(obs["device_kind"])
    share, _ = flops.roofline_share(n_flops, n_bytes, seconds, peak_flops,
                                    peak_bw)
    return share
