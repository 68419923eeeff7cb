"""The LITERAL north-star workload: Qwen3-14B QLoRA on one chip.

The reference's flagship fine-tune is Qwen3-14B QLoRA under ZeRO-3
(``Fine-Tuning/qwen3-14b-qlora-dist-deepspeed.py:95-123``,
``ds_zero3_config.json:5-22``) across multiple 24 GB GPUs. Round 3
proved the 8B sibling trains on ONE v5e chip under the scan with inline
dequant (``bench.py::_fused_scale_proof``, docs/perf.md Finding 10);
this tool runs the SAME machinery at the real 14B geometry (d5120 /
L40 / GQA 40:8 / inter 17408 / vocab 151936 — 14.8B params, NF4 base
≈ 8.3 GiB) and records ``QLORA_14B.json``. Memory arithmetic: packed
base + bf16 embed ≈ 9 GiB leaves ~6.5 GiB for LoRA/opt/remat
activations — batch 8 should fit, the ladder falls to 4/2 otherwise.

Run: ``python tools/tpu_qlora_14b.py`` (real TPU; ~20 min, most of it
``quantize_base_lowmem`` + one compile).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import (  # noqa: E402
    G14B, G14B_BATCHES, _fused_scale_proof, chip_peak,
)

OUT = os.path.join(REPO, "QLORA_14B.json")


def main() -> None:
    from llm_in_practise_tpu.core.mesh import require_tpu

    require_tpu()
    kind, peak = chip_peak()
    print(f"device {kind} peak {peak/1e12:.0f} TF", flush=True)
    result, errors = _fused_scale_proof(
        peak, dict(vocab=151936, n_layer=40, batches=G14B_BATCHES, **G14B),
        block_cache={})
    out = {"device": kind, "peak_bf16_flops": peak,
           "geometry": {**G14B, "n_layer": 40, "vocab": 151936},
           "ladder_errors": errors[:8]}
    if result is not None:
        out["qlora_14b"] = result
        print(json.dumps(result, indent=2), flush=True)
    else:
        out["failed"] = True
        print("14B rung failed everywhere:", "\n".join(errors), flush=True)
    with open(OUT, "w") as f:
        json.dump(out, f, indent=2)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
