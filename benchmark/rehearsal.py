"""The CPU rehearsal's sizes: the same code paths at a toy size, so that a
wrong path, argument or warm-up plan is found here and not on the chip.
Nothing a rehearsal prints is a measurement (``run.py --rehearse`` prints
no result line)."""

from __future__ import annotations

import copy

TINY = {
    "vocab_size": 1024, "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
}
SERVE_ARGS = ["--max_slots", "4", "--cache_len", "256",
              "--kv-cache-dtype", "bfloat16",
              "--enable-chunked-prefill", "64"]
SCALE = 4       # serving lengths shrink by the ratio of the cache lengths


def shrink(config: dict, workload: dict) -> tuple[dict, dict]:
    config, workload = copy.deepcopy(config), copy.deepcopy(workload)
    config.update(TINY)
    if "serve_args" in config.get("layout", {}):
        config["layout"]["serve_args"] = SERVE_ARGS
        config["max_position_embeddings"] = 256
        for key in ("prompt_tokens", "output_tokens"):
            for field in ("min", "max", "median"):
                if field in workload[key]:
                    workload[key][field] = max(
                        2 if key == "output_tokens" else 12,
                        workload[key][field] // SCALE)
        workload["max_total_tokens"] //= SCALE
        workload["grace_s"] = 60
        workload["trace_slice_s"] = 4
        if "clients" in workload:
            workload["clients"] = 4
            workload["pool"], workload["cycle"] = 64, 16
        workload["warm_admission_batch"] = 2
    else:
        workload.update(batch=1, seq=640, trace_slice_s=1,
                        loss_tolerance=0.05)
    return config, workload
