"""Training steps of the program's measured QLoRA path, one batch shape,
the same program every step.

``bench.build_qlora_scan_step`` is called, never copied: it builds the NF4
base, the LoRA factors, the optimizer state and the jitted step. A step
ends when its loss is on the host (as a training loop that logs its loss
runs), so the host clock around whole steps is the step time.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import flops, stats, trace


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    import bench
    from benchmark.reference import packed
    from benchmark.reference import qwen3 as ref
    from benchmark.serving import geometry

    config, workload = ctx["config"], ctx["workload"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    batch_size, seq = int(workload["batch"]), int(workload["seq"])
    if seq > bench.SEQ:
        raise ValueError(f"the measured path's RoPE table ends at {bench.SEQ}")
    built = bench.build_qlora_scan_step(
        config["vocab_size"], seed=seed,
        n_layer=config["num_hidden_layers"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"])
    rng = np.random.default_rng(int(seed))
    x = jnp.asarray(rng.integers(0, config["vocab_size"],
                                 (batch_size, seq)), jnp.int32)
    batch = (x, jnp.roll(x, -1, axis=1))
    key = jax.random.PRNGKey(seed)
    step = built.qstep.lower(built.lora, built.opt_state, built.qparams,
                             batch, key).compile()
    kernels = step.as_text().count("tpu_custom_call")
    lora, opt = built.lora, built.opt_state
    lora, opt, loss = step(lora, opt, built.qparams, batch, key)
    losses = [float(loss)]                  # the warm-up step: set-up

    marks, tracer = {}, None
    if ctx["trace"]:
        slice_s = min(float(workload["trace_slice_s"]), seconds)

        def traced_slice():
            time.sleep(max(0.0, (seconds - slice_s) / 2))
            with trace.capture(ctx["trace_dir"]) as m:
                time.sleep(slice_s)
            marks.update(m)

        tracer = threading.Thread(target=traced_slice, daemon=True)
    ctx["compiles"].window_open()
    setup_s = time.monotonic() - ctx["t_start"]
    if tracer is not None:
        tracer.start()
    t0 = time.monotonic()
    ends, steps = [], []
    while True:
        t_start = time.monotonic()
        # stop where the next whole step would end outside the window
        mean = (ends[-1] - t0) / len(ends) if ends else 0.0
        if t_start + mean > t0 + seconds:
            break
        wall_start = time.time()
        lora, opt, loss = step(lora, opt, built.qparams, batch, key)
        losses.append(float(loss))
        ends.append(time.monotonic())
        steps.append({"start_s": wall_start, "wall_s": ends[-1] - t_start,
                      "activities": {"dispatch and loss to host":
                                     ends[-1] - t_start}})
    ctx["compiles"].window_close(t0, t0 + seconds)
    if tracer is not None:
        tracer.join(timeout=300)
    device = ctx["describe_devices"]()
    n = sum(e <= t0 + seconds for e in ends)
    if n < 1:
        raise RuntimeError("no whole step finished inside the window")
    tokens_per_s = batch_size * seq * n / (ends[n - 1] - t0)
    step_s = [ends[0] - t0] + [ends[i] - ends[i - 1] for i in range(1, n)]

    m = flops.matmul_params(
        config["hidden_size"], config["intermediate_size"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], config["num_hidden_layers"],
        config["vocab_size"])
    f_tok = flops.qlora_flops_per_token(
        m, config["num_hidden_layers"], seq,
        config["num_attention_heads"] * config["head_dim"])

    # correctness, after the window: LoRA's B starts at zero, so the first
    # step's loss is the frozen base's own loss on this batch, which the
    # float32 reference computes from the dequantised NF4 weights
    reference = ref.Reference(geometry(built.cfg), packed.nf4_to_f32)
    stacked = built.qparams["blocks"]["block"]

    def blocks():
        return (jax.tree.map(lambda a: a[i], stacked)
                for i in range(built.cfg.n_layer))

    want = reference.mean_loss(
        built.qparams["tok_embed"]["embedding"],
        built.qparams["ln_f"]["scale"], blocks,
        np.asarray(x), np.asarray(batch[1]))
    tol = float(workload["loss_tolerance"])
    loss_ok = abs(losses[0] - want) <= tol
    finite = bool(np.isfinite(losses).all())
    flash_ok = kernels >= 3 or not ctx["on_chip"]
    notes = {
        "steps_in_window": n,
        "step_s": {"median": stats.median(step_s), "min": min(step_s),
                   "max": max(step_s)},
        "first_loss": losses[0], "last_loss": losses[-1],
        "reference_first_loss": want,
        "loss_tolerance": tol, "tpu_custom_calls": kernels,
        "params_total": built.n_total, "flops_per_token": f_tok,
        "nf4_build_s": built.quant_s,
        "check": {"ok": bool(loss_ok and finite and flash_ok),
                  "loss_ok": bool(loss_ok), "finite": finite,
                  "flash_kernels": flash_ok},
    }
    peak_flops = ctx["peaks"]()[0] if ctx["on_chip"] else None
    counters = {"achieved_flops_per_s": f_tok * tokens_per_s}
    if peak_flops:
        counters["peak_flops_per_s"] = peak_flops * len(ctx["devices"])
    return {"e2e": {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
            "notes": notes, "correct": notes["check"]["ok"],
            "attempted": n, "failed": 0, "device": device,
            "obs": {"requests": [], "counters": counters},
            "marks": marks, "steps": steps}
