"""One serving run of a model whose WINDOW layers' rings are LONGER than a
chunk (Arcee Trinity, ``models/afmoe.py``: a ring of 4,096 rows a slot
under 2,048-token chunks, rotary on the window layers and none on every
fourth, global, layer, a gate on the attention output, a shared expert
beside one chip's share of the routed ones): build the server users run
(``examples/serve_openai.py::build_server``, seeded bf16 weights), warm up
what the cell's length ranges can reach, drive the window over loopback
HTTP, then compare with ``benchmark/reference/afmoe.py``.

Warm-up, end-to-end reduction and the sampler are ``benchmark/serving.py``'s
by import; the window is ``serve_block_cell.SteadyLoop``, the tokenizer
that runner's ``letter_run_tokenizer``; the reading of a capture and of a
program's operations are ``serve_latent_cell``'s (``judged``,
``program_ops``) and the device plane's seconds by pattern
``serve_hybrid_cell``'s (``scope_seconds``), all imported as they are. The
configuration file gives the experts HELD under the published key
(``num_experts`` 32) and the router's width beside it (``router_experts``
256). The model is imported at the top of :func:`build`: a program without
``models/afmoe.py`` (the parent of the PR that added it) fails there, in
seconds, before any warm-up.

What this runner adds to the observation (``benchmark/metrics/``): the
window's deltas of the engine's step-statistics counters
(``serve/step_stats.py``: held-expert load; the global layer's attended
against viewed rows; the window layers' ring rows ATTENDED against the
ring rows READ; a chunk trip's real tokens against its width), the two
stores' bytes, and, in a traced run, ``scope_seconds`` (device seconds of
the two prefill kernels by their names on the device plane, with the
operations that put the ring in order; of the global decode attention by
the ``(slots, query heads, n)`` tensors only that path has; of the ring
decode path by the ring's own tensors) beside ``slice_work`` (what
``benchmark/flops_swa.py`` makes of the step records inside the slice:
true lengths, never view widths or ring rows read).

``check`` — after the window, at the cell's widths, through the timed
programs and no other (``notes.check_engine_compiles`` must be 0): a
1,536-token probe (its rings never wrap; one padded chunk) is submitted
and, once it DECODES, a 10,240-token one that chunk-prefills beside it in
fused mixed steps (five chunks, every ring wraps, the global view 16,384
wide); both emit 16 greedy tokens. For each probe the reference's float32
forward of prompt + tokens, teacher-forced, must agree on (a) the
prefill's last-position logits as the timed program returned them, (b)
every emitted token, (c) the routed sets at the 16 judged positions (the
prompt's last token first: the model reports a padded chunk's experts at
its last REAL position, and a near-tie there swaps a whole expert in the
logits that (a) reads).
Tolerances and their reasons: the reference's module.
"""

from __future__ import annotations

import re
import threading
import time

import numpy as np

from benchmark import flops_swa, serving, trace, traffic
from benchmark.runners.serve_block_cell import (
    SteadyLoop,
    letter_run_tokenizer,
)
from benchmark.runners.serve_hybrid_cell import scope_seconds
from benchmark.runners.serve_latent_cell import judged, program_ops

# the keys ``rehearsal.TINY`` does not know or gets wrong for this model
# (toy sizes, CPU only): 3 window layers to 1 global, a window longer than
# the toy chunk (64), a held share of the experts
REHEARSAL = {
    "num_hidden_layers": 4, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "router_experts": 32, "tie_word_embeddings": False,
    "rope_theta": 10000, "rms_norm_eps": 1e-05,
}
REHEARSAL_WORKLOAD = {
    "prompt_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.9,
                      "min": 24, "max": 208},
    "output_tokens": {"dist": "loguniform", "min": 8, "max": 32},
    "max_total_tokens": 256, "lead_in_s": 0.5,
}
PROBE_TOKENS = 16
PROBES = (1536, 10240)              # (decoding, chunk-prefilling beside it)
REHEARSAL_PROBES = (40, 192)
WINDOW_RING_PREFILL, GLOBAL_PREFILL, GLOBAL_DECODE, WINDOW_DECODE = (
    "window_ring_prefill_attention", "global_prefill_attention",
    "global_decode_attention", "window_decode_attention")
COUNTERS = ("window_rows_attended", "window_ring_rows_read",
            "global_tokens_attended", "global_view_tokens",
            "prefill_band_pairs", "prefill_band_keys_read",
            "prefill_global_pairs", "prefill_keys_read",
            "prefill_chunk_tokens", "prefill_chunk_capacity")


def model_config(config: dict):
    from llm_in_practise_tpu.models.afmoe import AfmoeConfig

    hf = dict(config, num_experts=config["router_experts"],
              experts_held=config["num_experts"],
              expert_offset=config["expert_offset"])
    return AfmoeConfig.from_hf_config(hf, compute_dtype="bfloat16")


def build(config: dict, seed: int, on_chip: bool) -> serving.Serving:
    # first of all: the parent of the PR that brought this model has no
    # such module and must fail here, before anything is built or warmed
    from llm_in_practise_tpu.models.afmoe import Afmoe, random_params

    import jax.numpy as jnp

    from benchmark.reference import afmoe as ref
    from examples import serve_openai
    from llm_in_practise_tpu.data.sft import IM_END

    if not on_chip:
        from llm_in_practise_tpu.ops import swa_attention as swa

        config = dict(config, **REHEARSAL)
        # a toy ring fits the dense corner, the cell's does not: the
        # rehearsal takes the cell's path (the ring through the kernel)
        swa.RING_CORNER_MAX = 0
    layout = config["layout"]
    cfg = model_config(config)
    params = random_params(cfg, seed, jnp.bfloat16)
    tok = letter_run_tokenizer(cfg.vocab_size)
    # a seeded head ends an answer by a coin flip, and one answer cut short
    # shifts the closed loop's whole schedule (serve_latent_cell.py): the
    # traffic states its output lengths, so that column is zero
    params["lm_head"] = params["lm_head"].at[:, tok.token_to_id(IM_END)].set(0)
    name = layout.get("model_name", "bench")
    parser = serve_openai.build_parser()
    args = parser.parse_args(["--model_name", name, "--host", "127.0.0.1",
                              "--port", "0", *layout["serve_args"]])
    serve_openai.validate_args(args, parser.error)
    server = serve_openai.build_server(
        args, tok, lambda mesh: (Afmoe(cfg), params), parser.error)
    port = server.serve(host=args.host, port=args.port, background=True)
    return serving.Serving(cfg, params, tok, server, server.engine, port,
                           name, ref.geometry(cfg))


def check(sv: serving.Serving, lengths, seed: int) -> dict:
    from benchmark.reference import afmoe as ref
    from llm_in_practise_tpu.serve.engine import SamplingParams

    eng, cfg = sv.engine, sv.cfg
    reference = ref.Reference(sv.geom)
    rng = np.random.default_rng([int(seed), 13])
    lengths = [int(n) for n in lengths]
    prompts = [rng.integers(4, cfg.vocab_size, n).tolist() for n in lengths]
    greedy = SamplingParams(temperature=0.0, greedy=True,
                            max_tokens=PROBE_TOKENS)
    eng.step_stats.capture = []
    try:
        short = eng.submit(prompts[0], greedy)
        head = short.next_item()        # the short probe now decodes
        long = eng.submit(prompts[1], greedy)
        emitted = [[head] + short.result() if isinstance(head, int) else [],
                   long.result()]
        # a request's last token is on its queue BEFORE the step that
        # emitted it has booked its statistics: the step holds the
        # engine's lock to its end
        with eng._lock:
            captured = eng.step_stats.capture
    finally:
        eng.step_stats.capture = None
    worst = {"rms_over_std": 0.0, "max_over_std": 0.0,
             "token_margin_over_std": 0.0}
    routing = {"pairs": 0, "flipped": 0, "outside_margin": 0,
               "worst_shortfall": 0.0}
    mixed_finish = any(c["kind"] == "mixed" and c["last_logits"]
                       for c in captured)
    for req, prompt, tokens in zip((short, long), prompts, emitted):
        got = judged(captured, req.uid)
        if (got is None or len(tokens) != PROBE_TOKENS
                or got[1].shape[1] != PROBE_TOKENS):
            return {"ok": False, "why": "a probe is incomplete",
                    "prompt_tokens": lengths,
                    "tokens": [len(t) for t in emitted]}
        logits, experts = got
        want, found = reference.logits(
            sv.params, prompt + tokens[:-1], last=PROBE_TOKENS,
            engine_experts=experts)
        err = ref.logit_error(logits, want[0])
        if "why" in err:
            return {"ok": False, "why": err["why"]}
        margin = ref.token_margins(want, tokens)["worst_margin_over_std"]
        worst["token_margin_over_std"] = max(
            worst["token_margin_over_std"], margin)
        for k in ("rms_over_std", "max_over_std"):
            worst[k] = max(worst[k], err[k])
        for k in ("pairs", "flipped", "outside_margin"):
            routing[k] += found[k]
        routing["worst_shortfall"] = max(routing["worst_shortfall"],
                                         found["worst_shortfall"])
    flip_share = routing["flipped"] / max(routing["pairs"], 1)
    ok = (mixed_finish
          and worst["rms_over_std"] <= ref.LOGIT_RMS_TOL
          and worst["max_over_std"] <= ref.LOGIT_MAX_TOL
          and worst["token_margin_over_std"] <= ref.TOKEN_MARGIN_TOL
          and routing["outside_margin"] == 0
          and flip_share <= ref.ROUTE_FLIP_SHARE_TOL)
    return {"ok": bool(ok), "prompt_tokens": lengths,
            "long_probe_ended_in_a_mixed_step": mixed_finish,
            "worst": worst, "routing": dict(routing, flip_share=flip_share),
            "tolerances": {
                "rms": ref.LOGIT_RMS_TOL, "max": ref.LOGIT_MAX_TOL,
                "token_margin": ref.TOKEN_MARGIN_TOL,
                "route_margin": ref.ROUTE_MARGIN,
                "route_flip_share": ref.ROUTE_FLIP_SHARE_TOL}}


def scope_patterns(cfg, eng) -> dict:
    """What the text of each path's instructions must hold (the device
    plane keeps no ``jax.named_scope``: an event is its instruction's text
    and three timings). The two prefill kernels: their custom calls, by
    the kernels' names, and for the window layers the operations that put
    ``[the ring ‖ the chunk's keys]`` in order for the kernel, by the
    ``(1, ring rows + chunk, K/V heads, 128)`` run only they hold (either
    order of its middle axes). The global decode attention: every
    operation that holds a ``(slots, query heads, n)`` tensor (the flat
    form of ``ops/swa_attention.py::decode_attention``). The ring decode
    path: every operation that holds the slot plane's ring ``(slots, ring
    rows, K/V heads, 128)`` (either order) or its scores ``(slots, K/V
    heads, group, ring rows)``: the write of the new row, the score
    einsum, the softmax fusions, the sum einsum."""
    from llm_in_practise_tpu.ops import swa_attention as swa

    slots, ring = int(eng.max_slots), int(eng.paged.ring_rows)
    run = ring + int(eng.chunked_prefill)
    h, hk, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    return {
        WINDOW_RING_PREFILL: re.compile(
            rf"{re.escape(swa.WINDOW_RING_KERNEL)}"
            rf"|\[1,{run},{hk},{d}\]|\[1,{hk},{run},{d}\]"),
        GLOBAL_PREFILL: re.compile(re.escape(swa.GLOBAL_KERNEL)),
        GLOBAL_DECODE: re.compile(rf"\[{slots},{h},\d+\]"),
        WINDOW_DECODE: re.compile(
            rf"\[{slots},{ring},{hk},{d}\]|\[{slots},{hk},{ring},{d}\]"
            rf"|\[{slots},{hk},{h // hk},{ring}\]"),
    }


def slice_work(steps: list[dict], marks: dict, cfg) -> dict:
    """What the steps inside the traced slice needed of the four
    attention paths, by ``flops_swa`` from the step records' true
    lengths."""
    t0, t1 = marks.get("begin_wall"), marks.get("end_wall")
    if t0 is None or t1 is None:
        return {}
    inside = [r for r in steps if t0 <= r["start_s"] < t1]

    def total(key):
        return sum(r.get(key, 0) for r in inside)

    n_window = sum(cfg.window_layers)
    n_global = cfg.n_layer - n_window
    out = {"steps": len(inside)}
    for name, pairs, keys, layers in (
            ("window_ring_prefill", "prefill_band_pairs",
             "prefill_band_keys_read", n_window),
            ("global_prefill", "prefill_global_pairs", "prefill_keys_read",
             n_global),
            ("global_decode", "global_tokens_attended",
             "global_tokens_attended", n_global),
            ("window_decode", "window_rows_attended",
             "window_rows_attended", n_window)):
        out[pairs], out[keys] = total(pairs), total(keys)
        out[name + "_flops"], out[name + "_bytes"] = (
            flops_swa.attention_cost(out[pairs], out[keys], layers,
                                     cfg.n_head, cfg.n_kv_head,
                                     cfg.head_dim, cfg.head_dim))
    return out


def stats_counters(eng) -> dict:
    st = eng.step_stats
    # a program without one of these counters (none today) leaves it out
    return dict(st.load.counters(), **{
        k: getattr(st, k) for k in COUNTERS if hasattr(st, k)})


def run(ctx: dict) -> dict:
    workload, seed, seconds = ctx["workload"], ctx["seed"], ctx["seconds"]
    probes = PROBES
    if not ctx["on_chip"]:
        # a rehearsal's cache is 256 tokens: the toy cell keeps the
        # shape (short and long prompts in one queue, a window between)
        workload = dict(workload, **REHEARSAL_WORKLOAD)
        probes = REHEARSAL_PROBES
    sv = build(ctx["config"], seed, ctx["on_chip"])
    try:
        eng = sv.engine
        warmed = serving.warm(sv, workload, seed)
        t_write = time.monotonic()
        work = serving.write_prompts(
            sv, traffic.plan(workload, seconds, seed), seed)
        write_s = time.monotonic() - t_write
        sampler = serving.Sampler(eng) if ctx["trace"] else None
        marks, tracer = {}, None
        if ctx["trace"]:
            slice_s = min(float(workload["trace_slice_s"]), seconds)

            def traced_slice():
                time.sleep((seconds - slice_s) / 2)
                with trace.capture(ctx["trace_dir"]) as m:
                    time.sleep(slice_s)
                marks.update(m)

            tracer = threading.Thread(target=traced_slice, daemon=True)
        loop = SteadyLoop(sv, workload, work, seconds)
        loop.wait_open()            # the lead-in is set-up
        step0 = eng.steptrace.snapshot()
        stats0 = stats_counters(eng)
        ctx["compiles"].window_open()
        setup_s = time.monotonic() - ctx["t_start"]
        if tracer is not None:
            tracer.start()
            sampler.start(loop.t0, loop.t_end)      # traced runs have both
        loop.wait_close()
        stats1 = stats_counters(eng)
        step1 = eng.steptrace.snapshot()
        ctx["compiles"].window_close(loop.t0, loop.t_end)
        window, lead_in = loop.drain()
        if tracer is not None:
            tracer.join(timeout=120)
            window.samples = sampler.stop()
        grace_s = time.monotonic() - loop.t_end
        device = ctx["describe_devices"]()
        e2e, notes = serving.end_to_end(window, workload)
        # the lead-in's requests: their tokens that arrived inside the
        # window were served inside it, and one of them that failed is a
        # failed operation of the run (serve_block_cell.py)
        carried = sum(loop.t0 <= t <= loop.t_end
                      for o in lead_in for t in o.token_times)
        e2e["serve_tokens_per_s"] += carried / seconds
        # beside the p95, for the per-layer ``ttft_*_window_ms`` pair (the
        # cell's TTFT tail is not held to a bound)
        e2e["ttft_median_ms"] = notes["ttft_ms"]["median"]
        notes["tokens_received_in_window"] += carried
        notes["attempted"] += len(lead_in)
        notes["failed"] += sum(not o.ok for o in lead_in)
        done = sorted(o.t_done - o.t_due for o in lead_in + window.outcomes
                      if o.t_done is not None)
        notes["lead_in"] = {"seconds": float(workload["lead_in_s"]),
                            "requests": len(lead_in),
                            "tokens_carried_into_window": carried,
                            # the rule behind lead_in_s (the workload's why)
                            "median_request_lifetime_s":
                                done[len(done) // 2] if done else None}
        notes["warm_up"] = warmed
        notes["write_prompts_s"] = write_s
        notes["prompt_tokens_written"] = sum(p.prompt_tokens
                                             for p, _ in work)
        notes["grace_and_trace_stop_s"] = grace_s
        notes["preemptions"] = eng.preemptions
        notes["engine_compile_events_total"] = eng.compile_meter.compile_events
        wall, dev = (step1[k] - step0[k] for k in (
            "step_wall_seconds_total", "device_seconds_total"))
        counters = {"step_wall_s": wall, "step_device_s": dev,
                    "step_host_s": wall - dev}
        counters.update({k: stats1[k] - stats0[k] for k in stats1})
        cfg = sv.cfg
        counters["moe_held_expert_slots"] = (counters["moe_layer_passes"]
                                             * cfg.held[1])
        counters["kv_window_state_bytes"] = eng.paged.slot_state_bytes
        notes["kv"] = {"row_bytes": eng.paged.row_bytes,
                       "slot_bytes": eng.paged.slot_bytes,
                       "pool_bytes": eng.paged.pool_bytes,
                       "window_state_bytes": eng.paged.slot_state_bytes,
                       "ring_rows": eng.paged.ring_rows}
        obs = {"requests": [], "counters": counters,
               "device_kind": ctx["devices"][0].device_kind}
        steps = eng.steptrace.records(limit=eng.steptrace.capacity)
        if sampler is not None:
            s = window.samples
            obs["requests"] = s["finished_cp"]
            counters["pool_pages_peak"] = max(s["pool_pages_used"])
            counters["pool_pages"] = s["pool_pages"]
            counters["kv_cache_bytes_peak"] = (
                eng.paged.slot_state_bytes
                + counters["pool_pages_peak"] * eng.paged.page_bytes)
            events = trace.load(trace.newest_xplane(ctx["trace_dir"]),
                                not ctx["on_chip"])
            obs["scope_seconds"], notes["scope_ops"] = scope_seconds(
                events, not ctx["on_chip"], scope_patterns(cfg, eng))
            notes["decode_step_ops"] = program_ops(
                events, "jit__paged_decode_fn", n=24)
            notes["mixed_step_ops"] = program_ops(
                events, "jit__paged_mixed_fn", n=24)
            del events
            obs["slice_work"] = slice_work(steps, marks, cfg)
            notes["scope_seconds"] = obs["scope_seconds"]
            notes["slice_work"] = obs["slice_work"]
        notes["step_stats"] = dict(counters)
        built = eng.compile_meter.compile_events
        t_check = time.monotonic()
        checked = check(sv, probes, seed)
        notes["check"] = checked
        notes["check_s"] = time.monotonic() - t_check
        # the probes ride the window's own executables: nothing is built
        notes["check_engine_compiles"] = (eng.compile_meter.compile_events
                                          - built)
        if notes["check_engine_compiles"]:
            checked = dict(checked, ok=False,
                           why="the check built a program of its own")
            notes["check"] = checked
    finally:
        sv.close()
    e2e["setup_s"] = setup_s
    requests = [{"index": o.index, "prompt_tokens": o.prompt_tokens,
                 "tokens": o.tokens, "due_s": o.t_due - window.t0,
                 "ttft_s": o.ttft_s(), "tpot_s": o.tpot_s(),
                 "done_s": None if o.t_done is None else o.t_done - window.t0,
                 "finish_reason": o.finish_reason, "error": o.error}
                for o in lead_in + window.outcomes]
    return {"e2e": e2e, "notes": notes, "correct": checked["ok"],
            "attempted": notes["attempted"], "failed": notes["failed"],
            "device": device, "obs": obs, "marks": marks, "steps": steps,
            "requests": requests}
