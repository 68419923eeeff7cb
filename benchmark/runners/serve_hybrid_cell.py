"""One serving run of a model that mixes WINDOW and GLOBAL attention layers
over routed experts (MiMo-V2): build the server users run
(``examples/serve_openai.py::build_server`` with ``models/mimo_v2.py``'s
model and seeded bf16 weights, one chip's share of its routed experts),
warm up what the cell's length ranges can reach, drive the window over
loopback HTTP, then compare with ``benchmark/reference/mimo_v2.py``.

Warm-up, end-to-end reduction and the sampler are ``benchmark/serving.py``'s
by import; the window is ``serve_block_cell.SteadyLoop`` and the tokenizer
that runner's ``letter_run_tokenizer``; the reading of a capture and of a
program's operations are ``serve_latent_cell``'s (``judged``,
``program_ops``), all imported as they are. The configuration file gives
the experts HELD under the published key (``n_routed_experts`` 16) and the
router's width beside it (``router_experts`` 256). The model is imported
at the top of :func:`build`: a program without ``models/mimo_v2.py`` (the
parent of the PR that added it) fails there, in seconds, before any
warm-up.

What this runner adds to the observation, for the metrics the cell brought
(``benchmark/metrics/``): the window's deltas of the engine's
step-statistics counters (``serve/step_stats.py``: held-expert load; the
global layers' attended against viewed cache rows; the window layers' ring
rows), the two stores' bytes (``PagedKV.slot_state_bytes`` beside the
pool's pages at their peak), and, in a traced run, ``scope_seconds``
(device seconds of the two prefill kernels by their names on the device
plane, and of the global decode attention's operations by the ``(slots,
query heads, n)`` tensors only that path has) beside
``slice_work`` (what ``benchmark/flops_swa.py`` makes of the step records
that fall inside the slice: true lengths, never view widths).

``check`` — after the window, at the cell's widths, through the timed
programs and no other (``notes.check_engine_compiles`` must be 0): a short
probe (two chunks) is submitted and, once it DECODES, a long one (three
times that) that chunk-prefills beside it in fused mixed steps, so every
window ring wraps dozens of times and the global view is four chunks wide
and more; both emit 16 greedy tokens. For each probe the reference's
float32 forward of prompt + tokens, teacher-forced, must agree on (a) the
prefill's last-position logits as the timed program returned them (rms /
max in units of their spread), (b) every emitted token (its reference
logit within a margin of the reference's best), (c) the routed sets at the
16 judged positions. Tolerances and their reasons: the reference's module.
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
from benchmark.runners.serve_latent_cell import judged, program_ops

# the keys ``rehearsal.TINY`` does not know or gets wrong for this model
# (toy sizes, CPU only): keys wider than values, two K/V head counts, a
# window far shorter than the rows, a held share of the experts
REHEARSAL = {
    "num_hidden_layers": 4, "hybrid_layer_pattern": [0, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1], "num_attention_heads": 8,
    "swa_num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 24, "swa_head_dim": 24,
    "v_head_dim": 16, "swa_v_head_dim": 16, "sliding_window": 8,
    "sliding_window_size": 8, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "router_experts": 32, "num_experts_per_tok": 4,
    "tie_word_embeddings": False,
}
REHEARSAL_WORKLOAD = {
    "prompt_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                      "min": 64, "max": 208},
    "output_tokens": {"dist": "loguniform", "min": 8, "max": 32},
    "max_total_tokens": 256, "lead_in_s": 0.5,
}
PROBE_TOKENS = 16
WINDOW_PREFILL, GLOBAL_PREFILL, GLOBAL_DECODE = (
    "window_prefill_attention", "global_prefill_attention",
    "global_decode_attention")


def model_config(config: dict):
    from llm_in_practise_tpu.models.mimo_v2 import MiMoV2Config

    hf = dict(config, n_routed_experts=config["router_experts"],
              experts_held=config["n_routed_experts"],
              expert_offset=config["expert_offset"])
    return MiMoV2Config.from_hf_config(hf, compute_dtype="bfloat16")


def build(config: dict, seed: int, on_chip: bool) -> serving.Serving:
    # first of all: the parent of the PR that brought this model has no
    # such module and must fail here, before anything is built or warmed
    from llm_in_practise_tpu.models.mimo_v2 import MiMoV2, random_params

    import jax.numpy as jnp

    from benchmark.reference import mimo_v2 as ref
    from examples import serve_openai
    from llm_in_practise_tpu.data.sft import IM_END

    if not on_chip:
        config = dict(config, **REHEARSAL)
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
        args, tok, lambda mesh: (MiMoV2(cfg), params), parser.error)
    port = server.serve(host=args.host, port=args.port, background=True)
    return serving.Serving(cfg, params, tok, server, server.engine, port,
                           name, ref.geometry(cfg))


def probe_lengths(workload: dict, chunk: int) -> list[int]:
    """(short, long): whole chunks inside the cell's range, the short one
    of two chunks at least (a prompt no longer than a chunk is one trip),
    the long one three times the short where the range allows."""
    pr = workload["prompt_tokens"]
    lo, hi = int(pr["min"]), int(pr["max"])
    short = max(-(-lo // chunk), 2) * chunk
    return [short, min(hi // chunk * chunk, max(3 * short, short + chunk))]


def check(sv: serving.Serving, workload: dict, seed: int) -> dict:
    from benchmark.reference import mimo_v2 as ref
    from llm_in_practise_tpu.serve.engine import SamplingParams

    eng, cfg = sv.engine, sv.cfg
    reference = ref.Reference(sv.geom)
    rng = np.random.default_rng([int(seed), 13])
    lengths = probe_lengths(workload, eng.chunked_prefill)
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


def scope_patterns(cfg, slots: int) -> dict:
    """What the text of each path's instructions must hold (the device
    plane keeps no ``jax.named_scope``: an event is its instruction's text
    and three timings). The two prefill kernels: their custom calls, by
    the kernels' names. The global decode attention: every operation that
    holds a ``(slots, query heads, n)`` tensor: the widened queries, the
    score einsum over the flat view, the softmax fusions, the sum einsum
    and the pick of each head's own columns (a window layer's are
    ``(slots, 8, 8, 128)``; no other tensor of the program has that
    shape)."""
    from llm_in_practise_tpu.ops import swa_attention as swa

    return {
        WINDOW_PREFILL: re.compile(re.escape(swa.WINDOW_KERNEL)),
        GLOBAL_PREFILL: re.compile(re.escape(swa.GLOBAL_KERNEL)),
        GLOBAL_DECODE: re.compile(rf"\[{int(slots)},{cfg.n_head},\d+\]"),
    }


def scope_seconds(events: list, rehearsal: bool,
                  patterns: dict) -> tuple[dict, dict]:
    """Device seconds, inside the traced slice, of each path's operations
    (``events``: ``trace.load`` of the run's trace file), and for the
    notes each path's five longest operations. A trace without such
    operations (the parent's) gives empty dicts."""
    marks = {e.name: e.start_ns for e in events
             if e.name in (trace.BEGIN, trace.END)}
    w0 = marks.get(trace.BEGIN, float("-inf"))
    w1 = marks.get(trace.END, float("inf"))
    first = min((e.plane for e in events if e.plane.startswith("/device:")),
                default=None)
    seconds, ops = {}, {}
    for e in events:
        if e.plane != first or e.name in marks or (
                e.line != trace.OP_LINE and not rehearsal):
            continue
        a = max(e.start_ns, w0)
        b = min(e.start_ns + e.dur_ns, w1)
        if b <= a or " while(" in e.name:
            continue        # a loop's own event spans its body's
        for scope, pattern in patterns.items():
            if pattern.search(e.name):
                seconds[scope] = seconds.get(scope, 0.0) + (b - a) * 1e-9
                mine = ops.setdefault(scope, {})
                name = trace.op_name(e.name)
                mine[name] = mine.get(name, 0.0) + (b - a) * 1e-9
    return seconds, {k: trace.top(v, 5) for k, v in ops.items()}


def slice_work(steps: list[dict], marks: dict, cfg) -> dict:
    """What the steps inside the traced slice needed of the three
    attention paths, by ``flops_swa`` from the step records' true
    lengths."""
    t0, t1 = marks.get("begin_wall"), marks.get("end_wall")
    if t0 is None or t1 is None:
        return {}
    inside = [r for r in steps if t0 <= r["start_s"] < t1]

    def total(key):
        return sum(r.get(key, 0) for r in inside)

    n_window = sum(cfg.hybrid_layer_pattern)
    n_global = cfg.n_layer - n_window
    sizes = (cfg.n_head, cfg.head_dim, cfg.v_head_dim)
    out = {"steps": len(inside)}
    for name, pairs, keys, layers, kv_heads in (
            ("window_prefill", "prefill_band_pairs",
             "prefill_band_keys_read", n_window, cfg.swa_n_kv_head),
            ("global_prefill", "prefill_global_pairs", "prefill_keys_read",
             n_global, cfg.n_kv_head),
            ("global_decode", "global_tokens_attended",
             "global_tokens_attended", n_global, cfg.n_kv_head)):
        out[pairs], out[keys] = total(pairs), total(keys)
        out[name + "_flops"], out[name + "_bytes"] = (
            flops_swa.attention_cost(out[pairs], out[keys], layers,
                                     sizes[0], kv_heads, *sizes[1:]))
    return out


def stats_counters(eng) -> dict:
    st = eng.step_stats
    return dict(st.load.counters(),
                window_rows_attended=st.window_rows_attended,
                global_tokens_attended=st.global_tokens_attended,
                global_view_tokens=st.global_view_tokens,
                prefill_band_pairs=st.prefill_band_pairs,
                prefill_global_pairs=st.prefill_global_pairs,
                prefill_keys_read=st.prefill_keys_read)


def run(ctx: dict) -> dict:
    workload, seed, seconds = ctx["workload"], ctx["seed"], ctx["seconds"]
    if not ctx["on_chip"]:
        # a rehearsal's cache is 256 tokens: the toy cell keeps the
        # shape (every prompt chunks, outputs a fraction of prompts)
        workload = dict(workload, **REHEARSAL_WORKLOAD)
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
        # cell's TTFT tail is not held to a bound: PERF.md section 6)
        e2e["ttft_median_ms"] = notes["ttft_ms"]["median"]
        notes["tokens_received_in_window"] += carried
        notes["attempted"] += len(lead_in)
        notes["failed"] += sum(not o.ok for o in lead_in)
        notes["lead_in"] = {"seconds": float(workload["lead_in_s"]),
                            "requests": len(lead_in),
                            "tokens_carried_into_window": carried}
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
                       "window_state_bytes": eng.paged.slot_state_bytes}
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
                events, not ctx["on_chip"],
                scope_patterns(cfg, eng.max_slots))
            notes["decode_step_ops"] = program_ops(
                events, "jit__paged_decode_fn")
            del events
            obs["slice_work"] = slice_work(steps, marks, cfg)
            notes["scope_seconds"] = obs["scope_seconds"]
            notes["slice_work"] = obs["slice_work"]
        notes["step_stats"] = dict(counters)
        built = eng.compile_meter.compile_events
        t_check = time.monotonic()
        checked = check(sv, workload, seed)
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
