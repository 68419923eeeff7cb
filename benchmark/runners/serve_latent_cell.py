"""One serving run of a LATENT-CACHE, ROUTED model (DeepSeek-V3): build the
server users run (``examples/serve_openai.py::build_server`` with
``models/deepseek_v3.py``'s model and seeded bf16 weights, one chip's share
of its routed experts), warm up what the cell's length ranges can reach,
drive the window over loopback HTTP, then compare with
``benchmark/reference/deepseek_v3.py``.

Warm-up, end-to-end reduction and the sampler are ``benchmark/serving.py``'s
by import; the window is ``serve_block_cell.SteadyLoop`` (the callers are
already in flight when it opens; the lead-in is set-up) and the tokenizer
that runner's ``letter_run_tokenizer`` (the stock 381-entry BPE spells the
prompts letter by letter, and every row then routes alike), both imported
as they are. The configuration file gives the experts HELD under the
published key (``n_routed_experts`` 16, the guide's convention) and the
router's width beside it (``router_experts`` 256): :func:`build` hands
the model ``n_routed_experts`` = the router's width and ``experts_held`` /
``expert_offset``.

What this runner adds to the observation, for the four metrics the cell
brought (``benchmark/metrics/``): the window's deltas of the engine's
step-statistics counters (``serve/step_stats.py``: held-expert load counted
on the device by every routed layer of every decode step, chunk row and
mixed step; attended against viewed cache rows of the decode steps);
and, in a traced run, ``scope_seconds`` (device seconds of the two
attention paths' operations, found in the trace file by what their
instructions' text holds: :func:`scope_seconds`) beside
``slice_work`` (what ``benchmark/flops_mla.py`` makes of the step records
that fall inside the slice: true lengths, never view widths). A program
without ``engine.step_stats`` fails at :func:`build`: it has no such
model.

``check`` — after the window, at the cell's widths, through the timed
programs and no other (``notes.check_engine_compiles`` must be 0): a
short probe (the smallest multiple of the chunk in the cell's range) is
submitted and, once it DECODES, a long one (twice that or more) that
chunk-prefills beside it in fused mixed steps; both emit 16 greedy
tokens. ``engine.step_stats.capture`` makes the step keep what the
programs return anyway: the last-position logits of the program that ends
a prompt, and the experts each row's last position chose. The probes'
lengths are whole chunks, so a prompt's last position is its last chunk's.
For each probe the reference's float32 forward of prompt + tokens,
teacher-forced, must agree on (a) the prefill's last-position logits (rms /
max in units of their spread), (b) every emitted token (its reference
logit within a margin of the reference's best), (c) the routed sets at the
16 judged positions (flips inside a margin and bounded in number; the
reference takes the engine's set at those pairs only). Tolerances and
their reasons: the reference's module.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import flops_mla, serving, trace, traffic
from benchmark.runners.serve_block_cell import (
    SteadyLoop,
    letter_run_tokenizer,
)

# the keys ``rehearsal.TINY`` does not know (toy sizes, CPU only)
REHEARSAL = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "moe_intermediate_size": 32, "n_routed_experts": 8,
    "router_experts": 32, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "num_key_value_heads": 4, "tie_word_embeddings": False,
}
REHEARSAL_WORKLOAD = {
    "prompt_tokens": {"dist": "loguniform", "min": 64, "max": 208},
    "output_tokens": {"dist": "loguniform", "min": 8, "max": 32},
    "max_total_tokens": 256, "lead_in_s": 0.5,
}
PROBE_TOKENS = 16


def model_config(config: dict):
    from llm_in_practise_tpu.models.deepseek_v3 import DeepSeekV3Config

    hf = dict(config, n_routed_experts=config["router_experts"],
              experts_held=config["n_routed_experts"],
              expert_offset=config["expert_offset"])
    return DeepSeekV3Config.from_hf_config(hf, compute_dtype="bfloat16")


def build(config: dict, seed: int, on_chip: bool) -> serving.Serving:
    import jax.numpy as jnp

    from benchmark.reference import deepseek_v3 as ref
    from examples import serve_openai
    from llm_in_practise_tpu.data.sft import IM_END
    from llm_in_practise_tpu.models.deepseek_v3 import (
        DeepSeekV3, random_params,
    )

    if not on_chip:
        config = dict(config, **REHEARSAL)
        config["rope_scaling"] = dict(
            config["rope_scaling"], factor=4,
            original_max_position_embeddings=64)
    layout = config["layout"]
    cfg = model_config(config)
    params = random_params(cfg, seed, jnp.bfloat16)
    tok = letter_run_tokenizer(cfg.vocab_size)
    # A seeded head ends an answer by a coin flip (its end-of-sequence
    # column is a random direction: 2 stops in one window of four, my chip
    # runs, PR 34), and one answer cut short shifts the closed loop's whole
    # schedule, which the 33 TTFTs of a window cannot absorb. The traffic
    # states its output lengths, so that column is zero: greedy never
    # chooses it, and every answer ends at its ``max_tokens``.
    params["lm_head"] = params["lm_head"].at[:, tok.token_to_id(IM_END)].set(0)
    name = layout.get("model_name", "bench")
    parser = serve_openai.build_parser()
    args = parser.parse_args(["--model_name", name, "--host", "127.0.0.1",
                              "--port", "0", *layout["serve_args"]])
    serve_openai.validate_args(args, parser.error)
    server = serve_openai.build_server(
        args, tok, lambda mesh: (DeepSeekV3(cfg), params), parser.error)
    port = server.serve(host=args.host, port=args.port, background=True)
    return serving.Serving(cfg, params, tok, server, server.engine, port,
                           name, ref.geometry(cfg))


def probe_lengths(workload: dict, chunk: int) -> list[int]:
    """(short, long): whole chunks inside the cell's range, the short one
    of two chunks at least (a prompt no longer than a chunk prefills in
    one shot), the long one twice the short where the range allows."""
    pr = workload["prompt_tokens"]
    lo, hi = int(pr["min"]), int(pr["max"])
    short = max(-(-lo // chunk), 2) * chunk
    return [short, min(hi // chunk * chunk, max(2 * short, short + chunk))]


def judged(captured: list, uid: int):
    """What the capture holds for one probe: its prefill's last-position
    logits, and per routed layer the experts chosen at its judged
    positions (the prompt's last, then each decoded position), in
    order. None where the capture is incomplete."""
    logits, routes = None, []
    for c in captured:
        slot = next((s for s, u in c["uids"].items() if u == uid), None)
        if slot is None:
            continue
        if logits is None:
            if slot in c["last_logits"]:        # the program that ended it
                logits = c["last_logits"][slot]
                routes.append(c["route"][0][:, slot])
        elif c["kind"] in ("decode", "mixed"):
            routes.append(c["route"][-1][:, slot])
    if logits is None:
        return None
    return logits, np.stack(routes, axis=1)     # (layers, positions, k)


def check(sv: serving.Serving, workload: dict, seed: int) -> dict:
    from benchmark.reference import deepseek_v3 as ref
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


def _first_device_plane(events: list) -> str | None:
    return min((e.plane for e in events if e.plane.startswith("/device:")),
               default=None)


def scope_seconds(events: list, rehearsal: bool, slots: int,
                  heads: int) -> tuple[dict, dict]:
    """Device seconds, inside the traced slice, of the two attention
    paths' operations (``events``: ``trace.load`` of the run's trace file,
    before ``run.py`` reduces it). The device plane keeps no ``jax.named_scope``: an
    event is its HLO instruction's text and three timing stats, nothing of
    ``op_name`` (my chip run, PR 34). So each path is found by what its
    instructions' text must hold:

    - ``mla_prefill_attention``: the Pallas flash kernel's custom calls,
      by the kernel's name (``ops/mla_attention.py::KERNEL_NAME``), as
      ``moe_grouped_matmul_roofline`` finds ``gmm``. The decompression of
      a key block and the join of the blocks' partial sums are NOT in the
      seconds (their fusions carry no name): the share reads the kernel.
    - ``mla_decode_attention``: every operation whose text holds a
      ``(slots, heads, n)`` tensor (the absorbed queries, the scores and
      probabilities over the view, the latent sums): the score einsum, the
      softmax fusions and the sum einsum. No other tensor of the program
      has that shape.

    Also, for the notes, each path's five longest operations with their
    seconds. A trace without such operations gives empty dicts."""
    import re

    from llm_in_practise_tpu.ops.mla_attention import KERNEL_NAME

    patterns = {
        "mla_prefill_attention": re.compile(re.escape(KERNEL_NAME)),
        "mla_decode_attention": re.compile(
            rf"\[{int(slots)},{int(heads)},\d+\]"),
    }
    marks = {e.name: e.start_ns for e in events
             if e.name in (trace.BEGIN, trace.END)}
    w0 = marks.get(trace.BEGIN, float("-inf"))
    w1 = marks.get(trace.END, float("inf"))
    first = _first_device_plane(events)
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


def program_ops(events: list, program: str, n: int = 14) -> dict:
    """For the notes (PERF.md section 5): the operations of the LAST whole
    run of ``program`` in the trace, the longest ``n`` with their
    milliseconds, loops left out as above."""
    first = _first_device_plane(events)
    runs = [e for e in events if e.plane == first
            and e.line == trace.MODULE_LINE
            and trace.program_name(e.name) == program]
    if len(runs) < 2:
        return {}
    run = runs[-2]      # the last may be cut short by the capture's end
    ops: dict[str, float] = {}
    for e in events:
        if (e.plane == first and e.line == trace.OP_LINE
                and run.start_ns <= e.start_ns < run.start_ns + run.dur_ns
                and " while(" not in e.name):
            name = trace.op_name(e.name)
            ops[name] = ops.get(name, 0.0) + e.dur_ns * 1e-6
    return {"run_ms": run.dur_ns * 1e-6, "ops_ms": trace.top(ops, n),
            "ops": len(ops), "ops_total_ms": sum(ops.values())}


def slice_work(steps: list[dict], marks: dict, cfg) -> dict:
    """What the steps inside the traced slice needed of the two attention
    paths, by ``flops_mla`` from the step records' true lengths."""
    t0, t1 = marks.get("begin_wall"), marks.get("end_wall")
    if t0 is None or t1 is None:
        return {}
    inside = [r for r in steps if t0 <= r["start_s"] < t1]
    attended = sum(r.get("latent_tokens_attended", 0) for r in inside)
    pairs = sum(r.get("prefill_qk_pairs", 0) for r in inside)
    keys = sum(r.get("prefill_keys_read", 0) for r in inside)
    d_flops, d_bytes = flops_mla.decode_cost(
        attended, cfg.n_layer, cfg.n_head, cfg.kv_lora_rank,
        cfg.qk_rope_head_dim)
    p_flops, p_bytes = flops_mla.prefill_cost(
        pairs, keys, cfg.n_layer, cfg.n_head, cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
    return {"steps": len(inside), "latent_tokens_attended": attended,
            "prefill_qk_pairs": pairs, "prefill_keys_read": keys,
            "mla_decode_flops": d_flops, "mla_decode_bytes": d_bytes,
            "mla_prefill_flops": p_flops, "mla_prefill_bytes": p_bytes}


def stats_counters(eng) -> dict:
    st = eng.step_stats
    return dict(st.load.counters(),
                latent_tokens_attended=st.latent_tokens_attended,
                latent_view_tokens=st.latent_view_tokens,
                prefill_qk_pairs=st.prefill_qk_pairs,
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
        wall = step1["step_wall_seconds_total"] - step0["step_wall_seconds_total"]
        dev = step1["device_seconds_total"] - step0["device_seconds_total"]
        counters = {"step_wall_s": wall, "step_device_s": dev,
                    "step_host_s": wall - dev}
        counters.update({k: stats1[k] - stats0[k] for k in stats1})
        cfg = sv.cfg
        counters["moe_held_expert_slots"] = (counters["moe_layer_passes"]
                                             * cfg.held[1])
        notes["step_stats"] = dict(counters)
        obs = {"requests": [], "counters": counters,
               "device_kind": ctx["devices"][0].device_kind}
        steps = eng.steptrace.records(limit=eng.steptrace.capacity)
        if sampler is not None:
            s = window.samples
            obs["requests"] = s["finished_cp"]
            counters["pool_pages_peak"] = max(s["pool_pages_used"])
            counters["pool_pages"] = s["pool_pages"]
            pre = sorted(cp.get("api_pre_submit", 0.0)
                         for cp in s["finished_cp"])
            notes["api_pre_submit_s"] = ({"n": len(pre), "median":
                                          pre[len(pre) // 2], "max": pre[-1]}
                                         if pre else None)
            events = trace.load(trace.newest_xplane(ctx["trace_dir"]),
                                not ctx["on_chip"])
            obs["scope_seconds"], notes["scope_ops"] = scope_seconds(
                events, not ctx["on_chip"], eng.max_slots, cfg.n_head)
            notes["decode_step_ops"] = program_ops(
                events, "jit__paged_decode_fn")
            del events
            obs["slice_work"] = slice_work(steps, marks, cfg)
            notes["scope_seconds"] = obs["scope_seconds"]
            notes["slice_work"] = obs["slice_work"]
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
