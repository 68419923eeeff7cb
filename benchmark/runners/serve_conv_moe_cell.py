"""One serving run of a model whose mixers are gated SHORT CONVOLUTIONS with
a two-row tail held by slot, every fourth layer paged grouped-query
attention, and 64 routed experts all held behind a sigmoid router with a
selection bias (LFM2-24B-A2B, ``models/lfm2_moe.py``: the first 10 of 40
layers, every width, expert and vocabulary row as published): build the
server users run (``examples/serve_openai.py::build_server``, seeded bf16
weights), warm up what the cell's length ranges can reach, drive the window
over loopback HTTP, then compare with ``benchmark/reference/lfm2_moe.py``.

Warm-up, end-to-end reduction and the sampler are ``benchmark/serving.py``'s
by import; the window is ``serve_block_cell.SteadyLoop``, the tokenizer that
runner's ``letter_run_tokenizer``; a program's operations (``program_ops``)
are ``serve_latent_cell``'s, the device plane's seconds by pattern
``serve_hybrid_cell``'s (``scope_seconds``) and the pick of the program that
ended a probe's prompt ``serve_recurrent_cell``'s (``judged``), imported as
they are. The model is imported at the top of :func:`build`: a program
without ``models/lfm2_moe.py`` (the parent of the PR that added it) fails
there, in seconds, before any warm-up.

What this runner adds to the observation (``benchmark/metrics/``): the
window's deltas of the engine's step-statistics counters
(``serve/step_stats.py``: tail rows advanced and held, the attention layers'
rows attended and pages read, a chunk trip's fill, the routed layers'
assignments and experts touched), the stores' bytes, the whole step's model
FLOPs a second against the published peak
(``benchmark/flops_lfm2.py::step_flops``), and, in a traced run,
``scope_seconds`` (device seconds of the grouped expert matmul's custom
calls and of the two attention kernels, by their names on the device plane)
beside ``slice_work`` (what ``flops_moe`` / ``flops_lfm2`` make of the step
records inside the slice).

``check`` — after the window, at the cell's widths, through the timed
programs and no other (``notes.check_engine_compiles`` must be 0), with
EVERY slot live: thirty fillers (512-token prompts, 128 tokens each) are
submitted and decode; a 1,536-token probe is submitted and, once it DECODES,
a 3,584-token one that chunk-prefills beside the thirty-one in fused mixed
steps (four chunks of 1,024, the last half padding: the tail crosses three
chunk boundaries and one padded stretch); both emit 16 greedy tokens. For
each probe the reference's float32 forward of prompt + tokens,
teacher-forced and computing the PROGRAM's experts at the 16 judged
positions (the reference's module says why), must agree on (a) the
prefill's last-position logits as the timed program returned them, (b) every
emitted token, by the reference's logits at the judged positions, (c) the
routed sets the programs chose there against the reference's OWN choice,
where its margin is clear, and (d) what the probe's slot HOLDS: every conv
layer's two-row tail when it is done, and both attention layers' rows of the
prompt AND of the tokens after it (copied while the request is live:
``Capture``), overall, at their worst row and at their median row; and one
filler's tails after its 128 one-position updates. Limits, their two
readings each and which planted fault or lower precision each one catches:
the reference's module.
"""

from __future__ import annotations

import re
import threading
import time

import numpy as np

from benchmark import flops_lfm2, flops_moe, serving, trace, traffic
from benchmark.runners.serve_block_cell import (
    SteadyLoop,
    letter_run_tokenizer,
)
from benchmark.runners.serve_hybrid_cell import scope_seconds
from benchmark.runners.serve_latent_cell import program_ops
from benchmark.runners.serve_recurrent_cell import judged

# the keys ``rehearsal.TINY`` does not know or gets wrong for this model
# (toy sizes, CPU only): the model's SHAPE, six layers, two dense, 8 experts
REHEARSAL = {
    "num_hidden_layers": 6, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv"],
    "moe_intermediate_size": 64, "num_experts": 8, "num_experts_per_tok": 2,
}
REHEARSAL_WORKLOAD = {
    "prompt_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                      "min": 16, "max": 180},
    "output_tokens": {"dist": "loguniform", "min": 8, "max": 32},
    "max_total_tokens": 256, "lead_in_s": 0.5,
}
PROBE_TOKENS = 16
PROBES = (1536, 3584)               # (decoding, chunk-prefilling beside it)
REHEARSAL_PROBES = (40, 168)
# the requests that hold every other slot while the probes run: (prompt
# tokens, output tokens), one chunk trip each, alike so that the reference
# compiles their shape once
FILLERS = (512, 128)
REHEARSAL_FILLERS = (30, 96)
REHEARSAL_SLACK = 4.0       # on every limit: a toy's readings set none
MOE_GMM, GLOBAL_PREFILL, GLOBAL_DECODE = (
    "moe_step_grouped_matmul", "global_prefill_attention",
    "global_decode_attention")
COUNTERS = ("conv_state_rows_advanced", "conv_state_rows_held",
            "global_tokens_attended", "global_view_tokens",
            "global_pages_read", "prefill_global_pairs", "prefill_keys_read",
            "prefill_chunk_tokens", "prefill_chunk_capacity")


def model_config(config: dict):
    from llm_in_practise_tpu.models.lfm2_moe import Lfm2MoeConfig

    return Lfm2MoeConfig.from_hf_config(config, compute_dtype="bfloat16")


def build(config: dict, seed: int, on_chip: bool) -> serving.Serving:
    # first of all: the parent of the PR that brought this model has no
    # such module and must fail here, before anything is built or warmed
    from llm_in_practise_tpu.models.lfm2_moe import Lfm2Moe, random_params

    import jax.numpy as jnp

    from benchmark.reference import lfm2_moe as ref
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
    # traffic states its output lengths. The head is tied: the ROW is zero,
    # its logit exactly 0 under the largest of the vocabulary's
    params["tok_embed"] = params["tok_embed"].at[
        tok.token_to_id(IM_END)].set(0)
    name = layout.get("model_name", "bench")
    parser = serve_openai.build_parser()
    args = parser.parse_args(["--model_name", name, "--host", "127.0.0.1",
                              "--port", "0", *layout["serve_args"]])
    serve_openai.validate_args(args, parser.error)
    server = serve_openai.build_server(
        args, tok, lambda mesh: (Lfm2Moe(cfg), params), parser.error)
    port = server.serve(host=args.host, port=args.port, background=True)
    return serving.Serving(cfg, params, tok, server, server.engine, port,
                           name, ref.geometry(cfg))


def held_tails(eng, slot: int) -> list:
    """The two-row tail of every conv layer of ``slot``, in layer order, on
    the host. Read under the engine's lock: a step in flight owns the
    buffers."""
    with eng._lock:
        return [np.asarray(layer["conv"][slot], np.float32)
                for layer in eng.paged.kv if "conv" in layer]


class Capture(list):
    """``StepStats.capture`` that also COPIES the attention layers' rows of
    a probe, ``(k, v)`` a layer as the pool holds them, at every program
    read from the one that ended its prompt on (the last copy stands): a
    finished request's pages go back to the pool and are the next
    request's, and the rows of the first positions AFTER the prompt are
    where a tail that padding rows advanced would show. ``append`` runs on
    the engine's thread, in the locked step that reads the program, while
    the request is live. ``wanted``: ``{uid: prompt tokens}``, a probe's
    entry made as soon as it is submitted; ``width``: a row's own (the pool
    pads a row to whole lanes). ``rows``: by uid, the rows written so far
    (the prompt's and one a decode program read since)."""

    def __init__(self, eng, width: int):
        super().__init__()
        self.eng, self.width, self.wanted, self.rows = eng, width, {}, {}
        self.decoded = {}

    def append(self, c):
        paged = self.eng.paged
        for slot, uid in c["uids"].items():
            if uid not in self.wanted:
                continue
            if uid not in self.decoded:
                if slot not in c["last_logits"]:
                    continue        # its prompt is still chunking
                self.decoded[uid] = 0
            elif c["kind"] != "chunk":      # a chunk program decodes no row
                self.decoded[uid] += 1
            n = self.wanted[uid] + self.decoded[uid]
            pages = np.asarray(paged.slot_pages(slot), np.int32)
            if len(pages) * paged.page_size < n:
                continue        # (its pages went back with its last token)
            self.rows[uid] = [
                tuple(np.asarray(pool[key][pages], np.float32).reshape(
                    -1, pool[key].shape[-1])[:n, :self.width]
                      for key in ("k", "v"))
                for pool in paged.kv if "k" in pool]
        super().append(c)


def routes_of(captured: list, uid: int) -> np.ndarray:
    """The routed sets the programs chose for one request, (routed layers,
    positions, k): its prompt's last position out of the chunk rows of the
    program that ended the prompt, then one position a later program that
    decoded it (a mixed program's decode half is its last part). A program
    issued ahead of the request's last token may follow: callers take the
    positions they judge."""
    out, decoding = [], False
    for c in captured:
        slot = next((s for s, u in c["uids"].items() if u == uid), None)
        if slot is None or not c["route"]:
            continue
        if not decoding:
            if slot in c["last_logits"]:
                out.append(c["route"][0][:, slot])
                decoding = True
        elif c["kind"] != "chunk":      # a chunk program decodes no row
            out.append(c["route"][-1][:, slot])
    return np.stack(out, axis=1) if out else np.zeros((0, 0, 0), np.int32)


def probe(sv: serving.Serving, lengths, seed: int, fillers=None) -> dict:
    """The engine's half of ``check``: every slot live. ``fillers`` =
    (prompt tokens, output tokens) of the requests that hold the other
    slots: they decode all through the probes' lives, and the first is
    judged by the tails its slot holds at its end."""
    from llm_in_practise_tpu.serve.engine import SamplingParams

    eng, cfg = sv.engine, sv.cfg
    fill_prompt, fill_out = fillers or FILLERS
    rng = np.random.default_rng([int(seed), 13])
    lengths = [int(n) for n in lengths]
    prompts = [rng.integers(4, cfg.vocab_size, n).tolist() for n in lengths]
    fill = [rng.integers(4, cfg.vocab_size, fill_prompt).tolist()
            for _ in range(eng.max_slots - len(lengths))]

    def greedy(n):
        return SamplingParams(temperature=0.0, greedy=True, max_tokens=n)

    captured = eng.step_stats.capture = Capture(
        eng, cfg.n_kv_head * cfg.head_dim)
    try:
        others = [eng.submit(p, greedy(fill_out)) for p in fill]
        heads = [o.next_item() for o in others]     # they all decode now
        probes = []
        for prompt in prompts:
            # the short probe decodes before the long one is submitted
            req = eng.submit(prompt, greedy(PROBE_TOKENS))
            captured.wanted[req.uid] = len(prompt)
            probes.append((req, req.next_item()))
        emitted = [[t] + r.result() if isinstance(t, int) else []
                   for r, t in probes]
        filled = [[t] + o.result() if isinstance(t, int) else []
                  for t, o in zip(heads, others)]
        # a request's last token is on its queue BEFORE the step that
        # emitted it has booked its statistics: the step holds the
        # engine's lock to its end
        with eng._lock:
            eng.step_stats.capture = None
    finally:
        eng.step_stats.capture = None
    seen = {"prompt_tokens": lengths, "probes": [],
            "mixed_finish": any(c["kind"] == "mixed" and c["last_logits"]
                                for c in captured),
            "slots_live": max((len(c["uids"]) for c in captured), default=0)}
    for (req, _), prompt, tokens in zip(probes, prompts, emitted):
        got, rows = judged(captured, req.uid), captured.rows.get(req.uid)
        if got is None or rows is None or len(tokens) != PROBE_TOKENS:
            return dict(seen, why="a probe is incomplete",
                        tokens=[len(t) for t in emitted])
        slot, logits = got
        # the probe's slot is idle since its last token: nothing has
        # touched what it holds
        seen["probes"].append({
            "prompt": prompt, "tokens": tokens, "slot": slot,
            "logits": logits, "pages": rows,
            "route": routes_of(captured, req.uid),
            "tails": held_tails(eng, slot)})
    slot = next((s for c in captured for s, u in c["uids"].items()
                 if u == others[0].uid), None)
    if slot is None or len(filled[0]) != fill_out:
        return dict(seen, why="a filler is incomplete",
                    tokens=[len(t) for t in filled])
    seen["filler"] = {"prompt": fill[0], "tokens": filled[0],
                      "route": routes_of(captured, others[0].uid),
                      "tails": held_tails(eng, slot)}
    return seen


def judge(sv: serving.Serving, seen: dict, geom: dict | None = None,
          slack: float = 1.0, forced: bool = True,
          crossed: bool = False) -> dict:
    """The reference's half of ``check``: every reading beside its limit.
    ``geom``: the reference's forms (one with a planted fault or one store
    in a lower precision: ``tools/swa_check_control.py``); ``slack``: a
    rehearsal's, on every limit; ``forced``: the reference computes the
    PROGRAM's routed sets at the judged positions (the reference's module
    says why; False: its own everywhere, a reading of what the flips there
    cost, held to nothing); ``crossed``: each probe's tails are judged as
    the OTHER's (a planted fault: what a slot holds taken from a wrong
    slot)."""
    from benchmark.reference import lfm2_moe as ref

    if "why" in seen:
        return {"ok": False, **{k: seen[k] for k in (
            "why", "prompt_tokens", "tokens") if k in seen}}
    reference = ref.Reference(geom or sv.geom)
    worst: dict = {}

    def note(name, value):
        if isinstance(value, list):
            old = worst.get(name, [0.0] * len(value))
            worst[name] = [max(a, b) for a, b in zip(old, value)]
        else:
            worst[name] = max(worst.get(name, 0.0), value)

    flipped = sets = 0
    tails = [p["tails"] for p in seen["probes"]][::-1 if crossed else 1]
    for p, held in zip(seen["probes"], tails):
        if p["route"].shape[1] < PROBE_TOKENS:
            return {"ok": False, "why": "a probe's routed sets are "
                    f"incomplete: {p['route'].shape}"}
        route, stores = p["route"][:, :PROBE_TOKENS], {}
        want = reference.logits(sv.params, p["prompt"] + p["tokens"][:-1],
                                last=PROBE_TOKENS, stores=stores,
                                prompt=len(p["prompt"]),
                                forced=route if forced else None)
        err = ref.logit_error(p["logits"], want[0])
        if "why" in err:
            return {"ok": False, "why": err["why"]}
        note("rms_over_std", err["rms_over_std"])
        note("max_over_std", err["max_over_std"])
        note("token_margin_over_std", ref.token_margin(want, p["tokens"]))
        a, b = ref.route_flips(route, stores["route"])
        flipped, sets = flipped + a, sets + b
        note("tail_error", [ref.store_error(x, y) for x, y in zip(
            held, stores["tail"])])
        # the rows the programs wrote: the prompt's and, past it, those of
        # the first tokens (where padding rows that advanced a tail show)
        n = min(len(p["pages"][0][0]), len(stores["pages"][0][0]))
        if n < len(p["prompt"]) + 2:
            return {"ok": False, "why": f"a probe's rows end at {n}"}
        for name, error in (("page_rows_error", ref.store_error),
                            ("page_row_worst", ref.worst_row_error),
                            ("page_row_median", ref.median_row_error)):
            note(name, [max(error(x[:n], y[:n]) for x, y in zip(got, rows))
                        for got, rows in zip(p["pages"], stores["pages"])])
    note("route_flip_share", flipped / max(sets, 1))
    # the filler: its output length of one-position updates of every tail
    f, stores = seen["filler"], {}
    if f["route"].shape[1] < len(f["tokens"]):
        return {"ok": False, "why": "the filler's routed sets are "
                f"incomplete: {f['route'].shape}"}
    reference.logits(sv.params, f["prompt"] + f["tokens"][:-1],
                     stores=stores, prompt=len(f["prompt"]),
                     forced=f["route"][:, :len(f["tokens"])] if forced
                     else None)
    note("filler_tail_error", [ref.store_error(x, y) for x, y in zip(
        f["tails"], stores["tail"])])
    limits = ref.limits(worst, slack)
    failed = [name for name, limit in limits.items()
              if np.any(np.asarray(worst[name]) > np.asarray(limit))]
    ok = (seen["mixed_finish"] and seen["slots_live"] == sv.engine.max_slots
          and not failed)
    return {"ok": bool(ok), "prompt_tokens": seen["prompt_tokens"],
            "long_probe_ended_in_a_mixed_step": seen["mixed_finish"],
            "slots_live": seen["slots_live"],
            "routed_sets_judged": sets, "routed_sets_flipped": flipped,
            "limits_failed": failed, "worst": worst, "tolerances": limits}


def check(sv: serving.Serving, lengths, seed: int, fillers=None,
          slack: float = 1.0) -> dict:
    t0 = time.monotonic()
    seen = probe(sv, lengths, seed, fillers)
    t1 = time.monotonic()
    out = judge(sv, seen, slack=slack)
    return dict(out, probe_s=t1 - t0, judge_s=time.monotonic() - t1)


def faults(sv: serving.Serving) -> dict:
    """What the reference is computed with, ONE at a time, to stand behind
    the check's limits (``tools/swa_check_control.py --faults``): the three
    planted faults, and the K/V rows in the nearest precision below the
    configuration's. Each must come out NOT ok."""
    return {"conv_break": int(sv.engine.chunked_prefill),
            "pad_advance": True, "bias_in_weights": True,
            "kv_dtype": "float8_e4m3fn"}


# judged and printed beside them, held to nothing: a bfloat16 router
# (scores move by ~0.002, under the margin a judged set must have)
READINGS = {"router_dtype": "bfloat16"}


def scope_patterns() -> dict:
    """What the text of each path's instructions must hold (the device
    plane keeps no ``jax.named_scope``: an event is its instruction's text
    and three timings): the grouped expert matmul's and the two attention
    kernels' custom calls, by the kernels' names."""
    from llm_in_practise_tpu.ops import swa_attention as swa

    return {MOE_GMM: re.compile(r"^%?gmm(\.\d+)? = .*custom-call"),
            GLOBAL_PREFILL: re.compile(re.escape(swa.GLOBAL_KERNEL)),
            GLOBAL_DECODE: re.compile(re.escape(swa.GLOBAL_PAGED_KERNEL))}


def slice_work(steps: list[dict], marks: dict, g: dict) -> dict:
    """What the steps inside the traced slice needed of the three paths:
    the routed layers' assignments and touched experts as the programs
    counted them (the whole plane and a chunk's padding too: what the
    kernel was given), the attention at true lengths."""
    t0, t1 = marks.get("begin_wall"), marks.get("end_wall")
    if t0 is None or t1 is None:
        return {}
    inside = [r for r in steps if t0 <= r["start_s"] < t1]

    def total(key):
        return sum(r.get(key, 0) for r in inside)

    out = {"steps": len(inside),
           "moe_assignments": total("moe_assignments_held"),
           "moe_experts_touched": total("moe_experts_touched")}
    out["moe_gmm_flops"], out["moe_gmm_bytes"] = (
        flops_moe.grouped_experts_cost(
            out["moe_assignments"], out["moe_experts_touched"], g["hidden"],
            g["width"]))
    for name, pairs, keys in (
            ("global_prefill", "prefill_global_pairs", "prefill_keys_read"),
            ("global_decode", "global_tokens_attended",
             "global_tokens_attended")):
        out[pairs], out[keys] = total(pairs), total(keys)
        out[name + "_flops"], out[name + "_bytes"] = (
            flops_lfm2.attention_cost(out[pairs], out[keys], g))
    return out


def stats_counters(eng) -> dict:
    st = eng.step_stats
    return dict(st.load.counters(),
                **{k: getattr(st, k) for k in COUNTERS})


def run(ctx: dict) -> dict:
    workload, seed, seconds = ctx["workload"], ctx["seed"], ctx["seconds"]
    probes = PROBES
    if not ctx["on_chip"]:
        # a rehearsal's cache is 256 tokens: the toy cell keeps the shape
        workload = dict(workload, **REHEARSAL_WORKLOAD)
        probes = REHEARSAL_PROBES
    sv = build(ctx["config"], seed, ctx["on_chip"])
    g = flops_lfm2.sizes(ctx["config"] if ctx["on_chip"]
                         else dict(ctx["config"], **REHEARSAL))
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
        counters["moe_expert_slots"] = (counters["moe_layer_passes"]
                                        * cfg.n_experts)
        counters["conv_state_bytes"] = eng.paged.recurrent_state_bytes
        # the whole step against the published peak: model FLOPs of the
        # window's work, from the counters, a second of the window
        counters["serve_model_flops_per_s"] = flops_lfm2.step_flops(
            counters, g, int(eng.chunked_prefill)) / seconds
        if ctx["on_chip"]:      # a CPU has no row of published peaks
            counters["peak_flops_per_s"] = ctx["peaks"]()[0]
        notes["kv"] = {"row_bytes": eng.paged.row_bytes,
                       "slot_bytes": eng.paged.slot_bytes,
                       "pool_bytes": eng.paged.pool_bytes,
                       "recurrent_state_bytes":
                           eng.paged.recurrent_state_bytes}
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
                events, not ctx["on_chip"], scope_patterns())
            notes["decode_step_ops"] = program_ops(
                events, "jit__paged_decode_fn", n=24)
            notes["mixed_step_ops"] = program_ops(
                events, "jit__paged_mixed_fn", n=24)
            del events
            obs["slice_work"] = slice_work(steps, marks, g)
            notes["scope_seconds"] = obs["scope_seconds"]
            notes["slice_work"] = obs["slice_work"]
        notes["step_stats"] = dict(counters)
        built = eng.compile_meter.compile_events
        t_check = time.monotonic()
        checked = check(sv, probes, seed,
                        *(() if ctx["on_chip"]
                          else (REHEARSAL_FILLERS, REHEARSAL_SLACK)))
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
