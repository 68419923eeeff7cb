"""One serving run of a model with RECURRENT layers whose state is replaced
every token, held by slot beside window rings, ONE paged full-attention
layer whose view a whole cross-decoder reads, and a cross-decoder that a
prompt's chunks skip (Phi-4-mini-flash-reasoning, ``models/phi4flash.py``,
whole: 32 layers, the vocabulary of 200,064, tied): build the server users
run (``examples/serve_openai.py::build_server``, seeded bf16 weights), warm
up what the cell's length ranges can reach, drive the window over loopback
HTTP, then compare with ``benchmark/reference/phi4flash.py``.

Warm-up, end-to-end reduction and the sampler are ``benchmark/serving.py``'s
by import; the window is ``serve_block_cell.SteadyLoop``, the tokenizer
that runner's ``letter_run_tokenizer`` (over the whole vocabulary); a
program's operations (``program_ops``) are ``serve_latent_cell``'s and the
device plane's seconds by pattern ``serve_hybrid_cell``'s
(``scope_seconds``), imported as they are. The model is imported at the top
of :func:`build`: a program without ``models/phi4flash.py`` (the parent of
the PR that added it) fails there, in seconds, before any warm-up.

What this runner adds to the observation (``benchmark/metrics/``): the
window's deltas of the engine's step-statistics counters
(``serve/step_stats.py``: positions scanned, state rows advanced and held,
rows through the self-decoder and the cross-decoder, the shared view's rows
attended x readers, the rings' rows, a chunk trip's fill), the stores'
bytes, the whole step's model FLOPs a second against the published peak
(``benchmark/flops_ssm.py::step_flops`` of those counters), and, in a
traced run, ``scope_seconds`` (device seconds of the scan kernel and the
two prefill attention kernels by their names on the device plane; of every
operation that holds the slot plane's shared view or its scores; of the
ring decode path by the rings' own tensors) beside ``slice_work`` (what
``flops_ssm`` makes of the step records inside the slice: true lengths,
never view widths or ring rows read).

``check`` — after the window, at the cell's widths, through the timed
programs and no other (``notes.check_engine_compiles`` must be 0), with
EVERY slot live: fourteen fillers (1,536-token prompts, 128 tokens each)
are submitted and decode; a 3,072-token probe is submitted and, once it
DECODES, a 7,168-token one that chunk-prefills beside the fifteen in fused
mixed steps (four chunks, the last padded; every ring wraps; the state
crosses three chunk boundaries; the cross-decoder runs at one of 7,168
positions); both emit 16 greedy tokens. For each probe the reference's
float32 forward of prompt + tokens, teacher-forced, must agree on (a) the
prefill's last-position logits as the timed program returned them, (b)
every emitted token, by the reference's logits at the 16 judged positions,
and (c) what the probe's slot HOLDS when it is done, layer by layer: every
recurrent layer's state and convolution tail, every window layer's ring
rows, and the paged layer's rows of the prompt (copied when the prompt
ends: ``Capture``), overall and at their worst row. For each filler the
first recurrent layer's state after its 128 one-position updates must be
the reference's, over the elements that forget slowest (a state rounded
at every update shows there; it needs the token ids alone). The logits of
32 layers carry every layer's bf16 matmul rounding and do not separate a
store kept in a lower precision; the stores do, and they see a state that
crossed between slots. Limits, their two readings each and which planted
fault or lower precision each one catches: the reference's module.
"""

from __future__ import annotations

import re
import threading
import time

import numpy as np

from benchmark import flops_ssm, serving, trace, traffic
from benchmark.runners.serve_block_cell import (
    SteadyLoop,
    letter_run_tokenizer,
)
from benchmark.runners.serve_hybrid_cell import scope_seconds
from benchmark.runners.serve_latent_cell import program_ops

# the keys ``rehearsal.TINY`` does not know or gets wrong for this model
# (toy sizes, CPU only): 8 layers = [M, W, M, W, M, F, G, X], a window
# shorter than the toy chunk (64), heads in pairs
REHEARSAL = {
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "sliding_window": 24,
    "tie_word_embeddings": True, "layer_norm_eps": 1e-05,
}
REHEARSAL_WORKLOAD = {
    "prompt_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                      "min": 24, "max": 208},
    "output_tokens": {"dist": "loguniform", "min": 8, "max": 32},
    "max_total_tokens": 256, "lead_in_s": 0.5,
}
PROBE_TOKENS = 16
PROBES = (3072, 7168)               # (decoding, chunk-prefilling beside it)
REHEARSAL_PROBES = (40, 180)
# the requests that hold every other slot while the probes run: (prompt
# tokens, output tokens), one chunk trip each, alike so that the reference
# compiles their shape once
FILLERS = (1536, 128)
REHEARSAL_FILLERS = (30, 96)
REHEARSAL_SLACK = 4.0       # on every limit: a toy's readings set none
SSM_SCAN, WINDOW_PREFILL, GLOBAL_PREFILL, SHARED_DECODE, WINDOW_DECODE = (
    "ssm_chunk_scan", "window_ring_prefill_attention",
    "global_prefill_attention", "shared_kv_decode_attention",
    "window_decode_attention")
COUNTERS = ("ssm_scan_tokens", "ssm_state_rows_advanced",
            "ssm_state_rows_held", "self_decoder_rows", "cross_decoder_rows",
            "cross_decoder_prefill_rows", "shared_kv_rows_attended",
            "window_rows_attended", "window_ring_rows_read",
            "global_tokens_attended", "global_view_tokens",
            "prefill_band_pairs", "prefill_band_keys_read",
            "prefill_global_pairs", "prefill_keys_read",
            "prefill_chunk_tokens", "prefill_chunk_capacity")


def model_config(config: dict, **overrides):
    from llm_in_practise_tpu.models.phi4flash import Phi4FlashConfig

    return Phi4FlashConfig.from_hf_config(
        config, compute_dtype="bfloat16", **overrides)


def build(config: dict, seed: int, on_chip: bool,
          **overrides) -> serving.Serving:
    """``overrides``: fields of the model's configuration a control run
    lowers (``tools/swa_check_control.py``: the state's dtype)."""
    # first of all: the parent of the PR that brought this model has no
    # such module and must fail here, before anything is built or warmed
    from llm_in_practise_tpu.models.phi4flash import Phi4Flash, random_params

    import jax.numpy as jnp

    from benchmark.reference import phi4flash as ref
    from examples import serve_openai
    from llm_in_practise_tpu.data.sft import IM_END

    if not on_chip:
        config = dict(config, **REHEARSAL)
    layout = config["layout"]
    cfg = model_config(config, **overrides)
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
        args, tok, lambda mesh: (Phi4Flash(cfg), params), parser.error)
    port = server.serve(host=args.host, port=args.port, background=True)
    return serving.Serving(cfg, params, tok, server, server.engine, port,
                           name, ref.geometry(cfg))


def judged(captured: list, uid: int):
    """``(slot, last-position logits)`` of the program that ended one
    probe's prompt, or None."""
    for c in captured:
        slot = next((s for s, u in c["uids"].items() if u == uid), None)
        if slot is not None and slot in c["last_logits"]:
            return slot, c["last_logits"][slot]
    return None


def slot_stores(eng, slot: int, length: int) -> dict:
    """What ``slot`` holds by slot after a sequence of ``length``
    positions, on the host, in layer order (``models/phi4flash.py``: the
    cache's first two entries, the layers of a kind stacked): every
    recurrent layer's state and convolution tail, every window layer's
    ring rows put in position order. Read under the engine's lock: a step
    in flight owns the buffers."""
    with eng._lock:
        state, rings = eng.paged.kv[0], eng.paged.kv[1]
        ssm = np.asarray(state["ssm"][slot], np.float32)
        conv = np.asarray(state["conv"][slot], np.float32)
        ring = {key: np.asarray(rings[key][slot], np.float32)
                for key in ("k1", "k2", "v")}
    rows = ring["k1"].shape[0]
    order = np.arange(max(length - rows, 0), length) % rows
    return {"state": list(ssm), "tail": list(conv),
            "rows": [tuple(ring[key][order, i] for key in ("k1", "k2", "v"))
                     for i in range(ring["k1"].shape[1])]}


class Capture(list):
    """``StepStats.capture`` that also COPIES the paged layer's rows of a
    probe's prompt, ``(k1, k2, v)`` as the pool holds them, when the
    program that ended the prompt is read: a finished request's pages go
    back to the pool and are the next request's. ``append`` runs on the
    engine's thread, in the locked step that reads the program, while the
    request is live. ``wanted``: ``{uid: prompt tokens}``, a probe's entry
    made as soon as it is submitted (its prompt takes a program at least);
    ``width``: a key row's (a value row is twice that; the pool pads a row
    to whole lanes). ``rows``: by uid."""

    def __init__(self, eng, width: int):
        super().__init__()
        self.eng, self.width, self.wanted, self.rows = eng, width, {}, {}

    def append(self, c):
        for slot in c["last_logits"]:
            uid = c["uids"].get(slot)
            if uid in self.wanted and uid not in self.rows:
                pages = np.asarray(self.eng.paged.slot_pages(slot), np.int32)
                pool = self.eng.paged.kv[2]
                self.rows[uid] = tuple(
                    np.asarray(pool[key][pages], np.float32).reshape(
                        -1, pool[key].shape[-1])[
                            :self.wanted[uid], :self.width * wide]
                    for key, wide in (("k1", 1), ("k2", 1), ("v", 2)))
        super().append(c)


def probe(sv: serving.Serving, lengths, seed: int, fillers=None) -> dict:
    """The engine's half of ``check``: every slot live. ``fillers`` =
    (prompt tokens, output tokens) of the requests that hold the other
    slots: they decode all through the probes' lives and are judged by
    the state their slots hold at their end."""
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
        eng, cfg.n_kv_head // 2 * cfg.head_dim)
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
        pages = [captured.rows.get(r.uid) for r, _ in probes]
    finally:
        eng.step_stats.capture = None
    seen = {"prompt_tokens": lengths, "probes": [], "fillers": [],
            "mixed_finish": any(c["kind"] == "mixed" and c["last_logits"]
                                for c in captured),
            "slots_live": max((len(c["uids"]) for c in captured), default=0)}
    for (req, _), prompt, tokens, rows in zip(probes, prompts, emitted,
                                              pages):
        got = judged(captured, req.uid)
        if got is None or rows is None or len(tokens) != PROBE_TOKENS:
            return dict(seen, why="a probe is incomplete",
                        tokens=[len(t) for t in emitted])
        slot, logits = got
        # the probe's slot is idle since its last token: nothing has
        # touched what it holds
        seen["probes"].append({
            "prompt": prompt, "tokens": tokens, "slot": slot,
            "logits": logits, "pages": rows,
            "held": slot_stores(eng, slot, len(prompt) + len(tokens) - 1)})
    for req, prompt, tokens in zip(others, fill, filled):
        slot = next((s for c in captured for s, u in c["uids"].items()
                     if u == req.uid), None)
        if slot is None or len(tokens) != fill_out:
            return dict(seen, why="a filler is incomplete",
                        tokens=[len(t) for t in filled])
        held = slot_stores(eng, slot, len(prompt) + len(tokens) - 1)
        seen["fillers"].append({"prompt": prompt, "tokens": tokens,
                                "slot": slot, "state": held["state"][0]})
    return seen


def judge(sv: serving.Serving, seen: dict, geom: dict | None = None,
          crossed: bool = False, slack: float = 1.0) -> dict:
    """The reference's half of ``check``: every reading beside its limit.
    ``geom``: the reference's forms (a left-out one is a planted fault:
    ``tools/swa_check_control.py --faults``); ``crossed``: each probe's
    stores judged against the OTHER probe's reference (the fault of a
    state that crossed between slots); ``slack``: a rehearsal's, on every
    limit."""
    from benchmark.reference import phi4flash as ref

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

    wants = []
    for p in seen["probes"]:
        stores = {}
        logits = reference.logits(sv.params, p["prompt"] + p["tokens"][:-1],
                                  last=PROBE_TOKENS, stores=stores)
        wants.append((logits, stores))
    for i, p in enumerate(seen["probes"]):
        want, stores = wants[i]
        err = ref.logit_error(p["logits"], want[0])
        if "why" in err:
            return {"ok": False, "why": err["why"]}
        note("rms_over_std", err["rms_over_std"])
        note("max_over_std", err["max_over_std"])
        note("token_margin_over_std", ref.token_margins(
            want, p["tokens"])["worst_margin_over_std"])
        held, pages = p["held"], p["pages"]
        if crossed:
            other = seen["probes"][1 - i]
            held, pages = other["held"], other["pages"]
        n = min(len(pages[0]), len(p["prompt"]))
        for name in ("state", "tail"):
            note(name + "_error", [ref.store_error(a, b) for a, b in zip(
                held[name], stores[name])])
        note("rows_error", [
            max(ref.store_error(a, b) for a, b in zip(got, rows))
            for got, rows in zip(held["rows"], stores["rows"])])
        note("page_rows_error", max(
            ref.store_error(a[:n], b[:n])
            for a, b in zip(pages, stores["pages"])))
        note("page_row_worst", max(
            ref.worst_row_error(a[:n], b[:n])
            for a, b in zip(pages, stores["pages"])))
    # the fillers: ``fill_out`` one-position updates of the first
    # recurrent layer's state, which needs the token ids alone
    first = ref.layer_params(sv.params, reference.geom["kinds"], 0)
    slow = ref.long_memory(first)
    for i, f in enumerate(seen["fillers"]):
        stores = {}
        g = seen["fillers"][i - 1] if crossed else f
        reference.logits(sv.params, g["prompt"] + g["tokens"][:-1],
                         stores=stores, depth=1)
        note("filler_state_error",
             ref.store_error(f["state"], stores["state"][0]))
        note("filler_slow_state_error",
             ref.store_error(f["state"], stores["state"][0], slow))
    limits = ref.limits(worst, slack)
    failed = [name for name, limit in limits.items()
              if np.any(np.asarray(worst[name]) > np.asarray(limit))]
    ok = (seen["mixed_finish"] and seen["slots_live"] == sv.engine.max_slots
          and not failed)
    return {"ok": bool(ok), "prompt_tokens": seen["prompt_tokens"],
            "long_probe_ended_in_a_mixed_step": seen["mixed_finish"],
            "slots_live": seen["slots_live"],
            "fillers": len(seen["fillers"]),
            "long_memory_elements": int(slow.sum()),
            "limits_failed": failed, "worst": worst, "tolerances": limits}


def check(sv: serving.Serving, lengths, seed: int, fillers=None,
          slack: float = 1.0) -> dict:
    return judge(sv, probe(sv, lengths, seed, fillers), slack=slack)


def faults(sv: serving.Serving) -> dict:
    """The reference's forms, each with the value that leaves it out
    (``tools/swa_check_control.py --faults``)."""
    return {"lambda_learned": False, "subln": False, "gmu_memory": False,
            "memory_shift": 1, "d_skip": False, "window_mask": False,
            "conv_break": int(sv.engine.chunked_prefill)}


def scope_patterns(cfg, eng) -> dict:
    """What the text of each path's instructions must hold (the device
    plane keeps no ``jax.named_scope``: an event is its instruction's text
    and three timings). The scan and the two prefill attention kernels:
    their custom calls, by the kernels' names, and for the window layers
    the operations that put ``[the ring ‖ the chunk's keys]`` in order, by
    the ``(1, ring rows + chunk, pairs, ·)`` run only they hold. The shared
    view's decode attention: every operation that holds the slot plane's
    view ``(slots, width, pairs x 64 | 128)`` (the gather that makes it,
    the new row's write, both einsums of every reader) or a reader's
    scores ``(slots, query pairs, width)``. The ring decode path: every
    operation that holds the slot plane's rings ``(slots, ring rows,
    pairs, ·)`` (either order) or their scores ``(slots, pairs, group,
    ring rows)``."""
    from llm_in_practise_tpu.ops import selective_scan as ssm
    from llm_in_practise_tpu.ops import swa_attention as swa

    slots, ring = int(eng.max_slots), int(eng.paged.ring_rows)
    run = ring + int(eng.chunked_prefill)
    hd, qp, kp = cfg.head_dim, cfg.n_head // 2, cfg.n_kv_head // 2
    wide = rf"(?:{hd}|{2 * hd})"
    return {
        SSM_SCAN: re.compile(re.escape(ssm.CHUNK_KERNEL)),
        WINDOW_PREFILL: re.compile(
            rf"{re.escape(swa.WINDOW_RING_KERNEL)}"
            rf"|\[1,{run},{kp},{wide}\]|\[1,{kp},{run},{wide}\]"),
        GLOBAL_PREFILL: re.compile(re.escape(swa.GLOBAL_KERNEL)),
        SHARED_DECODE: re.compile(
            rf"\[{slots},\d{{3,}},(?:{kp * hd}|{kp * 2 * hd})\]"
            rf"|\[{slots},{qp},\d{{3,}}\]"),
        WINDOW_DECODE: re.compile(
            rf"\[{slots},{ring},{kp},{wide}\]|\[{slots},{kp},{ring},{wide}\]"
            rf"|\[{slots},{kp},{qp // kp},{ring}\]"),
    }


def slice_work(steps: list[dict], marks: dict, g: dict) -> dict:
    """What the steps inside the traced slice needed of the five paths,
    by ``flops_ssm`` from the step records' true counts."""
    t0, t1 = marks.get("begin_wall"), marks.get("end_wall")
    if t0 is None or t1 is None:
        return {}
    inside = [r for r in steps if t0 <= r["start_s"] < t1]

    def total(key):
        return sum(r.get(key, 0) for r in inside)

    n_window = g["half"] // 2
    out = {"steps": len(inside), "ssm_scan_tokens": total("ssm_scan_tokens")}
    out["ssm_chunk_scan_flops"], out["ssm_chunk_scan_bytes"] = (
        flops_ssm.scan_cost(out["ssm_scan_tokens"], n_window + 1, g))
    for name, pairs, keys, layers in (
            ("window_ring_prefill", "prefill_band_pairs",
             "prefill_band_keys_read", n_window),
            ("global_prefill", "prefill_global_pairs", "prefill_keys_read",
             1),
            # true lengths x readers already: one "layer"
            ("shared_kv_decode", "shared_kv_rows_attended",
             "shared_kv_rows_attended", 1),
            ("window_decode", "window_rows_attended",
             "window_rows_attended", n_window)):
        out[pairs], out[keys] = total(pairs), total(keys)
        out[name + "_flops"], out[name + "_bytes"] = (
            flops_ssm.attention_cost(out[pairs], out[keys], layers, g))
    return out


def stats_counters(eng) -> dict:
    st = eng.step_stats
    return {k: getattr(st, k) for k in COUNTERS if hasattr(st, k)}


def run(ctx: dict) -> dict:
    workload, seed, seconds = ctx["workload"], ctx["seed"], ctx["seconds"]
    probes = PROBES
    if not ctx["on_chip"]:
        # a rehearsal's cache is 256 tokens: the toy cell keeps the
        # shape (short and long prompts in one queue, a window between)
        workload = dict(workload, **REHEARSAL_WORKLOAD)
        probes = REHEARSAL_PROBES
    sv = build(ctx["config"], seed, ctx["on_chip"])
    g = flops_ssm.sizes(ctx["config"] if ctx["on_chip"]
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
        counters["kv_window_state_bytes"] = eng.paged.slot_state_bytes
        counters["kv_recurrent_state_bytes"] = eng.paged.recurrent_state_bytes
        # the whole step against the published peak: model FLOPs of the
        # window's work, from the counters, a second of the window
        counters["serve_model_flops_per_s"] = (
            flops_ssm.step_flops(counters, g) / seconds)
        if ctx["on_chip"]:      # a CPU has no row of published peaks
            counters["peak_flops_per_s"] = ctx["peaks"]()[0]
        notes["kv"] = {"row_bytes": eng.paged.row_bytes,
                       "slot_bytes": eng.paged.slot_bytes,
                       "pool_bytes": eng.paged.pool_bytes,
                       "window_state_bytes": eng.paged.slot_state_bytes,
                       "recurrent_state_bytes":
                           eng.paged.recurrent_state_bytes,
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
