"""One serving run of a BLOCK-DIFFUSION model (SDAR-MoE): build the server
users run (``examples/serve_openai.py::build_server`` with
``models/sdar_moe.py``'s model and seeded bf16 weights), warm up what the
cell's length ranges can reach, drive the window over loopback HTTP, then
compare with ``benchmark/reference/sdar_moe.py``.

Warm-up (plus block-aligned prefill waves, see :func:`warm`),
end-to-end reduction and the sampler are ``benchmark/serving.py``'s, by
import: a block-diffusion engine takes the same ``submit`` and streams
over the same SSE path, only B tokens at a time. The window is
``serving.run_window``'s closed loop begun ``lead_in_s`` early
(:class:`SteadyLoop`): it opens on 16 generations in flight, not on 16
callers submitting at one instant. The counters this runner
adds to ``obs["counters"]`` are the window's deltas of
``engine.block.counters()`` (``serve/block_step.py``) plus two
denominators (``block_row_slots`` = passes x slots, ``moe_expert_slots`` =
passes x layers x experts); ``obs["model"]`` holds the expert layer's
shape for ``readers/moe_kernel_roofline.py``. A program without
``engine.block`` (the parent of the PR that added this file) fails at
``build``: it has no such model.

``check`` — after the window, at the cell's widths, through the timed
programs, two probes of 16 tokens through the public path, the first
with a prompt remainder of 2 (its first block opens half revealed), the
second submitted while the first decodes. The block program is ONE
executable: it returns its logits on every pass, in the window too, and
the engine's capture hook (``engine.block.capture``) only makes the step
fetch them, for every pass a probe rode, beside the experts the pass
chose; the request keeps its reveal log. A program built during the
check (``notes.check_engine_compiles``) makes the run incorrect. For
every DENOISE pass the reference is given the block exactly as the pass
saw it (teacher forcing) and must agree on:

(a) the logits of the B positions: rms / max in units of their spread;
(b) each revealed token: within a margin of the reference's best at its
    position; each revealed position: its reference confidence within a
    margin of the bar the masked positions' best confidences set;
(c) routing: the share of (token, layer) pairs whose expert set differs,
    each differing expert within a margin of the reference's k-th best
    (the reference takes the engine's set at those pairs only).

The tolerances, with their reasons, are in the reference's module.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import client, serving, trace, traffic

REHEARSAL_MOE = {"moe_intermediate_size": 64, "num_experts": 8,
                 "num_experts_per_tok": 2}
PROBE_TOKENS = 16


def letter_run_tokenizer(vocab_size: int):
    """``serving.full_vocab_tokenizer`` with the first of its stand-in
    pieces (``#k``) renamed to lowercase letter pairs and triples (and a
    word's leading space with its first two letters), and the merges that
    build them. The in-repo BPE has 381 entries, so the benchmark's
    prompts (seeded random letter words) tokenize letter by letter to ~50
    distinct ids: every slot's context is then a bag of the SAME ~50
    tokens, their attention outputs nearly coincide, and the masked
    positions of all 16 rows choose the same experts (on the chip 41-44%
    of the experts touched, the busiest taking 40 of a layer's 512
    assignments; PERF.md, PR 29). A 151,936-entry BPE has a piece for
    nearly every letter trigram; with these, the same words reach
    thousands of ids. A dense model's cost does not depend on which ids
    it is fed, so the dense cells keep the stock tokenizer."""
    from llm_in_practise_tpu.data import BPETokenizer

    tok = serving.full_vocab_tokenizer(vocab_size)
    vocab, merges = dict(tok.vocab), list(tok.merges)
    free = iter(range(vocab["#0"], vocab_size))     # the stand-ins' ids
    by_id = {i: p for p, i in vocab.items()}
    letters = "abcdefghijklmnopqrstuvwxyz"
    pairs = [(a, b) for a in letters for b in letters]
    # a word's leading space joins its first letters (the byte-level "Ġ"
    # alone would be every seventh token of every prompt), then pairs,
    # then triples
    for left, right in ([("Ġ", a) for a in letters]
                        + [("Ġ" + a, b) for a, b in pairs] + pairs
                        + [(a + b, c) for a, b in pairs for c in letters]):
        if left + right not in vocab:
            i = next(free, None)
            if i is None:
                break               # a rehearsal's small vocabulary
            del vocab[by_id[i]]
            vocab[left + right] = i
        if (left, right) not in tok.merge_ranks:
            merges.append((left, right))
    return BPETokenizer(vocab, merges, pre_tokenizer=tok.pre_tokenizer,
                        special_tokens=tok.special_tokens,
                        unk_token=tok.unk_token)


class SteadyLoop:
    """``serving.run_window``'s closed loop with the callers ALREADY IN
    FLIGHT when the window opens. They start ``lead_in_s`` (the workload
    file's) before it, and what they send until then is set-up.

    ``run_window`` starts its callers at the window's first instant, so
    all 16 submit at once and are admitted over the engine's next few
    steps: those 16 first requests wait 140-216 ms for their first block,
    one rank about 5 ms behind the next, where every later request of the
    run waits 90-135 ms (PR 29's nine runs, PERF.md section 6). They are 9% of
    the 165-181 requests a window completes, so the 95th percentile was
    the ninth or tenth of that ramp: which of them, and whether one stray
    stall pushed it a rank along, was what the driver's runs spread by
    (14.6 and 7.7 ms of 169). A service that keeps 16 generations in
    flight is in that state once, when it is switched on; the window now
    opens on the state it is in for the rest of its life. The callers,
    the pool and its order, the timing of a request (from the instant the
    caller took it) and the client are ``run_window``'s."""

    def __init__(self, sv: serving.Serving, workload: dict, work: list,
                 seconds: float):
        self.sv, self.seconds = sv, seconds
        self.outcomes, self.lock = [], threading.Lock()
        self.t0 = time.monotonic() + float(workload["lead_in_s"])
        self.t_end = self.t0 + seconds
        self.deadline = self.t_end + float(workload["grace_s"])
        todo = iter(work)

        def caller():
            while True:
                with self.lock:
                    item = next(todo, None)
                due = time.monotonic()
                if item is None or due >= self.t_end:
                    return
                p, text = item
                out = client.Outcome(p.index, p.prompt_tokens,
                                     p.output_tokens, due)
                client.stream_chat(sv.port, sv.model_name, text, out,
                                   self.deadline)
                with self.lock:
                    self.outcomes.append(out)

        self.threads = [threading.Thread(target=caller, daemon=True)
                        for _ in range(int(workload["clients"]))]
        for t in self.threads:
            t.start()

    def wait_open(self) -> None:
        time.sleep(max(0.0, self.t0 - time.monotonic()))

    def wait_close(self) -> None:
        time.sleep(max(0.0, self.t_end - time.monotonic()))
        eng = self.sv.engine
        self.in_flight = (sum(r is not None for r in eng.slot_req)
                          + eng.pending.qsize())

    def drain(self) -> tuple[serving.Window, list]:
        """(the window over the requests taken inside it, the lead-in's
        requests), once every caller has returned."""
        for t in self.threads:
            t.join(timeout=max(0.0, self.deadline + 5.0 - time.monotonic()))
        if any(t.is_alive() for t in self.threads):
            raise RuntimeError("a caller outlived the grace period")
        outs = sorted(self.outcomes, key=lambda o: o.index)
        return (serving.Window(self.t0, self.seconds,
                               [o for o in outs if o.t_due >= self.t0],
                               [], self.in_flight, {}),
                [o for o in outs if o.t_due < self.t0])

def build(config: dict, seed: int, on_chip: bool) -> serving.Serving:
    import jax.numpy as jnp

    from examples import serve_openai
    from llm_in_practise_tpu.models.sdar_moe import (
        SDARMoE, SDARMoEConfig, random_params,
    )

    if not on_chip:
        # the keys ``rehearsal.TINY`` does not know
        config = dict(config, **REHEARSAL_MOE,
                      mask_token_id=config["vocab_size"] - 1)
    layout = config["layout"]
    cfg = SDARMoEConfig.from_hf_config(config, compute_dtype="bfloat16")
    params = random_params(cfg, seed, jnp.bfloat16)
    tok = letter_run_tokenizer(cfg.vocab_size)
    name = layout.get("model_name", "bench")
    parser = serve_openai.build_parser()
    args = parser.parse_args(["--model_name", name, "--host", "127.0.0.1",
                              "--port", "0", *layout["serve_args"]])
    serve_openai.validate_args(args, parser.error)
    server = serve_openai.build_server(
        args, tok, lambda mesh: (SDARMoE(cfg), params), parser.error)
    port = server.serve(host=args.host, port=args.port, background=True)
    geom = dict(serving.geometry(cfg), block_length=cfg.block_length,
                top_k=cfg.n_experts_per_tok, norm_topk=cfg.norm_topk_prob)
    return serving.Serving(cfg, params, tok, server, server.engine, port,
                           name, geom)


def warm(sv: serving.Serving, workload: dict, seed: int) -> dict:
    """``serving.warm``, then the one-shot prefill waves of its plan once
    more with every prompt rounded UP to a whole number of blocks. The
    stock plan picks, for each prefill bucket, the shortest prompt that
    reaches it (65 for bucket 128); a block-diffusion engine prefills the
    prompt's WHOLE blocks only (64 of 65, the last token opens the first
    block), which falls into the bucket below and leaves the upper
    buckets' batched programs unbuilt (two of them compiled inside the
    window of PR 29's first runs)."""
    from llm_in_practise_tpu.serve.engine import SamplingParams

    warmed = serving.warm(sv, workload, seed)
    eng, B = sv.engine, sv.cfg.block_length
    plan = serving.warm_plan(
        workload, buckets=eng.buckets,
        chunk=eng.chunked_prefill or eng.cache_len, cache_len=eng.cache_len,
        page=eng.paged.page_size, slots=eng.max_slots)
    rng = np.random.default_rng([int(seed), 12])
    one = SamplingParams(temperature=0.0, greedy=True, max_tokens=1)
    waves = [w["group"] for w in plan
             if w["lead"] is None and all(n == 1 for _, n in w["group"])]
    for group in waves:
        with eng._lock:         # one engine step admits the whole group
            handles = [eng.submit(rng.integers(
                4, sv.cfg.vocab_size, -(-p // B) * B).tolist(), one)
                for p, _ in group]
        for h in handles:
            h.result()
    return dict(warmed, block_aligned_waves=len(waves))


def check(sv: serving.Serving, workload: dict, seed: int) -> dict:
    from benchmark.reference import sdar_moe as ref
    from llm_in_practise_tpu.serve.engine import SamplingParams

    eng, cfg = sv.engine, sv.cfg
    B = cfg.block_length
    reference = ref.Reference(sv.geom)
    rng = np.random.default_rng([int(seed), 13])
    pr = workload["prompt_tokens"]
    lo, hi = int(pr["min"]), int(pr["max"])
    typical = (lo + hi) // 2
    # remainders 2 and 1: the first probe's first block opens half
    # revealed
    lengths = [typical // B * B + 2, min(hi, typical * 3 // 2) // B * B + 1]
    prompts = [rng.integers(4, cfg.vocab_size, n).tolist() for n in lengths]
    greedy = SamplingParams(temperature=0.0, greedy=True,
                            max_tokens=PROBE_TOKENS)
    eng.block.capture = []
    try:
        first = eng.submit(prompts[0], greedy)
        head = first.next_item()
        second = eng.submit(prompts[1], greedy)
        emitted = [[head] + first.result() if isinstance(head, int) else [],
                   second.result()]
        captured = eng.block.capture
    finally:
        eng.block.capture = None
    worst = {"rms_over_std": 0.0, "max_over_std": 0.0,
             "token_margin_over_std": 0.0, "conf_margin_over_std": 0.0}
    routing = {"pairs": 0, "flipped": 0, "outside_margin": 0,
               "worst_shortfall": 0.0}
    passes = 0
    for req, prompt in zip((first, second), prompts):
        for c in captured:
            if c["uid"] != req.uid or c["commit"]:
                continue
            passes += 1
            ids, before = ref.block_inputs(
                prompt, req.reveal_log, B, cfg.mask_token_id, c["block"],
                c["pass"])
            want, found = reference.logits(sv.params, ids, last=B,
                                           engine_experts=c["experts"])
            err = ref.logit_error(c["logits"], want)
            if "why" in err:
                return {"ok": False, "why": err["why"]}
            now = [(pos, t) for b, p, pos, t in req.reveal_log
                   if b == c["block"] and p == c["pass"]]
            margins = ref.reveal_margins(want, before, now)
            for k in worst:
                worst[k] = max(worst[k], {**err, **margins}[k])
            for k in ("pairs", "flipped", "outside_margin"):
                routing[k] += found[k]
            routing["worst_shortfall"] = max(routing["worst_shortfall"],
                                             found["worst_shortfall"])
    flip_share = routing["flipped"] / max(routing["pairs"], 1)
    complete = all(len(t) == PROBE_TOKENS for t in emitted)
    ok = (complete and passes > 0
          and worst["rms_over_std"] <= ref.LOGIT_RMS_TOL
          and worst["max_over_std"] <= ref.LOGIT_MAX_TOL
          and worst["token_margin_over_std"] <= ref.TOKEN_MARGIN_TOL
          and worst["conf_margin_over_std"] <= ref.CONF_MARGIN_TOL
          and routing["outside_margin"] == 0
          and flip_share <= ref.ROUTE_FLIP_SHARE_TOL)
    return {"ok": bool(ok), "complete": complete, "denoise_passes": passes,
            "prompt_tokens": lengths, "worst": worst,
            "routing": dict(routing, flip_share=flip_share),
            "tolerances": {
                "rms": ref.LOGIT_RMS_TOL, "max": ref.LOGIT_MAX_TOL,
                "token_margin": ref.TOKEN_MARGIN_TOL,
                "conf_margin": ref.CONF_MARGIN_TOL,
                "route_margin": ref.ROUTE_MARGIN,
                "route_flip_share": ref.ROUTE_FLIP_SHARE_TOL}}


def run(ctx: dict) -> dict:
    workload, seed, seconds = ctx["workload"], ctx["seed"], ctx["seconds"]
    if not ctx["on_chip"]:
        # a rehearsal's pool of 64 toy requests lasts about 7 s
        workload = dict(workload, lead_in_s=0.5)
    sv = build(ctx["config"], seed, ctx["on_chip"])
    try:
        eng = sv.engine
        warmed = warm(sv, workload, seed)
        work = serving.write_prompts(
            sv, traffic.plan(workload, seconds, seed), seed)
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
        block0 = eng.block.counters()
        ctx["compiles"].window_open()
        setup_s = time.monotonic() - ctx["t_start"]
        if tracer is not None:
            tracer.start()
            sampler.start(loop.t0, loop.t_end)      # traced runs have both
        loop.wait_close()
        block1 = eng.block.counters()
        step1 = eng.steptrace.snapshot()
        ctx["compiles"].window_close(loop.t0, loop.t_end)
        window, lead_in = loop.drain()
        if tracer is not None:
            tracer.join(timeout=120)
            window.samples = sampler.stop()
        grace_s = time.monotonic() - loop.t_end
        device = ctx["describe_devices"]()
        e2e, notes = serving.end_to_end(window, workload)
        # the lead-in's requests are judged by nothing but this: their
        # tokens that arrived inside the window were served inside it,
        # and one of them that failed is a failed operation of the run
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
        notes["grace_and_trace_stop_s"] = grace_s
        notes["preemptions"] = eng.preemptions
        notes["engine_compile_events_total"] = eng.compile_meter.compile_events
        wall = step1["step_wall_seconds_total"] - step0["step_wall_seconds_total"]
        dev = step1["device_seconds_total"] - step0["device_seconds_total"]
        counters = {"step_wall_s": wall, "step_device_s": dev,
                    "step_host_s": wall - dev}
        counters.update({k: block1[k] - block0[k] for k in block1})
        cfg = sv.cfg
        counters["block_row_slots"] = (counters["block_passes"]
                                       * eng.max_slots)
        counters["moe_expert_slots"] = (counters["block_passes"]
                                        * cfg.n_layer * cfg.n_experts)
        notes["block"] = dict(counters)
        obs = {"requests": [], "counters": counters,
               "device_kind": ctx["devices"][0].device_kind,
               "model": {"layers": cfg.n_layer, "hidden": cfg.hidden_size,
                         "width": cfg.moe_intermediate_size,
                         "experts": cfg.n_experts}}
        if sampler is not None:
            s = window.samples
            obs["requests"] = s["finished_cp"]
            counters["pool_pages_peak"] = max(s["pool_pages_used"])
            counters["pool_pages"] = s["pool_pages"]
        steps = eng.steptrace.records(limit=eng.steptrace.capacity)
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
