"""The serving cells' shared machinery: build the server users run, warm up
what the cell's length ranges can reach, drive a window over loopback
HTTP, and compare with the plain reference afterwards.

From the program this takes the system under test
(``examples/serve_openai.py::build_server`` and what it builds) and its
counters. Traffic, clocks, the reduction to metrics and the comparison
that decides ``correct`` are the benchmark's own.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import types

import numpy as np

from benchmark import client, stats, traffic

# Open loop: threads that each sleep until the next request is due. More
# than the requests ever in flight at once (a queue of 64 is four times
# the slots), so a request is never sent late for want of a sender.
SENDERS = 64


@dataclasses.dataclass
class Serving:
    cfg: object
    params: dict
    tok: object
    server: object
    engine: object
    port: int
    model_name: str
    geom: dict

    def close(self) -> None:
        self.server.shutdown()


# ------------------------------------------------------------------ build


def full_vocab_tokenizer(vocab_size: int):
    """The in-repo ChatML BPE (trained in memory on the repo's own
    self-cognition records, as ``chip_smoke.py`` does), extended with one
    printable piece for every further id of the model's vocabulary. With a
    real checkpoint every token the model emits decodes to text and the
    server streams one event per token; with seeded weights and an
    800-entry tokenizer it would stream almost none, and neither the
    per-token host work nor the client's per-token clock would be real."""
    from examples import qwen3_lora_sft
    from llm_in_practise_tpu.data import BPETokenizer
    from llm_in_practise_tpu.data.sft import self_cognition_records

    base = qwen3_lora_sft.train_tokenizer(
        self_cognition_records(n=64), "Bench", "Repo")
    vocab = dict(base.vocab)
    if len(vocab) > vocab_size:
        raise ValueError(f"tokenizer has {len(vocab)} entries, the model "
                         f"{vocab_size}")
    k = 0
    while len(vocab) < vocab_size:
        piece = f"#{k:x}"
        k += 1
        if piece not in vocab:
            vocab[piece] = len(vocab)
    return BPETokenizer(vocab, base.merges,
                        pre_tokenizer=base.pre_tokenizer,
                        special_tokens=base.special_tokens,
                        unk_token=base.unk_token)


def qwen3_config(config: dict):
    from llm_in_practise_tpu.models.qwen3 import Qwen3Config

    return Qwen3Config.from_hf_config(config, compute_dtype="bfloat16")


def geometry(cfg) -> dict:
    return {"n_head": cfg.n_head, "n_kv_head": cfg.n_kv_head,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "rope_theta": cfg.rope_theta}


def build(config: dict, seed: int) -> Serving:
    """Seeded packed weights on the device, then the server that
    ``python examples/serve_openai.py <layout.serve_args>`` would start,
    listening on a loopback port."""
    import bench
    from examples import serve_openai
    from llm_in_practise_tpu.models.qwen3 import Qwen3
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    layout = config["layout"]
    cfg = qwen3_config(config)
    params, _ = bench._distinct_nf4_base(cfg, Qwen3, fmt=layout["weights"],
                                         seed=seed)
    tok = full_vocab_tokenizer(cfg.vocab_size)
    name = layout.get("model_name", "bench")
    parser = serve_openai.build_parser()
    args = parser.parse_args(["--model_name", name, "--host", "127.0.0.1",
                              "--port", "0", *layout["serve_args"]])
    serve_openai.validate_args(args, parser.error)
    server = serve_openai.build_server(
        args, tok,
        lambda mesh: (QuantizedModel(Qwen3(cfg), mesh=mesh), params),
        parser.error)
    port = server.serve(host=args.host, port=args.port, background=True)
    return Serving(cfg, params, tok, server, server.engine, port, name,
                   geometry(cfg))


def render(content: str) -> str:
    """The prompt the server builds for one user message (its own
    function, so the benchmark counts tokens as the server will)."""
    from llm_in_practise_tpu.serve.api import build_prompt

    return build_prompt([types.SimpleNamespace(role="user", content=content)])


# ---------------------------------------------------------------- warm-up


def _pow2_width(need: int, page: int, cache_len: int) -> int:
    w = page
    while w < need:
        w *= 2
    return min(w, cache_len)


def warm_plan(workload: dict, *, buckets, chunk: int, cache_len: int,
              page: int, slots: int) -> list[dict]:
    """Waves of (prompt_tokens, max_tokens) requests that make the engine
    compile every program the cell's LENGTH RANGES can reach, derived from
    the workload file and the engine's geometry and never from the seed:

    - one-shot prefill at every (admission batch size, bucket) pair that
      prompts no longer than the chunk can form: batch sizes are the
      powers of two up to ``warm_admission_batch`` (how many requests one
      engine step can admit together: arrivals queue while a long step
      runs);
    - the paged decode program at every pow2 view width between the
      shortest prompt + 1 and the longest prompt + output;
    - the chunk program alone at every width a chunked prompt walks;
    - the fused mixed step (a prompt chunking while another row decodes)
      at the widths its rows can need.

    A wave is ``{"lead": (p, n) | None, "group": [(p, n), ...]}``: the
    lead must have produced its first token before the group is
    submitted; the group is admitted in one engine step."""
    pr, out = workload["prompt_tokens"], workload["output_tokens"]
    pmin, pmax = int(pr["min"]), int(pr["max"])
    cap = int(workload["max_total_tokens"])
    longest = min(pmax + int(out["max"]), cap)

    def bucket_for(n):
        return next((b for b in buckets if n <= b), cache_len)

    waves = []
    one_shot_max = min(pmax, chunk)
    if pmin <= one_shot_max:
        sizes, g = [], 1
        while g <= min(slots, int(workload.get("warm_admission_batch",
                                               slots))):
            sizes.append(g)
            g *= 2
        seen = set()
        for p in range(pmin, one_shot_max + 1):
            b = bucket_for(p)
            if b in seen:
                continue
            seen.add(b)
            for g in sizes:
                waves.append({"lead": None, "group": [(p, 1)] * g})
    # decode widths (a prompt longer than the chunk also walks the chunk
    # program's widths on its way)
    w = _pow2_width(pmin + 1, page, cache_len)
    while True:
        lo = w // 2 + 1 if w > page else 1      # smallest need in bucket w
        p = min(max(lo - 1, pmin), pmax)
        n = max(2, lo - p + 1)
        if p + n <= cap:
            waves.append({"lead": None, "group": [(p, n)]})
        if w >= _pow2_width(longest, page, cache_len):
            break
        w *= 2
    if pmax > chunk:
        # every chunk width up to the longest prompt, nothing decoding
        waves.append({"lead": None, "group": [(pmax, 2)]})
        # fused mixed step: the shortest prompt is decoding (and has
        # tokens enough left to outlast the hand-over) when the longest
        # arrives and walks its chunks
        n_chunks = -(-pmax // chunk)
        waves.append({"lead": (pmin, min(n_chunks + 40, cap - pmin)),
                      "group": [(pmax, 2)]})
    return waves


def warm(sv: Serving, workload: dict, seed: int) -> dict:
    """Run the warm plan through ``engine.submit`` (the entry the HTTP
    handler calls), then one request over HTTP. The engine's step lock is
    held while a group is submitted so that one step admits it whole."""
    from llm_in_practise_tpu.serve.engine import SamplingParams

    eng = sv.engine
    plan = warm_plan(workload, buckets=eng.buckets,
                     chunk=eng.chunked_prefill or eng.cache_len,
                     cache_len=eng.cache_len, page=eng.paged.page_size,
                     slots=eng.max_slots)
    rng = np.random.default_rng([int(seed), 11])
    vocab = sv.cfg.vocab_size

    def submit(p, n):
        ids = rng.integers(4, vocab, p).tolist()
        return eng.submit(ids, SamplingParams(temperature=0.0, greedy=True,
                                              max_tokens=n))

    t0 = time.monotonic()
    for wave in plan:
        lead = None
        if wave["lead"] is not None:
            lead = submit(*wave["lead"])
            first = lead.next_item()
            if not isinstance(first, int):
                raise RuntimeError("warm-up lead finished without a token")
        if lead is None:
            # the engine is idle: hold its step lock so ONE step admits
            # the whole group
            with eng._lock:
                handles = [submit(p, n) for p, n in wave["group"]]
        else:
            # the engine is stepping (and would starve a lock waiter):
            # the group joins whatever step comes next
            handles = [submit(p, n) for p, n in wave["group"]]
        for h in handles + ([lead] if lead is not None else []):
            h.result()
            if h.finish_reason not in ("length", "stop"):
                raise RuntimeError(f"warm-up request ended with "
                                   f"{h.finish_reason!r}")
    # one request over HTTP, of a length whose programs now exist
    pmin = int(workload["prompt_tokens"]["min"])
    text = traffic.PromptWriter(sv.tok, render, seed, n_words=64).write(pmin)
    first = client.stream_chat(
        sv.port, sv.model_name, text,
        client.Outcome(-1, pmin, 2, time.monotonic()),
        time.monotonic() + 120)
    if not first.ok:
        raise RuntimeError(f"warm-up HTTP request failed: {first}")
    return {"waves": len(plan),
            "requests": sum(len(w["group"]) + (w["lead"] is not None)
                            for w in plan),
            "seconds": time.monotonic() - t0}


# ----------------------------------------------------------------- window


@dataclasses.dataclass
class Window:
    """What one measured window produced, on the client's clock."""

    t0: float
    seconds: float
    outcomes: list
    lateness_s: list            # open loop: how late each send was
    in_flight_at_end: int
    samples: dict               # traced runs: pool pages, finished requests


def write_prompts(sv: Serving, planned: list, seed: int) -> list:
    """(planned request, its text) pairs. All text is written BEFORE the
    window (it counts as set-up): no tokenizer work of the benchmark's own
    competes with the server inside it."""
    writer = traffic.PromptWriter(sv.tok, render, seed)
    return [(p, writer.write(p.prompt_tokens)) for p in planned]


def run_window(sv: Serving, workload: dict, work: list, seconds: float,
               sampler=None) -> Window:
    """Offer the planned requests for ``seconds`` and let what is in
    flight finish within the workload's ``grace_s`` (outside the window).

    Open loop: a pool of sender threads, each sleeping until the next
    request is due; a request is timed from the instant it was DUE, so a
    late sender cannot hide queueing. Closed loop: ``clients`` threads,
    each taking the pool's next request when its last completed, until
    the window ends."""
    open_loop = "arrivals" in workload
    n_threads = SENDERS if open_loop else int(workload["clients"])
    outcomes, lateness = [], []
    lock = threading.Lock()
    cursor = [0]
    t0 = time.monotonic() + 0.25
    t_end = t0 + seconds
    deadline = t_end + float(workload["grace_s"])

    def take():
        with lock:
            i = cursor[0]
            if i >= len(work):
                return None
            cursor[0] += 1
        return work[i]

    def sender():
        while True:
            item = take()
            if item is None:
                return
            p, text = item
            if open_loop:
                due = t0 + p.due_s
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
            else:
                due = time.monotonic()
                if due >= t_end:
                    return
            out = client.Outcome(p.index, p.prompt_tokens, p.output_tokens,
                                 due)
            client.stream_chat(sv.port, sv.model_name, text, out, deadline)
            with lock:
                outcomes.append(out)
                if open_loop:
                    lateness.append(out.t_sent - due)

    threads = [threading.Thread(target=sender, daemon=True)
               for _ in range(n_threads)]
    delay = t0 - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    if sampler is not None:
        sampler.start(t0, t_end)
    for t in threads:
        t.start()
    time.sleep(max(0.0, t_end - time.monotonic()))
    eng = sv.engine
    in_flight = sum(r is not None for r in eng.slot_req) + eng.pending.qsize()
    for t in threads:
        t.join(timeout=max(0.0, deadline + 5.0 - time.monotonic()))
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a sender thread outlived the grace period")
    samples = sampler.stop() if sampler is not None else {}
    outcomes.sort(key=lambda o: o.index)
    return Window(t0, seconds, outcomes, lateness, in_flight, samples)


class Sampler:
    """Traced runs only: a thread that samples, every ``period_s``, the
    page pool's used pages and the engine's ring of finished requests
    (128 deep, so it is read before it wraps)."""

    def __init__(self, engine, period_s: float = 0.05):
        self.engine = engine
        self.period_s = period_s
        self.pages = []
        self.finished = {}
        self._stop = threading.Event()
        self._thread = None

    def _take(self):
        self.pages.append(self.engine.paged.pool.used_pages)
        for r in list(self.engine.finished):
            if r.uid not in self.finished:
                self.finished[r.uid] = (r.submit_time, dict(r.cp),
                                        r.finish_reason)

    def start(self, t0: float, t_end: float) -> None:
        self.t0, self.t_end = t0, t_end

        def loop():
            while not self._stop.wait(self.period_s):
                self._take()

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join(timeout=5.0)
        self._take()
        inside = [cp for t, cp, _ in self.finished.values()
                  if self.t0 <= t < self.t_end]
        return {"pool_pages_used": self.pages,
                "pool_pages": self.engine.paged.pool.capacity,
                "finished_cp": inside}


def end_to_end(window: Window, workload: dict) -> tuple[dict, dict]:
    """(metrics, notes) of a window. A request that failed, was refused or
    had no first token by the end of the grace period enters the TTFT
    percentile as the window's length: missing, not absent."""
    outs = window.outcomes
    ms = 1000.0
    ttft = [o.ttft_s() if (o.ok and o.t_first is not None)
            else window.seconds for o in outs]
    tpot = [o.tpot_s() for o in outs if o.tpot_s() is not None]
    t0, t1 = window.t0, window.t0 + window.seconds
    tokens_in = sum(1 for o in outs for t in o.token_times if t0 <= t <= t1)
    metrics = {
        "ttft_p95_ms": stats.percentile(ttft, 95) * ms,
        "serve_tokens_per_s": tokens_in / window.seconds,
    }
    if tpot:
        metrics["tpot_p95_ms"] = stats.percentile(tpot, 95) * ms
    limits = workload.get("limits", {})
    met = [o for o in outs if o.ok and o.ttft_s() is not None
           and o.ttft_s() <= limits.get("ttft_s", float("inf"))
           and (o.tpot_s() is None
                or o.tpot_s() <= limits.get("tpot_s", float("inf")))]
    notes = {
        "attempted": len(outs),
        "failed": sum(not o.ok for o in outs),
        "ttft_ms": {"n": len(ttft), "median": stats.median(ttft) * ms,
                    "p95": stats.percentile(ttft, 95) * ms},
        "tpot_ms": ({"n": len(tpot), "median": stats.median(tpot) * ms,
                     "p95": stats.percentile(tpot, 95) * ms}
                    if tpot else None),
        "tokens_generated": sum(o.tokens for o in outs if o.ok),
        "tokens_received_in_window": tokens_in,
        "met_both_limits_share": len(met) / max(len(outs), 1),
        "finish_reasons": {r: sum(o.finish_reason == r for o in outs)
                           for r in {o.finish_reason for o in outs}},
        "in_flight_at_window_end": window.in_flight_at_end,
        "sender_lateness_ms": ({
            "median": stats.median(window.lateness_s) * ms,
            "max": max(window.lateness_s) * ms}
            if window.lateness_s else None),
        "errors": sorted({o.error for o in outs if o.error})[:5],
    }
    return metrics, notes


# ------------------------------------------------------------ correctness


def probe_lengths(workload: dict, chunk: int) -> list[int]:
    """Prompt lengths of the two probes, inside the cell's own range: its
    typical prompt, and one that is prefilled in chunks where the range
    reaches beyond the chunk."""
    pr = workload["prompt_tokens"]
    lo, hi = int(pr["min"]), int(pr["max"])
    typical = int(min(max(pr.get("median", (lo + hi) // 2), lo), hi))
    return [typical, min(hi, max(typical, chunk + chunk // 2))]


def check(sv: Serving, workload: dict, seed: int) -> dict:
    """After the window, outside every timing, against
    ``benchmark/reference``'s float32 forward of the SAME packed weights,
    through the programs the cell's traffic runs and no other:

    1. where the cell's prompts can be prefilled in one shot, that
       program's last-position logits for a seeded probe (rms / max
       tolerances of the reference);
    2. greedy tokens of two probes through the public path. The second
       is submitted once the first has its first token, so that where it
       is prefilled in chunks the first decodes beside it (the fused
       mixed step), and both then decode through the paged cache. Each
       token's reference logit must be within the token margin of the
       reference's best at that position."""
    import jax.numpy as jnp

    from benchmark.reference import packed
    from benchmark.reference import qwen3 as ref
    from llm_in_practise_tpu.serve.engine import SamplingParams

    eng, cfg = sv.engine, sv.cfg
    reference = ref.Reference(sv.geom, packed.int8_to_f32)
    embedding = sv.params["tok_embed"]["embedding"]
    scale = sv.params["ln_f"]["scale"]

    def blocks():
        return (sv.params[f"block_{i}"] for i in range(cfg.n_layer))

    rng = np.random.default_rng([int(seed), 13])
    chunk = eng.chunked_prefill or eng.cache_len
    lengths = probe_lengths(workload, chunk)
    prefill = None
    if min(lengths) <= chunk:
        short = rng.integers(4, cfg.vocab_size, min(lengths)).tolist()
        padded = np.zeros((1, eng._bucket_for(len(short))), np.int32)
        padded[0, :len(short)] = short
        last, _ = eng._prefill(eng.params, jnp.asarray(padded),
                               jnp.asarray([len(short)], np.int32))
        prefill = ref.logit_error(
            np.asarray(last[0], np.float32),
            reference.logits(embedding, scale, blocks(), short)[0])

    n_new = 16
    greedy = SamplingParams(temperature=0.0, greedy=True, max_tokens=n_new)
    prompts = [rng.integers(4, cfg.vocab_size, n).tolist() for n in lengths]
    first = eng.submit(prompts[0], greedy)
    head = first.next_item()
    second = eng.submit(prompts[1], greedy)
    emitted = [[head] + first.result() if isinstance(head, int) else [],
               second.result()]
    probes = []
    for prompt, tokens in zip(prompts, emitted):
        if not tokens:
            probes.append({"ok": False, "complete": False,
                           "prompt_tokens": len(prompt)})
            continue
        rows = reference.logits(embedding, scale, blocks(),
                                prompt + tokens[:-1], last=len(tokens))
        probes.append(dict(ref.token_margins(rows, tokens),
                           prompt_tokens=len(prompt),
                           complete=len(tokens) == n_new))
    return {"ok": bool((prefill is None or prefill["ok"])
                       and all(p["ok"] and p["complete"] for p in probes)),
            "prefill_logits": prefill, "decode_tokens": probes}
