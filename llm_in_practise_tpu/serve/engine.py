"""Continuous-batching inference engine — the TPU answer to vLLM's core loop.

The reference serves models three ways: raw HF ``generate`` behind FastAPI
(``Scripts/inference/07-deepseek1.5b-api-infr.py:122-130``, one request at a
time), vLLM (continuous batching + paged KV, CUDA), and Ray Serve replicas of
vLLM. This engine is the from-scratch TPU equivalent of the vLLM loop:

- **Slot-based static KV cache**: a ``(max_slots, cache_len, …)`` buffer per
  layer. Requests are admitted into free slots mid-flight; every jitted step
  decodes ALL slots in one batched forward — no retrace, no dynamic shapes.
  (vLLM pages the cache; here the slot dimension is the batching unit and
  XLA keeps the buffer resident in HBM. Paged/prefix reuse is layered on in
  :mod:`llm_in_practise_tpu.serve.prefix_cache`.)
- **Per-slot positions**: each cache entry carries a ``(max_slots,)`` index
  vector; writes scatter per slot (``models.layers.cache_update``) and the
  causal mask uses per-slot offsets, so slot 0 can be 900 tokens deep while
  slot 1 is prefilling.
- **Per-slot sampling params** via
  :func:`llm_in_practise_tpu.infer.sampling.sample_token_batched`.
- **Bucketed prefill**: prompts are right-padded to a few bucket lengths so
  prefill compiles once per bucket, then cache rows are scattered into the
  slot (chunked-prefill analog — vLLM ``enable_chunked_prefill``,
  ``Deployment/Ray/serve_run_examples/deepseek.py:33``).

Threading: HTTP handler threads call :meth:`InferenceEngine.submit`; one
background thread runs :meth:`step` forever. Tokens stream to per-request
queues — the producer/consumer shape of the reference's
``TextIteratorStreamer`` + generation thread
(``Scripts/inference/06-…-streaming-infr.py:52-75``).
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from llm_in_practise_tpu.infer.generate import max_positions
from llm_in_practise_tpu.infer.sampling import (
    sample_token_batched,
    sampler_tier_name,
)
from llm_in_practise_tpu.models.layers import (
    FINISH_KEY,
    PAGES_KEY,
    VALID_KEY,
    head_logits,
    last_position_hidden,
    last_position_logits,
)
from llm_in_practise_tpu.obs.cost import CostModel, tree_bytes
from llm_in_practise_tpu.obs.hbm import get_ledger, host_entry_bytes
from llm_in_practise_tpu.obs.logging import get_logger
from llm_in_practise_tpu.obs.meter import DispatchMeter, GoodputMeter
from llm_in_practise_tpu.obs.prof import CompileMeter
from llm_in_practise_tpu.obs.registry import HistogramAccumulator
from llm_in_practise_tpu.obs.steptrace import StepTrace
from llm_in_practise_tpu.obs.trace import get_tracer
from llm_in_practise_tpu.serve import paged_kv
from llm_in_practise_tpu.serve.mixed_step import (
    batched_chunk,
    batched_chunk_hidden,
    decode_scan,
    make_masked_mixed_step,
    make_mixed_step,
    pin_index,
    spec_verify_block,
)
from llm_in_practise_tpu.serve.multi_lora import current_lora, lora_context


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs (OpenAI request fields)."""

    temperature: float = 1.0
    top_k: int = 0          # 0 = disabled
    top_p: float = 1.0      # >= 1.0 = disabled
    greedy: bool = False
    max_tokens: int = 128
    # Constrained decoding (serve/constrain.py, ISSUE 12): a compiled
    # TokenAutomaton (shared, reusable across requests with the same
    # schema) — the engine mints a per-request cursor at activation and
    # adds the cursor state's vocab-width logit mask inside the jitted
    # dispatch. None = unconstrained (the exact pre-constraint
    # programs run; golden tokens are bit-identical).
    constraint: Any = None
    # Block-diffusion models only (serve/block_step.py); None = the
    # model's own default. ``denoising_steps``: denoise passes a block of
    # B positions takes under the static rule (1..B); ``remasking``:
    # "low_confidence_static" | "low_confidence_dynamic";
    # ``confidence_threshold``: the dynamic rule's reveal threshold.
    denoising_steps: int | None = None
    remasking: str | None = None
    confidence_threshold: float | None = None


_FINISH = object()  # sentinel closing a request's token queue

# Prompt tokens one step's chunks may hold in the PAGED layout, whose
# chunk and fused mixed programs pay one trip a chunking row. While the
# mid-prefill rows' chunks fit, all of them advance together. A burst
# that does not fit advances the rows with the FEWEST CHUNKS LEFT (ties:
# the oldest), so the prompts nearest their first token get it before a
# longer one takes a trip, and no step stalls its decode rows for more
# than 2048 / chunk trips. A row waits only while that many others are
# nearer their end. The contiguous layout computes the whole slot plane
# whatever chunks, so there every mid-prefill row advances.
CHUNK_TOKENS_PER_STEP = 2048

# Why a step's program was NOT issued while the one before it was unread
# (InferenceEngine._drain; a step record's ``drain``,
# ``llm_step_drains_total{reason}``). ``idle``: nothing was in flight and
# nothing forbade it (the engine had been idle, or the step before read
# its own program). The rest are states of the engine, observed step by
# step: the layout (``contiguous``), a ready row (``grammar``,
# ``speculative``, ``session``; ``block_dynamic``: a block-diffusion row
# under ``low_confidence_dynamic``, whose pass reveals as many positions
# as clear its threshold, so which rows commit next is a device value; a
# block engine whose ready rows are all static runs its passes ahead,
# serve/block_step.py), an admission that dispatches or reads values
# (``oneshot_prefill``, ``kv_pull``, ``host_first_token``), a step of
# more than one program (``two_dispatch``: also a block engine's chunk
# beside a pass), a page reservation that must preempt or finish a row
# (``preempt``).
DRAIN_REASONS = ("idle", "contiguous", "block_dynamic", "grammar",
                 "speculative", "session", "oneshot_prefill", "kv_pull",
                 "host_first_token", "two_dispatch", "preempt")

# Per-request critical-path segments (ISSUE 11): every finished
# request's wall time decomposes into these bins — surfaced per request
# at GET /debug/requests and aggregated into
# llm_request_critical_path_seconds_total{segment=…}.
#
# The booking rule for dispatch windows: EVERY window is booked to EVERY
# request that holds a slot during it, under the segment that names the
# request's relation to the window — ``prefill_dispatch`` (it advanced
# this request's own prompt), ``decode_dispatch`` (a plain decode window
# this request rode), ``prefill_stall`` (it advanced someone else's
# prompt — one-shot, chunk or fused mixed step — while this request sat
# in its slot) and ``decode_interleave`` (a plain decode window while
# this request was mid-prefill). ``host_gap``, the residual none of the
# attributed segments claim, is thereby host time only: the engine
# thread between windows.
#
# Overlays are reported alongside and excluded from the wall-clock
# partition: ``stream_flush`` (the API-side SSE write tail, concurrent
# with decode), ``dispatch_issue`` (the issue parts of the windows
# booked to the request: host time inside them), ``api_pre_submit``
# (HTTP body read → the request entering ``submit``: JSON, chat
# template, BPE; before the wall clock starts) and ``api_first_flush``
# (first token on the engine thread → first SSE event handed to the
# socket), and the engine thread's time BY STATE while the request held
# a slot: ``engine_wall`` (the wall of every recorded step it held a
# slot in; of the step it finished in, the part before its finish) =
# ``engine_cpu`` (the thread ran: host work, covered by a running
# program or not) + ``engine_blocked`` (it waited for the device inside
# ``StepTrace.fetch``) + ``engine_stalled`` (neither: the GIL was a
# handler thread's, the thread was descheduled or slept in a lock).
# All four are written together, a 0.0 included.
CP_THREAD_STATES = ("engine_wall", "engine_cpu", "engine_blocked",
                    "engine_stalled")
CP_SEGMENTS = ("queue_wait", "admission", "prefill_dispatch",
               "decode_dispatch", "prefill_stall", "decode_interleave",
               "host_gap", "handoff_wire", "preempt_recompute",
               "stream_flush", "dispatch_issue", "api_pre_submit",
               "api_first_flush", *CP_THREAD_STATES)
CP_OVERLAYS = frozenset(("stream_flush", "dispatch_issue",
                         "api_pre_submit", "api_first_flush",
                         *CP_THREAD_STATES))
# re-admission after a page-pool preemption re-pays these segments; the
# re-pay is charged to preempt_recompute so a preempted request's
# breakdown says "recompute", not "a second mysterious prefill"
_CP_RECOMPUTE_SEGS = frozenset(
    ("queue_wait", "admission", "prefill_dispatch"))


class EngineDeadError(RuntimeError):
    """The engine loop died while a request waited on its token queue."""


@dataclasses.dataclass
class Request:
    """A submitted generation request and its streaming output channel."""

    uid: int
    prompt_ids: list[int]
    params: SamplingParams
    tokens: "queue.Queue[Any]" = dataclasses.field(default_factory=queue.Queue)
    submit_time: float = dataclasses.field(default_factory=time.monotonic)
    first_token_time: float | None = None
    finish_time: float | None = None
    finish_reason: str | None = None
    n_generated: int = 0
    # set by submit(); lets every queue consumer bound its wait with a
    # liveness check instead of blocking forever on a dead engine
    engine: "InferenceEngine | None" = dataclasses.field(
        default=None, repr=False, compare=False)
    # Disaggregated serving (serve/disagg.py): ``kv_entry`` is a device
    # PrefixEntry claimed from a handoff store — admission seeds the slot
    # via the full-prefix direct-insert path, zero prefill work here.
    # ``handoff_id`` marks a prefill-role request: the engine publishes
    # the prompt KV under this id when prefill completes and finishes the
    # request (finish_reason "handoff") instead of decoding.
    kv_entry: object | None = dataclasses.field(
        default=None, repr=False, compare=False)
    handoff_id: str | None = None
    # Paged-KV preemption (serve/paged_kv.py): when the page pool
    # exhausts mid-decode, the youngest slot is preempted BY RECOMPUTE —
    # its request re-enters the queue with ``prompt_ids`` extended to
    # everything already emitted, ``resume_last`` holding the one token
    # whose KV is not yet written, and ``resume_budget`` the remaining
    # token budget. Re-admission prefills the extended prompt (usually a
    # page-index hit — the preempted pages were registered) and resumes
    # decoding WITHOUT emitting or re-sampling; the client stream never
    # notices beyond the latency bubble.
    resume_last: int | None = dataclasses.field(
        default=None, repr=False, compare=False)
    resume_budget: int = dataclasses.field(
        default=0, repr=False, compare=False)
    # request tracing (obs/trace.py): the TraceContext the API layer
    # minted for this request — the engine parents its queue-wait /
    # admission / prefill-chunk / decode / handoff-publish spans here,
    # so one trace id covers the request across every hop. ``None``
    # (untraced submit paths: benches, direct engine use) records
    # nothing.
    trace: object | None = dataclasses.field(
        default=None, repr=False, compare=False)
    # critical-path breakdown (GET /debug/requests): per-segment
    # seconds of this request's wall clock, accumulated where the
    # engine knows them (see CP_SEGMENTS). Writers are phase-exclusive
    # — the HTTP thread at submit, the engine thread while slotted, the
    # publisher thread at publish, the API thread after the stream
    # closes — so no lock is needed.
    cp: dict = dataclasses.field(default_factory=dict, repr=False,
                                 compare=False)
    # warm-vs-cold TTFT attribution: the prefix-/handoff-hit outcome at
    # FIRST admission ("hit" | "partial" | "cold"); labels the
    # llm_ttft_seconds histogram with cache=…
    cache_outcome: str | None = dataclasses.field(
        default=None, repr=False, compare=False)
    # stamped by the paged preempt path so the re-admission's queue
    # wait is charged to preempt_recompute, not queue_wait
    requeue_time: float | None = dataclasses.field(
        default=None, repr=False, compare=False)
    # origin of the NEXT queue_wait interval: re-armed at every queue
    # pop and re-stamped by preempt, so a request requeued N times
    # (admit-blocked on a dry page pool, or preempted) books N disjoint
    # wait intervals instead of N overlapping ones from submit_time
    cp_queue_origin: float | None = dataclasses.field(
        default=None, repr=False, compare=False)
    # constrained decoding (serve/constrain.py): this request's live
    # grammar cursor, minted from params.constraint at first
    # activation. It RIDES the request through preempt-by-recompute
    # requeues — the resumed stream continues from the exact grammar
    # position, nothing is replayed (the byte-identical-stream
    # guarantee extends to constrained requests).
    constraint_state: object | None = dataclasses.field(
        default=None, repr=False, compare=False)
    # batched multi-LoRA (serve/multi_lora.py, ISSUE 15): the adapter
    # name this request decodes under (None = base model). Admission
    # stamps it into ``slot_adapter``; the registry holds a refcount
    # from submit until _record_finished so the adapter can't be
    # evicted mid-request. Rides preempt-by-recompute requeues — the
    # ref stays held, the resumed slot re-stamps the same adapter.
    adapter: str | None = None
    # True while this request holds a registry refcount (set by submit,
    # cleared by _record_finished) — release must never run for a
    # request whose acquire never did (the too_large fast-reject)
    adapter_ref: bool = dataclasses.field(
        default=False, repr=False, compare=False)
    # Session-native serving (serve/sessions.py, ISSUE 17): the client's
    # conversation handle. On finish the slot's full KV pages are pinned
    # under it in the engine's SessionStore (and published to the fleet
    # handoff namespace when one is wired) so the next turn starts warm;
    # admission consults the store's pending fleet pulls under this id.
    session_id: str | None = None
    # seconds of dispatch windows booked to this request so far, under
    # whatever segment (engine thread only): admission's bookkeeping
    # share is the admit wall minus what this grew by meanwhile
    cp_window_s: float = dataclasses.field(
        default=0.0, repr=False, compare=False)
    # the HTTP handler's two instants (``time.monotonic``; plain floats,
    # never a ``cp`` insert from a handler thread): the body was read /
    # the first SSE event had been handed to the socket. The finish
    # funnel turns them into the ``api_*`` overlays.
    api_body_time: float | None = dataclasses.field(
        default=None, repr=False, compare=False)
    api_first_flush_time: float | None = dataclasses.field(
        default=None, repr=False, compare=False)
    # Block-diffusion decoding (serve/block_step.py). ``prompt_ids``
    # then holds the prompt's WHOLE blocks (what prefill stores) and
    # ``block_open`` the ``P mod B`` tokens left over, which open the
    # first generated block as revealed positions. ``reveal_log``: one
    # ``(block, pass, position, token)`` per position a denoise pass
    # revealed (a few ints, always on: the reference comparison replays
    # it); ``block_passes``: dispatches of the block program this
    # request rode.
    block_open: list = dataclasses.field(
        default_factory=list, repr=False, compare=False)
    reveal_log: list = dataclasses.field(
        default_factory=list, repr=False, compare=False)
    block_passes: int = dataclasses.field(
        default=0, repr=False, compare=False)

    def cp_add(self, seg: str, dt: float) -> None:
        """Accumulate ``dt`` seconds into critical-path segment ``seg``.
        Once a request has been preempted, the re-paid admission
        segments redirect into ``preempt_recompute``. Keyed on
        ``requeue_time`` (stamped by every preempt) rather than
        ``resume_last``: a MID-PREFILL preempt emitted nothing, so it
        has no resume token, but its second prefill is recompute all
        the same."""
        if self.requeue_time is not None and seg in _CP_RECOMPUTE_SEGS:
            seg = "preempt_recompute"
        self.cp[seg] = self.cp.get(seg, 0.0) + float(dt)

    def cp_thread_states(self, states) -> None:
        """Book ``(wall, cpu, blocked, stalled)`` seconds of an engine
        step this request held a slot in (CP_THREAD_STATES)."""
        cp = self.cp
        for key, dt in zip(CP_THREAD_STATES, states):
            cp[key] = cp.get(key, 0.0) + dt

    def cp_window(self, seg: str, dt: float, issue_s: float) -> None:
        """Book one dispatch window this request sat through (see
        CP_SEGMENTS for the rule that picks ``seg``)."""
        self.cp_add(seg, dt)
        self.cp_window_s += dt
        self.cp["dispatch_issue"] = (self.cp.get("dispatch_issue", 0.0)
                                     + issue_s)

    def next_item(self, poll_s: float = 1.0):
        """Next queue item — a token id or the internal finish sentinel
        (compare with ``is`` against ``_FINISH``). The wait is BOUNDED:
        between ``poll_s`` polls the engine's liveness is checked, so a
        crashed/stopped engine raises :class:`EngineDeadError` instead
        of freezing the consumer thread (the API layer maps it to a
        5xx; benches/scripts see the exception)."""
        while True:
            try:
                return self.tokens.get(timeout=poll_s)
            except queue.Empty:
                if self.engine is not None and not self.engine.is_alive():
                    raise EngineDeadError(
                        "engine loop is not running; request "
                        f"{self.uid} will never finish")

    def __iter__(self):
        """Yield generated token ids until the request finishes."""
        while True:
            item = self.next_item()
            if item is _FINISH:
                return
            yield item

    def result(self) -> list[int]:
        return list(self)

    @property
    def ttft_s(self) -> float | None:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def tpot_s(self) -> float | None:
        """Mean time per output token after the first."""
        if self.finish_time is None or self.n_generated < 2:
            return None
        return (self.finish_time - self.first_token_time) / (self.n_generated - 1)


@dataclasses.dataclass
class _Flight:
    """A paged decode / mixed / chunk / block program between its issue
    and its retire: the device outputs nobody has read yet, and the
    host-side plan that produced them (everything here was known at
    issue)."""

    kind: str                       # "decode" | "mixed" | "chunk" | "block"
    # rows that decode: (slot, request, why it ends with this token or
    # None); a block-diffusion pass's rows carry more
    # (BlockDecoder._advance_row)
    rows: list = dataclasses.field(default_factory=list)
    # (max_slots, 1) sampled tokens; a block pass: its (max_slots, B)
    # blocks, their revealed flags, the experts the plane chose and, for
    # a reference comparison, its logits
    toks: Any = None
    rev: Any = None
    experts: Any = None
    logits: Any = None
    # rows that chunk: (slot, state, chunk) as dispatched
    entries: list = dataclasses.field(default_factory=list)
    # prompts this program ends whose first token it sampled: (slot,
    # request, state, why the stream ends with that token or None); the
    # slot is live from issue on
    finished: list = dataclasses.field(default_factory=list)
    first: Any = None               # (max_slots,) first tokens by slot
    # a prompt it ends samples its first token on the host: read at once
    host_first: bool = False
    # requests that held a slot at issue (the window is booked to them)
    holders: list = dataclasses.field(default_factory=list)
    stats: Any = None               # StepStats' pending entry
    book: dict = dataclasses.field(default_factory=dict)

    def decodes(self, slot: int) -> bool:
        return any(row[0] == slot for row in self.rows)


# what a planning pass returns after it had to read the program in flight
# (a page reservation that must preempt): the step plans again
_REPLAN = object()


class EngineStats:
    """Counters/histograms surfaced at /metrics (SURVEY §5.5 PromQL table).

    TTFT/TPOT are fixed-bucket :class:`HistogramAccumulator`s — O(1)
    memory however long the server runs. (They were plain lists growing
    one float per request forever; a week of sustained load leaked the
    whole latency history into RAM just to answer a quantile query.)
    """

    def __init__(self):
        self.lock = threading.Lock()
        self.requests_total = 0         # guarded-by: lock
        self.tokens_generated_total = 0  # guarded-by: lock
        self.ttft = HistogramAccumulator()
        self.tpot = HistogramAccumulator()
        self.queue_depth = 0            # guarded-by: lock
        self.active_slots = 0           # guarded-by: lock
        self.requests_shed = 0          # guarded-by: lock
        # warm-vs-cold TTFT attribution (ISSUE 11 satellite / ROADMAP
        # item 1's metric ask): the same TTFT observations, split by the
        # prefix-/handoff-hit outcome at admission — rendered as
        # llm_ttft_seconds{cache="hit"|"partial"|"cold"} next to the
        # plain series, so "is the cache working fleet-wide" is one
        # PromQL ratio instead of a bench run
        self.ttft_by_cache = {k: HistogramAccumulator()
                              for k in ("hit", "partial", "cold")}
        # per-segment request critical-path aggregate
        # (llm_request_critical_path_seconds_total{segment=…}); written
        # from the engine thread (finish) AND the publisher/API threads
        # (handoff, stream flush), hence under the lock
        self.critical_path = {seg: 0.0 for seg in CP_SEGMENTS}  # guarded-by: lock
        # SLO goodput (obs/meter.py): inactive until thresholds are
        # configured (engine ttft_slo_s/tpot_slo_s kwargs, or the serve
        # benches post-warmup) — then every finished request's tokens
        # land in llm_goodput_tokens_total{slo=ok|violated}
        self.goodput = GoodputMeter()

    def note_stream_flush(self, dt: float) -> None:
        """Book a stream's SSE write tail (API handler thread) into the
        critical-path aggregate — it arrives after the engine finished
        the request, so it cannot ride ``observe_finished``."""
        with self.lock:
            self.critical_path["stream_flush"] += float(dt)

    def critical_path_snapshot(self) -> dict:
        with self.lock:
            return dict(self.critical_path)

    def observe_finished(self, req: Request):
        with self.lock:
            self.tokens_generated_total += req.n_generated
        # the accumulators carry their own locks — keep the observe
        # outside stats.lock so a scrape-time snapshot never serializes
        # against the engine thread's finish path
        if req.ttft_s is not None:
            self.ttft.observe(req.ttft_s)
            acc = self.ttft_by_cache.get(req.cache_outcome or "cold")
            (acc or self.ttft_by_cache["cold"]).observe(req.ttft_s)
        if req.tpot_s is not None:
            self.tpot.observe(req.tpot_s)
        if self.goodput.enabled and req.finish_reason != "queue_full":
            # sheds are already counted (requests_shed / 429s); goodput
            # prices the tokens the engine actually produced
            self.goodput.observe(
                tokens=req.n_generated, ttft_s=req.ttft_s,
                tpot_s=req.tpot_s,
                trace_id=getattr(req.trace, "trace_id", None))


def _default_buckets(cache_len: int) -> tuple[int, ...]:
    out, b = [], 16
    while b < cache_len:
        out.append(b)
        b *= 2
    return tuple(out) or (cache_len,)


class InferenceEngine:
    """Continuous-batching decode loop over a slot-structured KV cache.

    ``model`` must expose ``init_cache(batch, max_len, dtype=...)`` and a
    flax ``apply`` taking ``(idx, deterministic=..., cache=...)`` and
    returning ``(logits, cache)`` — true of every model family in-tree.
    """

    def __init__(
        self,
        model,
        params,
        *,
        max_slots: int = 8,
        cache_len: int = 512,
        eos_id: int | None = None,
        cache_dtype=jnp.bfloat16,
        prefill_buckets: tuple[int, ...] | None = None,
        rng: jax.Array | None = None,
        prefix_cache: "PrefixCache | bool | None" = None,
        chunked_prefill: int | None = None,
        mesh=None,
        kv_pool=None,
        speculative_k: int | None = None,
        speculative_ngram: int = 3,
        prefill_budget: int = 1,
        mixed_step: bool = True,
        max_queue: int | None = None,
        queue_timeout_s: float | None = None,
        draft_model=None,
        draft_params=None,
        role: str = "both",
        handoff=None,
        tracer=None,
        ttft_slo_s: float | None = None,
        tpot_slo_s: float | None = None,
        kv_layout: str = "contiguous",
        kv_page_size: int = 16,
        kv_pool_tokens: int | None = None,
        steptrace: StepTrace | None = None,
        adapter_registry=None,
        session_store=None,
    ):
        # Engine warmup is compile-bound; the persistent cache turns
        # every restart after the first into cache loads. Idempotent;
        # core/compile_cache.py says where the cache goes.
        from llm_in_practise_tpu.core.compile_cache import (
            enable_compilation_cache,
        )

        enable_compilation_cache()
        # Every KV buffer here is (slot, position, ...): the unrolled
        # per-layer layout. The stacked layout (Qwen3 ``scan_layers``:
        # axis 0 is the layer) trains and decodes through
        # infer/generate.py; the engine does not serve it.
        for which, m in (("model", model), ("draft_model", draft_model)):
            if m is not None and int(getattr(m, "cache_slot_axis", 0)) != 0:
                raise ValueError(
                    f"{which} has the stacked (scan_layers) cache layout; "
                    "the engine serves the unrolled layout only "
                    "(cache_slot_axis == 0): build the model with "
                    "scan_layers=False over unstack_layer_params(params)")
        # Batched multi-LoRA (serve/multi_lora.py, ISSUE 15): wrap the
        # model in the gathered-BGMV facade BEFORE anything below closes
        # over it (mixed-step builders, PagedKV, init_cache, the cost
        # model all take the LOCAL ``model``). The facade delegates
        # untouched while no lora context is set, so every base program
        # traces the exact pre-LoRA computation; only the *_lora twins
        # push a context.
        self.adapter_registry = adapter_registry
        if adapter_registry is not None:
            from llm_in_practise_tpu.serve.multi_lora import (
                LoRAServingModel,
            )

            model = LoRAServingModel(model)
        self.model = model
        self.params = params
        # Tensor-parallel serving (vLLM --tensor-parallel-size parity):
        # pass a mesh and params already placed by
        # :func:`shard_params_for_serving`; the KV cache shards its heads
        # dim over the mesh's ``model`` axis and XLA compiles the
        # activation collectives into the same decode/prefill programs.
        # ``tp`` (the model-axis extent) scales the device plane's peaks
        # so MFU/BW utilizations attribute PER CHIP, and prices the
        # per-dispatch activation collectives (docs/serving-tp.md).
        self.mesh = mesh
        self.tp = int(mesh.shape.get("model", 1)) if mesh is not None else 1
        self.max_slots = max_slots
        limit = max_positions(getattr(model, "config", None))
        self.cache_len = min(cache_len, limit) if limit else cache_len
        self.eos_id = eos_id
        self.cache_dtype = cache_dtype
        self.buckets = tuple(
            b for b in (prefill_buckets or _default_buckets(self.cache_len))
            if b <= self.cache_len
        )
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)

        # KV layout (ROADMAP item 2 / docs/paged-kv.md): "contiguous" is
        # the original slot-owns-a-cache_len-region buffer; "paged"
        # carves one flat pool into fixed-size pages behind per-slot
        # block tables (vLLM PagedAttention idiom) — admission reserves
        # actual pages instead of worst-case context, prefixes share
        # refcounted pages, and handoff/tiering move page-aligned rows.
        # Golden tokens are layout-invariant (tests/test_paged_kv.py);
        # "contiguous" remains the fallback for one release.
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(
                f"kv_layout must be 'paged' or 'contiguous', got "
                f"{kv_layout!r}")
        self.paged = None
        self.draft_kv_reserved_tokens = 0
        if kv_layout == "paged":
            from llm_in_practise_tpu.serve.paged_kv import (
                PagedKV,
                kv_row_bytes,
            )

            pool_request = (kv_pool_tokens if kv_pool_tokens is not None
                            else max_slots * self.cache_len)
            if draft_model is not None and kv_pool_tokens is not None:
                # The draft cache is a CONTIGUOUS max_slots x cache_len
                # reservation living NEXT TO the page pool. An explicit
                # --kv-pool-tokens models the operator's KV byte budget,
                # so the draft's bytes come out of it (token-equivalent
                # at the target's bytes/row) — a paged engine with a
                # draft model must not over-admit against memory the
                # draft cache already spent. The default pool size keeps
                # worst-case reservation semantics (over-admission is
                # impossible there), so nothing is deducted.
                drow = kv_row_bytes(draft_model, cache_dtype)
                trow = kv_row_bytes(model, cache_dtype)
                self.draft_kv_reserved_tokens = -(
                    -max_slots * self.cache_len * drow // trow)
                pool_request -= self.draft_kv_reserved_tokens
                if pool_request < 2 * kv_page_size:
                    raise ValueError(
                        f"kv_pool_tokens={kv_pool_tokens} leaves only "
                        f"{pool_request} tokens after the draft cache's "
                        f"{self.draft_kv_reserved_tokens}-token "
                        "equivalent reservation — raise the pool budget "
                        "or drop the draft model")
            self.paged = PagedKV(
                model, max_slots=max_slots, cache_len=self.cache_len,
                page_size=kv_page_size,
                pool_tokens=pool_request,
                dtype=cache_dtype, mesh=mesh)
            # no contiguous engine cache exists in this layout; the
            # jitted paged programs gather transient views from the pool
            self.cache = None
        else:
            self.cache = model.init_cache(max_slots, self.cache_len,
                                          dtype=cache_dtype)
            self._vectorize_cache_index()
            if mesh is not None:
                self.cache = jax.device_put(self.cache,
                                            self._cache_shardings())
        # layers whose cache state is bounded (a sliding-window layer's
        # ring) live by slot beside the pool; only a program that tells
        # them which of its positions are real may write them, so every
        # prompt of such a model prefills through the paged chunk program
        # (one-shot admission's bucket-wide rows carry padding)
        self._slot_state = (self.paged is not None
                            and any(self.paged.by_slot))
        # a model whose later layers only read (a cross-decoder) runs them
        # at a prompt's last position alone: the chunk program tells it
        # which rows' prompts end (models/layers.py FINISH_KEY)
        self._reads_finish = self._slot_state and bool(getattr(
            getattr(model, "inner", model), "reads_finish", False))
        # a model that walks its paged layer's pages where they lie
        # (``reads_pages``: paged_kv.PagedKV.in_place): a decode program
        # gathers no view of that layer and has no view width
        self._reads_pages = (self.paged is not None
                             and any(self.paged.in_place))
        self.preemptions = 0            # paged pool-pressure preemptions
        self.rejected_too_large = 0     # prompts that can NEVER fit the pool
        self._paged_admit_blocked = False

        # Host-side slot table (slot_len mirrors the device cache index so
        # finish checks never force a device sync).
        self.slot_req: list[Request | None] = [None] * max_slots
        self.slot_ready = np.zeros((max_slots,), bool)
        # chunked prefill (vLLM enable_chunked_prefill parity): prompts
        # longer than this many tokens prefill one chunk per engine step,
        # interleaved with decode so long prompts don't stall active slots.
        if chunked_prefill is not None and chunked_prefill < 1:
            raise ValueError(
                f"chunked_prefill must be >= 1, got {chunked_prefill}"
            )
        self.chunked_prefill = chunked_prefill
        self.slot_prefill: dict[int, dict] = {}
        self.slot_last_token = np.zeros((max_slots,), np.int32)
        # One step of lookahead (paged layout;
        # docs/tutorials/08_serving_internals.md §5b). ``_flight``: the
        # program issued and not yet read. The last tokens live ON THE
        # DEVICE: every paged decode / multi /
        # mixed / chunk program takes the plane ``_tokens_dev`` and
        # returns it updated, so the next program can be issued before
        # this one's tokens are on the host; ``slot_last_token`` above is
        # the host's mirror, filled when a program is read. A token only
        # the host knows (a one-shot prefill's, a resumed stream's, a
        # speculative round's) waits in ``_tokens_fix`` (-1: none) and
        # the next program puts it into the plane.
        self._flight: _Flight | None = None
        self._ahead: _Flight | None = None  # issued past the one being read
        self._tokens_dev = None
        if self.paged is not None:
            plane = np.zeros((max_slots,), np.int32)
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                # the form the programs return it in (one jit-cache
                # entry a program, whoever built the plane)
                self._tokens_dev = jax.device_put(
                    plane, NamedSharding(mesh, PartitionSpec()))
            else:
                self._tokens_dev = jnp.asarray(plane)
        self._tokens_fix = np.full((max_slots,), -1, np.int32)
        # why a row that took its last deterministic step (budget, cache
        # room) ends when its program is read: it is left out of every
        # program issued until then; and the rows whose stream ended in
        # EOS while a later program that writes their pages was unread:
        # their slot and pages go when that program is read
        self.slot_closing: list[str | None] = [None] * max_slots
        self._zombies: set[int] = set()
        # who held a slot at the open step's begin, by uid: the step's
        # end books its thread states to them and to the holders then
        # (CP_THREAD_STATES); one that finishes on the way is booked
        # there and leaves
        self._step_held: dict[int, Request] = {}
        # why this step does not run ahead (None: nothing forbids it),
        # and the reason the previous step left for this one
        self._step_why: str | None = None
        self._next_why: str | None = None
        self._flew = False              # this step issued a program
        self._read = False              # ... found one unread
        self.slot_len = np.zeros((max_slots,), np.int64)
        self.slot_budget = np.zeros((max_slots,), np.int64)  # tokens remaining
        self._temperature = np.ones((max_slots,), np.float32)
        self._top_k = np.zeros((max_slots,), np.int32)
        self._top_p = np.ones((max_slots,), np.float32)
        self._greedy = np.zeros((max_slots,), bool)
        # Constrained decoding (serve/constrain.py, ISSUE 12): per-slot
        # grammar cursor (None = unconstrained). The mask (one
        # automaton state per slot) is built on the host as part of
        # the dispatch plan, and the masked twin
        # programs apply it in-dispatch — 1 dispatch/step holds with
        # grammar on, on both KV layouts. Engine-thread only.
        self.slot_constraint: list = [None] * max_slots
        # Per-slot adapter name (multi-LoRA, ISSUE 15; None = base).
        # Engine-thread only; joins the dispatch plan as the gathered
        # row-index array the *_lora twins consume.
        self.slot_adapter: list[str | None] = [None] * max_slots
        # lifetime grammar telemetry (engine-thread writes, scrape-side
        # monotone-float reads — the collective_* counter convention):
        # llm_grammar_mask_seconds_total / llm_spec_grammar_rejects_total
        self.grammar_mask_seconds_total = 0.0
        self.spec_grammar_rejects = 0

        # Admission control (VERDICT r4 #5 — the reference's ingress
        # backpressure, `05-KEDA-AutoScale/vllm-ingress-backpressure.yaml`,
        # moved into the engine so oversubscription degrades BOUNDED
        # instead of stretching TTFT without limit: at conc 32 over 8
        # slots the r4 ladders measured 5-30 s TTFT p99 with every
        # request eventually served late). ``max_queue``: reject at
        # submit once this many requests wait (finish_reason
        # "queue_full"; the API layer maps it to HTTP 429).
        # ``queue_timeout_s``: shed requests still unadmitted after this
        # long — a client that would see a worse-than-SLA TTFT gets a
        # fast failure it can retry against another replica (the
        # gateway's retry/fallback chains consume exactly this). Both
        # default off: capacity tests and closed-loop benches that WANT
        # deep queues keep today's behavior.
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if queue_timeout_s is not None and queue_timeout_s <= 0:
            raise ValueError(
                f"queue_timeout_s must be > 0, got {queue_timeout_s}")
        self.max_queue = max_queue
        self.queue_timeout_s = queue_timeout_s
        # serializes the max_queue check-then-put: without it two HTTP
        # threads can both see depth N-1 and overshoot the bound
        self._submit_lock = threading.Lock()
        self.pending: "queue.Queue[Request]" = queue.Queue()
        self.stats = EngineStats()
        self._uid = itertools.count()
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._wake = threading.Event()  # set on submit; idle loop waits on it
        self._thread: threading.Thread | None = None

        # Prefix caching (vLLM APC parity): True -> default-sized cache.
        from llm_in_practise_tpu.serve.prefix_cache import (
            PagedPrefixIndex,
            PrefixCache,
        )

        if self.paged is not None:
            # Paged engines share PHYSICAL PAGES instead of copying
            # rows: the L1 "cache" is the hash-per-page index over the
            # pool itself (partial-prefix hits at page granularity,
            # refcounted COW sharing — see prefix_cache.PagedPrefixIndex).
            # A row-based PrefixCache instance passed in is replaced;
            # its budget knobs carry over.
            want = bool(prefix_cache) or kv_pool is not None
            idx = None
            if want:
                kwargs = {}
                if isinstance(prefix_cache, PrefixCache):
                    kwargs = dict(max_tokens=prefix_cache.max_tokens,
                                  min_prefix=prefix_cache.min_prefix)
                idx = PagedPrefixIndex(self.paged.pool, **kwargs)
                # admission pressure reclaims cold shared prefixes
                # before it preempts anybody
                self.paged.pool.reclaim = idx.evict_pages
            self.prefix_cache = idx
        elif prefix_cache is True or (not prefix_cache
                                      and kv_pool is not None):
            prefix_cache = PrefixCache()
            self.prefix_cache = prefix_cache
        else:
            self.prefix_cache = prefix_cache or None
        # Tiered offload (LMCache parity): L1 evictions flow into the
        # host/remote pool instead of vanishing; lookups cascade back up.
        # (Paged engines populate the tiers by write-through only: an
        # evicted shared page has no token-tuple key of its own.)
        self.kv_pool = kv_pool
        if (kv_pool is not None and self.prefix_cache is not None
                and self.paged is None):
            prior = self.prefix_cache.on_evict
            def _evict(key, entry, _prior=prior):
                if _prior is not None:
                    _prior(key, entry)
                # with write-through on, the entry already went down the
                # tiers at prefill time — re-offloading on eviction would
                # double every device_get + TCP put
                if not kv_pool.offload_on_put:
                    kv_pool.offload(list(key), entry)
            self.prefix_cache.on_evict = _evict

        # Speculative decoding (vLLM ngram/prompt-lookup parity, lossless):
        # draft K tokens per slot by matching the trailing n-gram earlier
        # in that slot's context, verify all K+1 positions in ONE forward,
        # keep the longest prefix that matches what greedy would emit.
        # Decode is HBM-bound (weights dominate the traffic), so the wider
        # verify step costs ≈ one normal step; every accepted draft is a
        # full decode step saved (2-3x measured on one v5e chip at 38%
        # acceptance on self-similar text). Greedy-only: with sampling the
        # verify comparison is no longer exact, so mixed batches fall
        # back. Equality with one-token decode is bitwise on CPU; on TPU
        # the wide matmul's different reduction order can flip near-tie
        # argmaxes — the emitted tokens are still exact greedy outputs of
        # the verify forward itself (the same caveat applies to any
        # batched-verify speculator, vLLM's included).
        if speculative_k is not None and speculative_k < 1:
            raise ValueError(f"speculative_k must be >= 1, got {speculative_k}")
        self.speculative_k = speculative_k
        self.speculative_ngram = speculative_ngram
        self.slot_hist: list[list[int] | None] = [None] * max_slots
        self.spec_proposed = 0
        self.spec_accepted = 0
        # fused spec-round accounting (the BENCH_SPEC_LADDER evidence):
        # rounds = spec-verify dispatches issued; round_tokens = tokens
        # those dispatches actually committed (accepted + bonus) —
        # tokens/dispatch on the spec path in two ints
        self.spec_rounds = 0
        self.spec_round_tokens = 0
        # Draft-MODEL speculation (vLLM draft-model / Eagle-style
        # proposer parity; the ngram speculator above is prompt-lookup):
        # a small model with its OWN slot KV cache proposes the k tokens
        # instead of the n-gram matcher. No activation hooks needed —
        # ``slot_hist`` already holds prompt+tokens, so a per-slot
        # ``_draft_sync`` watermark says how much of it the draft cache
        # has consumed; a lazy catch-up (chunked feed through the same
        # machinery as chunked prefill) covers initial prompt feed,
        # tokens emitted by non-spec steps, and rejected-token re-sync
        # uniformly (the draft cache index is pinned from the host every
        # dispatch, so stale rolled KV is simply overwritten in order).
        self.draft_model = draft_model
        self.draft_params = draft_params
        if draft_model is not None:
            if speculative_k is None:
                raise ValueError(
                    "draft_model needs speculative_k (the proposal len)")
            if draft_params is None:
                raise ValueError(
                    "draft_model needs draft_params (a None params tree "
                    "would fail opaquely inside the first jitted draft "
                    "dispatch on the serving thread)")
            self.draft_cache = draft_model.init_cache(
                max_slots, self.cache_len, dtype=cache_dtype)
            if mesh is not None:
                # TP serving (ISSUE 10 satellite): the draft is small —
                # REPLICATE its params and KV cache across the mesh
                # instead of sharding, so the draft roll/catch-up
                # programs run without collectives and their outputs
                # feed the sharded target's verify without resharding.
                # (An unplaced draft tree would sit committed on device
                # 0 and conflict with the mesh-placed target inside the
                # same jitted dispatch.)
                from jax.sharding import NamedSharding, PartitionSpec

                rep = NamedSharding(mesh, PartitionSpec())
                self.draft_params = draft_params = jax.device_put(
                    draft_params,
                    jax.tree_util.tree_map(lambda _: rep, draft_params))
                self.draft_cache = jax.device_put(
                    self.draft_cache,
                    jax.tree_util.tree_map(lambda _: rep,
                                           self.draft_cache))
            for layer in self.draft_cache:
                layer["index"] = jnp.zeros((self.max_slots,), jnp.int32)
            self._draft_sync = np.zeros((max_slots,), np.int64)
            self._draft_uid = np.full((max_slots,), -1, np.int64)
            # catch-up window: biggest normal re-sync is a fully
            # accepted fused round's k+1 verify tokens
            self._draft_window = max(
                16, 1 << speculative_k.bit_length())
        # Fused mixed-batch step (r6): while prompts are mid-chunked-
        # prefill AND slots are decoding, ONE jitted program advances
        # every prefill row a chunk and decodes every ready row a
        # token — mixed-load steps cost 1 dispatch instead of 2 (see
        # serve/mixed_step.py and docs/perf.md Finding 17).
        self.mixed_step = bool(mixed_step)
        self.mixed_blocks = 0
        # chunk dispatches: prompts that advanced a chunk, and the rows
        # the device computed for them (_note_chunk_rows)
        self.prefill_chunk_rows = 0
        self.prefill_chunk_row_slots = 0
        # pages the paged views gathered whole (_paged_view_idx; stays 0
        # for a flat pool, whose views gather rows)
        self.view_pages_gathered = 0
        # prompts that finished in a chunk, mixed or suffix program, by
        # where their first token was sampled (_note_first_token)
        self.first_tokens = {"program": 0, "host": 0}
        self._log = get_logger("serve.engine")
        # request tracing (obs/trace.py): spans parent to each request's
        # TraceContext; the process default keeps a single-process stack
        # (tests, chip sharing) on one correlated trace plane
        self.tracer = tracer if tracer is not None else get_tracer()
        # host-gap flight recorder (obs/steptrace.py, ISSUE 11): one
        # record per step(), partitioning the step's wall clock into
        # named host activities + device-busy time. Engine-thread
        # writer; /metrics reads its swapped snapshot.
        # LLM_TPU_STEPTRACE=off disables (tests pin golden-token
        # parity either way).
        self.steptrace = steptrace if steptrace is not None else StepTrace()
        # recent finished requests for GET /debug/requests — each
        # carries its critical-path breakdown (Request.cp). deque
        # append/iteration are GIL-atomic; HTTP readers snapshot with
        # list() (same contract as the slot_prefill .get reads).
        self.finished: deque = deque(maxlen=128)
        self._mixed_fallbacks_logged: set[str] = set()
        # Guaranteed chunked-prefill budget: every engine step runs up to
        # this many prefill chunks BEFORE any decode work, so decode load
        # can never starve a prompt that is mid-prefill (the TTFT-fairness
        # guarantee chunked prefill exists for — vLLM enable_chunked_prefill,
        # Deployment/Ray/serve_run_examples/deepseek.py:32-35).
        if prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1, got {prefill_budget}"
            )
        self.prefill_budget = prefill_budget

        # Disaggregated serving (serve/disagg.py — the llm-d prefill/
        # decode split). ``role`` is a *soft* constraint the metrics make
        # assertable, not a hard gate: a decode replica whose handoff
        # entry was lost re-prefills locally (graceful degradation, the
        # llm-d fallback), and the ``local_prefills`` counter + a
        # logged-once warning surface that it happened. A prefill
        # replica needs a ``handoff`` store to publish into; requests
        # carrying a ``handoff_id`` finish at the end of prefill with
        # ``finish_reason="handoff"`` instead of occupying a decode slot.
        from llm_in_practise_tpu.serve.disagg import validate_roles

        self.role = validate_roles(role)
        self.handoff = handoff
        if role == "prefill" and handoff is None:
            raise ValueError(
                "role='prefill' needs a handoff store to publish KV into "
                "(serve.disagg.LocalHandoff or RemoteHandoff)")
        self.handoff_published = 0      # entries pinned into the store
        self.handoff_publish_failed = 0
        # publisher workers: the device→host copy + TCP put of each
        # handoff run OFF the engine thread (a dead pool server must
        # stall only the waiting handoff request, not the decode loop).
        # A small POOL, not one thread: publishes are independent I/O,
        # and serializing them would stack each one's transfer — or,
        # pool-down, its full connect timeout — onto every later
        # request's KV-ready time. Unbounded queue is safe: in-flight
        # handoffs are bounded by the router, which waits on each
        # publish before dispatching the decode half.
        self._publish_queue: "queue.Queue" = queue.Queue()
        self._publishers: list[threading.Thread] = []
        self._n_publishers = min(4, max_slots)
        self._publish_lock = threading.Lock()  # counter increments
        self.kv_admitted = 0            # requests seeded by external KV
        self.kv_rejected = 0            # external entries that failed checks
        self.local_prefills = 0         # prefills a decode replica ran
        self._decode_prefill_logged = False

        # Session-native serving (serve/sessions.py, ISSUE 17): requests
        # carrying a session_id pin their conversation KV across turns —
        # the store chains into the page pool's reclaim hook (after the
        # COW index, so sessions yield to active slots) and, with a
        # handoff store, publishes each finished turn for fleet-wide
        # migration. Attached AFTER the paged/prefix/handoff wiring
        # above — attach() reads all three.
        self.session_store = session_store
        if session_store is not None:
            session_store.attach(self)

        # SLO goodput thresholds (obs/meter.py GoodputMeter; exported
        # as llm_goodput_tokens_total{slo=…}); the tracer enables
        # per-phase blame of violated requests from the span ring
        self.stats.goodput.tracer = self.tracer
        if ttft_slo_s is not None or tpot_slo_s is not None:
            self.stats.goodput.configure(ttft_slo_s, tpot_slo_s)

        # Device-plane cost model (obs/cost.py): analytic FLOPs/bytes
        # per dispatch → live per-phase MFU / HBM-bandwidth-utilization
        # gauges. Fail-open None for model families the analytic
        # geometry doesn't cover (the gauges just don't render).
        # Under TP the peaks scale by the mesh's model extent so the
        # utilizations attribute per chip (ISSUE 10 satellite).
        self.cost_model = CostModel.from_model(model, params,
                                               cache_dtype=cache_dtype,
                                               tp=self.tp)
        # tensor-parallel collective attribution (docs/serving-tp.md):
        # per-chip ICI wire bytes of each dispatch's row-parallel
        # activation all-reduces (analytic — cost model), and the
        # lower-bound seconds they cost at datasheet ICI bandwidth.
        # Engine-thread writes, scrape-thread reads of monotone floats
        # (the single-writer convention of the spec_* counters). Both
        # stay 0.0 at tp=1, so the /metrics families render zeros there.
        self.collective_bytes_total = 0.0
        self.collective_seconds_total = 0.0
        # int8 quantized collectives (parallel/collectives.py): the
        # model facade carries the behavior; the engine only needs the
        # flag to halve the wire-byte attribution
        from llm_in_practise_tpu.parallel.collectives import (
            TPQuantizedCollectives,
        )

        self.tp_quantized_collectives = isinstance(
            getattr(model, "inner", model), TPQuantizedCollectives)

        # HBM ledger (obs/hbm.py, ISSUE 19): book this engine's durable
        # device allocations under their owner accounts; stop() returns
        # every byte. The page pool booked itself inside PagedKV; the
        # per-dispatch transient gather views pulse at the dispatch
        # sites. kv.draft is the draft cache's REAL byte footprint —
        # the same quantity /debug/kv's draft_kv_reserved_tokens
        # expresses in pool tokens through the kv_row_bytes exchange
        # rate, so --speculative setups see the draft tax on the
        # ownership scoreboard.
        self._hbm = get_ledger()
        self._hbm_booked = {}  # engine thread + stop(); freed once
        self._hbm_book("weights/model", tree_bytes(self.params))
        if self.cache is not None:
            self._hbm_book("kv.contiguous", tree_bytes(self.cache))
        if self.draft_model is not None:
            self._hbm_book("weights/draft_model",
                           tree_bytes(self.draft_params))
            self._hbm_book("kv.draft", tree_bytes(self.draft_cache))

        # Every prefill program takes a row's last hidden state BEFORE
        # the output head (models/layers.py); a model that cannot split
        # its forward there is refused here, not left to run the head
        # over every position of every chunk.
        self._hidden_like = self._split_head_probe(model, self.params)
        if draft_model is not None:
            self._split_head_probe(draft_model, self.draft_params)

        # Dispatch accounting: every jitted engine program is wrapped so
        # /metrics (llm_dispatches_*) and the mixed-step tests can assert
        # dispatches/step instead of inferring it from wall-clock. The
        # compile meter rides the same wrap: a jit-cache miss's call
        # time is booked as compile seconds (llm_compile_*), so a 40 s
        # recompile mid-serving is a counter bump, not a mystery stall.
        self.dispatch_meter = DispatchMeter()
        self.compile_meter = CompileMeter()
        _c = lambda fn: self.dispatch_meter.wrap(  # noqa: E731
            self.compile_meter.wrap(fn))
        self._decode = _c(jax.jit(self._decode_fn, donate_argnums=(1,)))
        self._decode_spec = _c(jax.jit(self._decode_spec_fn,
                                       donate_argnums=(1,)))
        self._prefill = _c(jax.jit(self._prefill_fn))
        self._prefill_suffix = _c(jax.jit(self._prefill_suffix_fn))
        self._sample_first = _c(jax.jit(self._sample_first_fn))
        self._insert = _c(jax.jit(self._insert_fn, donate_argnums=(0,),
                                  static_argnames=("slot",)))
        self._insert_batch = _c(jax.jit(self._insert_batch_fn,
                                        donate_argnums=(0,)))
        self._insert_rows = _c(jax.jit(self._insert_rows_fn,
                                       donate_argnums=(0,),
                                       static_argnames=("slot",)))
        self._chunk_slot = _c(jax.jit(self._chunk_slot_fn,
                                      donate_argnums=(1,)))
        self._chunk_batch = _c(jax.jit(self._chunk_batch_fn,
                                       donate_argnums=(1,)))
        self._slot_rows = _c(jax.jit(self._slot_rows_fn,
                                     static_argnames=("bucket",)))
        self._mixed_raw = make_mixed_step(model)
        self._mixed = _c(jax.jit(self._mixed_raw,
                                 donate_argnums=(1,)))
        # Grammar-masked twins (serve/constrain.py): SEPARATE compiled
        # programs with a trailing additive-mask argument, not a flag
        # on the unmasked ones — unconstrained steps keep the exact
        # pre-constraint executables (golden parity by construction)
        # and never pay the (B, vocab) mask transfer. jit is lazy, so
        # an engine that never sees a constrained request never
        # compiles these.
        self._decode_masked = _c(jax.jit(self._decode_masked_fn,
                                         donate_argnums=(1,)))
        self._decode_spec_masked = _c(jax.jit(
            self._decode_spec_masked_fn, donate_argnums=(1,)))
        self._mixed_masked_raw = make_masked_mixed_step(model)
        self._mixed_masked = _c(jax.jit(self._mixed_masked_raw,
                                        donate_argnums=(1,)))
        if self.paged is not None:
            # Paged twins of the engine programs: same RAW bodies (the
            # math that pins golden parity) between a page gather and a
            # window scatter, one dispatch each — see the "jitted
            # pieces, paged" section. The pool is donated so updates
            # are in place; the contiguous view is a transient XLA
            # frees between dispatches.
            self._pg_decode = _c(jax.jit(self._paged_decode_fn,
                                         donate_argnums=(1,)))
            self._pg_spec = _c(jax.jit(self._paged_spec_fn,
                                       donate_argnums=(1,)))
            self._pg_chunk = _c(jax.jit(self._paged_chunk_fn,
                                        donate_argnums=(1,)))
            self._pg_mixed = _c(jax.jit(self._paged_mixed_fn,
                                        donate_argnums=(1,)))
            self._pg_decode_masked = _c(jax.jit(
                self._paged_decode_masked_fn, donate_argnums=(1,)))
            self._pg_spec_masked = _c(jax.jit(
                self._paged_spec_masked_fn, donate_argnums=(1,)))
            self._pg_mixed_masked = _c(jax.jit(
                self._paged_mixed_masked_fn, donate_argnums=(1,)))
            self._pg_write_rows = _c(jax.jit(self._paged_write_rows_fn,
                                             donate_argnums=(0,)))
            self._pg_gather_rows = _c(jax.jit(self._paged_gather_rows_fn))
            self._pg_page_copy = _c(jax.jit(self._paged_page_copy_fn,
                                            donate_argnums=(0,)))
        if draft_model is not None:
            self._draft_chunk = _c(jax.jit(self._draft_chunk_fn,
                                           donate_argnums=(1,)))
            self._draft_roll = _c(jax.jit(self._draft_roll_fn,
                                          donate_argnums=(1,),
                                          static_argnames=("k",)))
        if adapter_registry is not None:
            # Adapter twins (serve/multi_lora.py, ISSUE 15 — the
            # grammar-masked-twin economics): SEPARATE compiled programs
            # taking a KW-ONLY ``lora`` pytree (per-row bank indices +
            # the stacked A/B factor banks) pushed as the thread-local
            # lora context INSIDE the traced body, so the facade's
            # interceptor adds the gathered low-rank delta on the LoRA
            # target matmuls. Keyword-only keeps every positional
            # donate_argnums index valid; jit laziness means a step
            # whose rows are all base runs the base executable and the
            # twin never compiles. Draft programs deliberately have NO
            # twins — drafts stay base-model (ISSUE 15) and rejected
            # drafts cost nothing; the verify dispatch IS the target
            # forward, so the spec twins below carry the delta.
            from llm_in_practise_tpu.serve.multi_lora import lora_wrap

            self._decode_lora = _c(jax.jit(
                lora_wrap(self._decode_fn), donate_argnums=(1,)))
            self._decode_spec_lora = _c(jax.jit(
                lora_wrap(self._decode_spec_fn), donate_argnums=(1,)))
            self._prefill_lora = _c(jax.jit(
                lora_wrap(self._prefill_fn)))
            self._prefill_suffix_lora = _c(jax.jit(
                lora_wrap(self._prefill_suffix_fn)))
            self._chunk_slot_lora = _c(jax.jit(
                lora_wrap(self._chunk_slot_fn), donate_argnums=(1,)))
            self._chunk_batch_lora = _c(jax.jit(
                lora_wrap(self._chunk_batch_fn), donate_argnums=(1,)))
            self._mixed_lora = _c(jax.jit(
                lora_wrap(self._mixed_raw), donate_argnums=(1,)))
            self._decode_masked_lora = _c(jax.jit(
                lora_wrap(self._decode_masked_fn), donate_argnums=(1,)))
            self._decode_spec_masked_lora = _c(jax.jit(
                lora_wrap(self._decode_spec_masked_fn),
                donate_argnums=(1,)))
            self._mixed_masked_lora = _c(jax.jit(
                lora_wrap(self._mixed_masked_raw), donate_argnums=(1,)))
            if self.paged is not None:
                self._pg_decode_lora = _c(jax.jit(
                    lora_wrap(self._paged_decode_fn),
                    donate_argnums=(1,)))
                self._pg_spec_lora = _c(jax.jit(
                    lora_wrap(self._paged_spec_fn), donate_argnums=(1,)))
                self._pg_chunk_lora = _c(jax.jit(
                    lora_wrap(self._paged_chunk_fn), donate_argnums=(1,)))
                self._pg_mixed_lora = _c(jax.jit(
                    lora_wrap(self._paged_mixed_fn), donate_argnums=(1,)))
                self._pg_decode_masked_lora = _c(jax.jit(
                    lora_wrap(self._paged_decode_masked_fn),
                    donate_argnums=(1,)))
                self._pg_spec_masked_lora = _c(jax.jit(
                    lora_wrap(self._paged_spec_masked_fn),
                    donate_argnums=(1,)))
                self._pg_mixed_masked_lora = _c(jax.jit(
                    lora_wrap(self._paged_mixed_masked_fn),
                    donate_argnums=(1,)))
        # Block-diffusion decoding (serve/block_step.py): a model with
        # block_length > 1 reveals part of a block a pass instead of
        # committing one token. The engine keeps admission, prefill and
        # the finish funnel; the decoder owns the block state and the
        # one block program. Everything that has no block form yet is
        # refused here, with its reason.
        from llm_in_practise_tpu.serve.block_step import (
            BlockDecoder,
            block_length_of,
        )

        self.block = None
        if block_length_of(model) > 1:
            BlockDecoder.check_engine(self)
            self.block = BlockDecoder(self)
        # A model that counts what its steps did (a held share of routed
        # experts, a latent cache: serve/step_stats.py) has the paged
        # programs return those counts; what it cannot meet yet is
        # refused here, with its reason.
        from llm_in_practise_tpu.serve.step_stats import (
            StepStats,
            stats_model,
        )

        core = stats_model(model)
        self.step_stats = None if core is None else StepStats(self, core)
        if self._slot_state:
            StepStats.check_engine(self, "a model with layers held by slot")
        # expert load, whoever books it (/metrics llm_moe_*_total)
        self.routing_load = (self.block.routing if self.block is not None
                             else self.step_stats.load
                             if self.step_stats is not None else None)

    # --- jitted pieces -------------------------------------------------------

    def _split_head_probe(self, model, params):
        """One abstract trace of the two halves every prefill program
        is built from (``models/layers.py``). Returns the shape and dtype
        of a row's final-norm hidden state, ``(1, hidden)``."""
        def halves(p):
            last, _ = last_position_hidden(
                model, p, jnp.zeros((1, 8), jnp.int32),
                jnp.ones((1,), jnp.int32),
                model.init_cache(1, 8, dtype=self.cache_dtype))
            return last, head_logits(model, p, last)

        try:
            return jax.eval_shape(halves, params)[0]
        except TypeError as e:
            raise ValueError(
                f"{type(model).__name__} cannot split its forward before "
                "the output head: the engine's prefill programs need the "
                "`return_hidden` and `head_only` keywords on its "
                f"__call__ (models/layers.py): {e}") from e

    def _sample_first_fn(self, rng, logits, temperature, top_k, top_p,
                         greedy, *gmask):
        """First tokens from (B, vocab) prefill logits ON THE HOST PATH:
        a one-shot admission batch, and a chunked prompt whose logits
        the host reads first (a grammar's start state: ``gmask``, at
        most one additive (B, vocab) mask). One program a batch size;
        the sampler is the decode programs'."""
        logits = sum(gmask, logits.astype(jnp.float32))
        return sample_token_batched(
            rng, logits, temperature=temperature, top_k=top_k,
            top_p=top_p, greedy=greedy).astype(jnp.int32)

    def _vectorize_cache_index(self):
        """Scalar per-layer cache index -> (max_slots,) vector."""
        for layer in self.cache:
            layer["index"] = jnp.zeros((self.max_slots,), jnp.int32)

    def _cache_shardings(self):
        """KV heads ('k'/'v' buffers, second-to-last dim in either cache
        layout) shard over the ``model`` axis; everything else (latent
        MLA 'kv' buffers, indices) replicates."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from llm_in_practise_tpu.utils.tree import path_str

        tp = self.mesh.shape.get("model", 1)

        def leaf(path, x):
            key = path_str(path).rsplit("/", 1)[-1]
            if key in ("k", "v") and tp > 1 and x.shape[-2] % tp == 0:
                spec = [None] * x.ndim
                spec[-2] = "model"
                return NamedSharding(self.mesh, P(*spec))
            return NamedSharding(self.mesh, P())

        return jax.tree_util.tree_map_with_path(leaf, self.cache)

    def _decode_fn(self, params, cache, tokens, rng, temperature, top_k, top_p, greedy):
        logits, cache = self.model.apply(
            {"params": params}, tokens[:, None], deterministic=True, cache=cache
        )
        next_tok = sample_token_batched(
            rng, logits[:, -1, :].astype(jnp.float32),
            temperature=temperature, top_k=top_k, top_p=top_p, greedy=greedy,
        )
        return next_tok.astype(jnp.int32), cache

    def _decode_spec_fn(self, params, cache, tokens, base, mask):
        """Fused speculative round (serve/mixed_step.spec_verify_block):
        verify the (B, K+1) proposed tokens, accept on DEVICE and fix
        the per-slot index — one dispatch per spec round."""
        return spec_verify_block(self.model, params, cache, tokens,
                                 base, mask)

    def _decode_masked_fn(self, params, cache, tokens, rng, temperature,
                          top_k, top_p, greedy, gmask):
        """Grammar-masked single-token decode: the ``_decode_fn`` body
        plus the (B, vocab) additive logit mask staged by the host from
        each constrained slot's automaton state (serve/constrain.py).
        Zero rows leave unconstrained slots' sampling untouched."""
        logits, cache = self.model.apply(
            {"params": params}, tokens[:, None], deterministic=True,
            cache=cache
        )
        next_tok = sample_token_batched(
            rng, logits[:, -1, :].astype(jnp.float32) + gmask,
            temperature=temperature, top_k=top_k, top_p=top_p,
            greedy=greedy,
        )
        return next_tok.astype(jnp.int32), cache

    def _decode_spec_masked_fn(self, params, cache, tokens, base, mask,
                               gmasks):
        """Grammar-masked fused spec round: (B, K+1, vocab) staged
        masks — position ``j`` carries the automaton state after the
        first ``j`` drafts, so a grammar-forbidden draft truncates the
        on-device acceptance cumprod exactly like an argmax mismatch."""
        return spec_verify_block(self.model, params, cache, tokens,
                                 base, mask, gmasks=gmasks)

    def _prefill_fn(self, params, prompt_ids, length):
        """prompt_ids: (B, bucket), length: (B,). Returns per-request
        last-valid logits (B, vocab) and a B-row, BUCKET-length prefill
        cache (only bucket rows are ever written — allocating B x
        cache_len here would transiently rival the whole engine cache at
        a saturated admission burst). B > 1 = batched admission: several
        same-bucket prompts prefill in ONE dispatch (vLLM batches waiting
        prefills the same way; on TPU the batch dim also feeds the MXU
        properly for short prompts)."""
        B, bucket = prompt_ids.shape
        cache = self.model.init_cache(B, bucket, dtype=self.cache_dtype)
        return last_position_logits(self.model, params, prompt_ids,
                                    length, cache)

    def _primed(self, cache, prefix_rows, prefix_len):
        """Fresh 1-slot cache with prefix KV rows inserted, index offset."""
        primed = []
        for layer, rows in zip(cache, prefix_rows):
            new = {"index": jnp.full_like(layer["index"], prefix_len)}
            for key, buf in layer.items():
                if key == "index":
                    continue
                new[key] = jax.lax.dynamic_update_slice_in_dim(
                    buf, rows[key].astype(buf.dtype), 0, axis=1
                )
            primed.append(new)
        return primed

    def _prefill_suffix_fn(self, params, prefix_rows, prefix_len,
                           suffix_ids, suffix_len):
        """Prefill only the prompt suffix over pre-inserted prefix KV rows.

        ``prefix_rows``: per-layer {key: (1, bucket, ...)}; positions and
        causal masking follow from the cache index (= prefix_len), so this
        equals a cold prefill of the full prompt.
        """
        cache = self.model.init_cache(1, self.cache_len, dtype=self.cache_dtype)
        return last_position_logits(
            self.model, params, suffix_ids, suffix_len,
            self._primed(cache, prefix_rows, prefix_len))

    def _chunk_slot_fn(self, params, cache, chunk_ids, slot, done,
                       chunk_len):
        return self._chunk_slot_impl(self.model, params, cache, chunk_ids,
                                     slot, done, chunk_len)

    def _chunk_slot_impl(self, model, params, cache, chunk_ids, slot,
                         done, chunk_len):
        """One chunked-prefill step, DIRECTLY against the engine cache:
        slice ``slot``'s rows into a transient 1-slot view (index pinned
        to the host-tracked ``done`` — the device index may have drifted
        from other dispatches' writes into the reserved slot), run the
        fixed-size padded chunk, and scatter the chunk's KV back at
        ``(slot, done)``. The index is reset to ``done + chunk_len``
        (padding KV beyond it is overwritten by the next chunk / decode
        in order, and never attended). Only ONE slot-slice transient
        exists at a time, however many prefills are in flight.
        ``model`` is a parameter so the draft-model cache (speculative
        decoding) reuses the same machinery."""
        mini = []
        for layer in cache:
            m = {}
            for key, buf in layer.items():
                if key == "index":
                    m["index"] = jnp.full((1,), done, jnp.int32)
                else:
                    m[key] = jax.lax.dynamic_slice_in_dim(
                        buf, slot, 1, axis=0)
            mini.append(m)
        last, mini = last_position_logits(model, params, chunk_ids,
                                          chunk_len, mini)
        width = chunk_ids.shape[1]
        new = []
        for layer, m2 in zip(cache, mini):
            out = {}
            for key, buf in layer.items():
                if key == "index":
                    out["index"] = buf.at[slot].set(done + chunk_len)
                else:
                    rows = jax.lax.dynamic_slice_in_dim(
                        m2[key], done, width, axis=1)
                    zero = jnp.zeros((), jnp.int32)
                    out[key] = jax.lax.dynamic_update_slice(
                        buf, rows.astype(buf.dtype),
                        (slot, done) + (zero,) * (buf.ndim - 2))
            new.append(out)
        return last, new

    # shared pin/advance idiom of the batched chunk, draft, and fused
    # mixed-step paths — single definition in serve/mixed_step.py
    _pin_index = staticmethod(pin_index)

    def _chunk_batch_fn(self, params, cache, chunk_ids, starts, lens):
        """Advance EVERY slot one prefill chunk in a single dispatch,
        operating on the engine cache DIRECTLY — the multi-slot twin of
        :meth:`_chunk_slot_fn`, and the r5 long-context TTFT fix:
        per-slot chunk dispatches serialize concurrent long prompts
        (docs/perf.md Finding 5).
        (A gathered B-row mini cache was tried first and OOM'd: at 8K
        width the gather+scatter copies of full-width rows cost more
        HBM than the cache itself.)

        ``chunk_ids`` is (max_slots, chunk): real chunk tokens for
        mid-prefill rows, zeros elsewhere. ``starts`` pins each row's
        cache index for the forward (host-tracked ``done`` for prefill
        rows; the row's current length for others — their rows compute
        garbage KV beyond their index, which the overwrite-before-
        attend invariant already covers, same as the single-slot path's
        drift writes). ``lens`` is the real chunk length per row (0 for
        non-prefill rows), so the returned index ``starts + lens``
        advances exactly the prefilling rows. The caller guarantees
        every row's ``starts[i] + chunk <= cache_len`` (no clamped
        scatter can touch attended rows). Body shared with the fused
        mixed step (serve/mixed_step.py).
        """
        return batched_chunk(self.model, params, cache, chunk_ids,
                             starts, lens)

    def _draft_chunk_fn(self, params, cache, chunk_ids, slot, done,
                        chunk_len):
        """Chunked feed into the DRAFT cache (catch-up beyond the
        batched window: initial prompt sync, mostly)."""
        return self._chunk_slot_impl(self.draft_model, params, cache,
                                     chunk_ids, slot, done, chunk_len)

    def _draft_roll_fn(self, params, cache, catchup, starts, lens, *,
                       k: int):
        """One dispatch: feed each slot's un-synced tokens (``catchup``
        padded rows, index pinned to ``starts``) through the draft
        model, then roll ``k`` greedy draft tokens with a ``lax.scan``
        of single-token decodes. Returns ``(drafts (S, k), cache)``.
        The returned cache's index is ``starts + lens`` — the rolled
        tokens' KV beyond it is garbage-for-later, overwritten by the
        next round's catch-up (overwrite-before-attend, as everywhere
        else in this engine)."""
        model = self.draft_model
        last, cache2 = last_position_logits(
            model, params, catchup, lens, self._pin_index(cache, starts))
        # the catch-up apply advanced every row's index by the PADDED
        # width W; re-pin to the true filled length before rolling, or
        # draft tokens 2..k decode at wrong RoPE positions and write
        # their KV above the watermark (review r5: draft quality
        # collapsed to ~1 usable token whenever the gap < W)
        cache2 = self._pin_index(cache2, starts + lens)
        first = jnp.argmax(last, axis=-1).astype(jnp.int32)

        def body(carry, _):
            cache_c, tok = carry
            lg, cache_c = model.apply(
                {"params": params}, tok[:, None], deterministic=True,
                cache=cache_c)
            nxt = jnp.argmax(lg[:, 0, :], axis=-1).astype(jnp.int32)
            return (cache_c, nxt), nxt

        (cache3, _), rest = jax.lax.scan(
            body, (cache2, first), None, length=k - 1)
        drafts = jnp.concatenate(
            [first[:, None], jnp.swapaxes(rest, 0, 1)], axis=1)  # (S, k)
        return drafts, self._pin_index(cache3, starts + lens)

    def _draft_model_propose(self, active: list[int], k: int) -> dict:
        """Host side of draft-model proposal: re-sync each slot's draft
        cache to its ``slot_hist`` (chunked for big gaps), then one
        batched catch-up+roll dispatch. Returns {slot: [k tokens]}."""
        W = self._draft_window
        rows = []
        for s in active:
            hist = self.slot_hist[s]
            req = self.slot_req[s]
            if hist is None or req is None:
                continue
            if self._draft_uid[s] != req.uid:     # recycled slot
                self._draft_uid[s] = req.uid
                self._draft_sync[s] = 0
            # the roll writes up to len(hist)+k positions, and the
            # W-wide catch-up window must also fit — a clamped scatter
            # near the cache end would shift backward over already-
            # synced real KV (the idle-row clamp exists for dead rows
            # only; active rows must be exact, so skip them instead)
            # tightest post-catch-up watermark is len(hist)-1 (the last
            # token is always unsynced), so that is the window bound
            if (len(hist) + k > self.cache_len
                    or len(hist) - 1 + W > self.cache_len):
                # This slot now falls into the idle-row clamped dead
                # write below, which may overwrite its already-synced
                # draft KV near the cache tail. That is safe only while
                # the skip is permanent — so enforce the invariant:
                # drop the watermark, and any future re-admission of
                # this slot forces a full KV re-sync instead of
                # attending the clamped dead-write's corrupted rows
                # (ADVICE.md round 5).
                self._draft_uid[s] = -1
                continue
            # big gap (initial prompt): chunked feed down to <= W
            while len(hist) - int(self._draft_sync[s]) > W:
                done = int(self._draft_sync[s])
                chunk = hist[done: done + W]
                padded = np.zeros((1, W), np.int32)
                padded[0, :len(chunk)] = chunk
                _, self.draft_cache = self._draft_chunk(
                    self.draft_params, self.draft_cache,
                    jnp.asarray(padded), jnp.asarray(s, jnp.int32),
                    jnp.asarray(done, jnp.int32),
                    jnp.asarray(len(chunk), jnp.int32))
                self._draft_sync[s] = done + len(chunk)
            rows.append(s)
        if not rows:
            return {}
        catchup = np.zeros((self.max_slots, W), np.int32)
        starts = np.zeros((self.max_slots,), np.int32)
        lens = np.zeros((self.max_slots,), np.int32)
        for s in rows:
            hist = self.slot_hist[s]
            done = int(self._draft_sync[s])
            gap = hist[done:]
            catchup[s, :len(gap)] = gap
            starts[s] = done
            lens[s] = len(gap)
        for s in range(self.max_slots):
            if s not in rows:                      # idle rows: dead write
                starts[s] = min(int(self._draft_sync[s]),
                                self.cache_len - W)
        drafts, self.draft_cache = self._draft_roll(
            self.draft_params, self.draft_cache, jnp.asarray(catchup),
            jnp.asarray(starts), jnp.asarray(lens), k=k)
        drafts = np.asarray(drafts)
        out = {}
        for s in rows:
            self._draft_sync[s] = len(self.slot_hist[s])
            out[s] = [int(t) for t in drafts[s]]
        return out

    def _slot_rows_fn(self, cache, slot, bucket: int):
        """Copy ``slot``'s first ``bucket`` KV rows out as a 1-slot rows
        list (prefix-cache storage for the chunked path)."""
        rows = []
        for layer in cache:
            r = {}
            for key, buf in layer.items():
                if key == "index":
                    continue
                s = jax.lax.dynamic_slice_in_dim(buf, slot, 1, axis=0)
                r[key] = jax.lax.slice_in_dim(s, 0, bucket, axis=1)
            rows.append(r)
        return rows

    @staticmethod
    def _slot_write(eng, rows, slot):
        """Write ``rows`` (slot-axis size 1 or B) into ``eng`` at
        ``slot`` (scalar or (B,) vector), over the rows' own width of
        the sequence axis."""
        rows = rows.astype(eng.dtype)
        single = isinstance(slot, int)  # one slot: drop rows' slot axis
        return eng.at[slot, :rows.shape[1]].set(rows[0] if single else rows)

    def _insert_fn(self, engine_cache, prefill_cache, slot: int, length):
        """Copy a prefilled request's cache rows into ``slot``. The
        prefill cache may be bucket-length (one-shot path) or full-length
        (suffix/chunked paths); only its width is written."""
        new = []
        for eng, pre in zip(engine_cache, prefill_cache):
            layer = {}
            for key in eng:
                if key == "index":
                    layer["index"] = eng["index"].at[slot].set(length)
                else:
                    layer[key] = self._slot_write(
                        eng[key], pre[key], slot)
            new.append(layer)
        return new

    def _insert_batch_fn(self, engine_cache, pre_cache, slot_ids, lengths):
        """Scatter a B-row bucket-length prefill cache into B slots at
        once. ``slot_ids`` is a traced (B,) vector, so one compilation
        serves every slot combination of a given batch size."""
        new = []
        for eng, pre in zip(engine_cache, pre_cache):
            layer = {}
            for key in eng:
                if key == "index":
                    layer["index"] = eng["index"].at[slot_ids].set(lengths)
                else:
                    layer[key] = self._slot_write(
                        eng[key], pre[key], slot_ids)
            new.append(layer)
        return new

    def _insert_rows_fn(self, engine_cache, rows, slot: int, length):
        """Copy stored prefix rows (bucket-length) directly into ``slot``."""
        new = []
        for eng, layer_rows in zip(engine_cache, rows):
            layer = {}
            for key in eng:
                if key == "index":
                    layer["index"] = eng["index"].at[slot].set(length)
                else:
                    layer[key] = self._slot_write(
                        eng[key], layer_rows[key], slot)
            new.append(layer)
        return new

    # --- jitted pieces, paged (serve/paged_kv.py) ----------------------------
    #
    # Each program is gather -> UNCHANGED raw engine body -> window
    # scatter, in ONE jitted dispatch. The host passes precomputed flat
    # pool-row index arrays (PagedKV.gather_idx / scatter_idx), so the
    # jitted code is pure take/at — no traced block-table arithmetic,
    # no retrace (shapes are the only static component: one compile per
    # pow2 view-width bucket per program, same bound as prefill
    # buckets). Discarded writes (idle rows, padding past a row's valid
    # window) are routed by the host indices into the reserved trash
    # page, which replaces the contiguous path's clamp-and-overwrite
    # dead-write reasoning wholesale. A pool stored by pages (a row
    # that is one vector: paged_kv.stored_by_pages) takes the same
    # arguments through paged_kv's accessors: its view gathers whole
    # pages from block-table columns, its writes split the host's flat
    # rows into (page, offset); a flat pool's programs are untouched.

    def _paged_view(self, pool, gidx, index_vec, *, slot=None, valid=None,
                    finish=None, in_place=False):
        """Gather each slot's pages into a contiguous cache view
        (slots, W, ...) with the per-slot index pinned from the host.
        ``gidx`` is :meth:`PagedKV.view_idx`'s: pool rows (S, W) for a
        flat pool, whole pages (S, W / page_size) for one stored by
        pages. A layer held by slot (``paged.by_slot``: its state is
        bounded) is not gathered: the model gets the plane's rows as they
        are, or the one row of ``slot`` (1,), and ``valid`` (S,), how
        many of this call's positions are real for each row (a layer
        that owns its writes must not take padding or a dead row's), and,
        for a model that asks (``reads_finish``), ``finish`` (S,) bool:
        whether the row's prompt ends in this call. ``in_place`` (a
        slot-plane decode of a model that ``reads_pages``): ``gidx`` is
        the whole block table (S, pages a slot), and a layer of
        ``paged.in_place`` is not gathered either: the model gets the
        pool's buffers as they are stored, the table under
        ``PAGES_KEY`` and ``valid``."""
        by_pages = self.paged.form == "pages"
        S, W = gidx.shape
        flat = gidx.reshape(-1)
        view = []
        # a model that counts what its steps did (serve/step_stats.py)
        # gets its zeroed entries beside each layer's gathered rows
        extra = ([{}] * len(pool) if self.step_stats is None
                 else self.step_stats.view_entries(S))
        for layer, more, tails, bounded, pages in zip(
                pool, extra, self.paged.tails, self.paged.by_slot,
                self.paged.in_place):
            d = {"index": index_vec.astype(jnp.int32), **more}
            if in_place and pages:
                view.append({**d, **layer, VALID_KEY: valid,
                             PAGES_KEY: gidx})
                continue
            if bounded:
                d[VALID_KEY] = valid
                if finish is not None:
                    d[FINISH_KEY] = finish
                for key, buf in layer.items():
                    d[key] = (buf if slot is None else
                              jax.lax.dynamic_slice_in_dim(
                                  buf, slot[0], 1, axis=0))
                view.append(d)
                continue
            for key, buf in layer.items():
                if by_pages:
                    d[key] = paged_kv.take_pages(buf, gidx, *tails[key])
                    continue
                # clip, not take's default fill: the host builds every
                # index inside the pool (unmapped pages read the trash
                # page), and fill's out-of-bounds select is one more
                # pass over the whole view (with it an 8B decode program
                # at 16 x 1,024 takes 35 ms, without it 19: PERF.md §6)
                d[key] = jnp.take(buf, flat, axis=0, mode="clip").reshape(
                    (S, W) + buf.shape[1:])
            view.append(d)
        return view

    def _live_rows(self, sidx):
        """Keyword arguments of :meth:`_paged_view` for a slot-plane
        decode: ``valid`` = 1 for the rows whose write-back lands in
        their own pages, 0 for those the host routed to the trash page
        (idle and mid-prefill rows). Nothing for a model without layers
        held by slot that reads no pages in place either: its programs
        lower as before."""
        if not (self._slot_state or self._reads_pages):
            return {}
        return {"valid": (sidx[:, 0] >= self.paged.page_size).astype(
            jnp.int32), "in_place": self._reads_pages}

    def _paged_writeback(self, pool, view, sidx, wstart, *, slot=None):
        """Scatter each row's freshly written window
        ``[wstart[s], wstart[s] + Wwin)`` from the view back into the
        pool at the host-resolved page rows ``sidx``. A layer held by
        slot wrote its own rows (the model's ring): they go back whole,
        or into ``slot``'s row. A layer handed over in place
        (``PAGES_KEY``) comes back as the pool it is: no pass follows."""
        by_pages = self.paged.form == "pages"
        S, Wwin = sidx.shape
        flat = sidx.reshape(-1)
        j = jnp.arange(Wwin)
        new = []
        for pl, vl, bounded in zip(pool, view, self.paged.by_slot):
            if PAGES_KEY in vl:     # the pool itself: the model wrote it
                new.append({key: vl[key] for key in pl})
                continue
            d = {}
            for key, buf in pl.items():
                vb = vl[key]
                if bounded:
                    d[key] = (vb.astype(buf.dtype) if slot is None else
                              jax.lax.dynamic_update_slice_in_dim(
                                  buf, vb.astype(buf.dtype), slot[0],
                                  axis=0))
                    continue
                W = vb.shape[1]
                pos = jnp.clip(wstart[:, None] + j[None, :], 0, W - 1)
                idx = pos.reshape((S, Wwin) + (1,) * (vb.ndim - 2))
                rows = jnp.take_along_axis(vb, idx, axis=1)
                if by_pages:
                    d[key] = paged_kv.set_page_rows(
                        buf, flat, rows.reshape(S * Wwin, -1))
                    continue
                d[key] = buf.at[flat].set(
                    rows.reshape((S * Wwin,) + vb.shape[2:]).astype(
                        buf.dtype))
            new.append(d)
        return new

    def _view_stats(self, view) -> tuple:
        """What a program returns behind its pool: one part of step
        statistics read off the view its body returned, or nothing."""
        return (() if self.step_stats is None
                else (self.step_stats.of_view(view),))

    # The last-token plane. Every program below takes ``tokens``
    # (max_slots,), the plane as the program before it returned it, and
    # ``fix`` (max_slots,), the tokens only the host knew (-1: none),
    # and returns the plane with its own tokens in: a row that decoded
    # holds the token it sampled, a row whose prompt the program ended
    # its first, every other row what it held. The next program can so be
    # issued before anyone has read this one's tokens.

    @staticmethod
    def _plane_in(tokens, fix):
        return jnp.where(fix >= 0, fix, tokens)

    def _plane_out(self, tokens, new, sidx):
        """``tokens`` with ``new`` in the rows that decoded: those whose
        write-back lands in their own pages (the host routes every other
        row's to the trash page, :meth:`_live_rows`' rule)."""
        return jnp.where(sidx[:, 0] >= self.paged.page_size,
                         new.astype(tokens.dtype), tokens)

    def _paged_decode_fn(self, params, pool, gidx, index_vec, sidx,
                         tokens, fix, rng, temperature, top_k, top_p,
                         greedy):
        tokens = self._plane_in(tokens, fix)
        view = self._paged_view(pool, gidx, index_vec,
                                **self._live_rows(sidx))
        tok, view = self._decode_fn(params, view, tokens, rng,
                                    temperature, top_k, top_p, greedy)
        return (tok, self._plane_out(tokens, tok, sidx),
                self._paged_writeback(pool, view, sidx, index_vec),
                *self._view_stats(view))

    def _paged_spec_fn(self, params, pool, gidx, index_vec, sidx, tokens,
                       mask):
        view = self._paged_view(pool, gidx, index_vec)
        # base = the pinned per-dispatch index; the body's index fixup
        # matters only within the view (the pool derives each
        # dispatch's index from host slot_len), but the ACCEPTANCE runs
        # on device exactly like the contiguous twin — rejected rows'
        # page contents are overwritten by the next real write
        out, n_acc, view = spec_verify_block(
            self.model, params, view, tokens, index_vec, mask)
        return out, n_acc, self._paged_writeback(
            pool, view, sidx, index_vec)

    def _paged_chunk_fn(self, params, pool, slots, gidx, chunk_ids,
                        starts, lens, sidx, n_rows, ends, rng, tokens,
                        fix, temperature, top_k, top_p, greedy):
        """Advance the listed mid-prefill ROWS one chunk each against
        the page pool, and end the prompts that finish here in their
        first token: the device work follows the number of rows that
        chunk, not ``max_slots``.

        ``slots`` (R,) names each row's slot, ``gidx`` (R, W) / ``sidx``
        (R, C) are its pool-row gather and window-scatter indices,
        ``chunk_ids`` (R, C), ``starts`` / ``lens`` (R,) as in
        :func:`batched_chunk`. R is fixed (``max_slots``, or 1 for a
        suffix) whatever the number of rows that chunk, so the compile
        key holds no row count: a loop with the TRACED trip count
        ``n_rows`` takes the first ``n_rows`` rows one a trip — gather
        the row's pages, run the shared ``batched_chunk_hidden`` body
        on that one-row view, scatter its chunk window back — and never
        visits the padding behind them. One row a trip because a chip
        timing found it the fastest per row at every row count (PERF.md,
        PR 28). A trip stops BEFORE the output head: the loop carries
        each row's last-position hidden state by slot, and
        :meth:`_prefill_tail` runs the head once over that plane, and
        the sampler on its logits, when ``ends`` (max_slots,) says some
        row's prompt ends in this program (1: the host samples its first
        token from the logits, 2: the program's sample is its first
        token and goes into the last-token plane; ``rng`` and the slot
        plane's sampling arrays are the sampler's, as in the decode
        programs). Returns ``((max_slots,) first tokens, (max_slots,
        vocab) last-position logits, the last-token plane, pool)``, the
        first two by slot, meaningful for the rows that finish, zeros
        when none does (and, behind the pool, a part of step statistics
        for a model that counts them)."""
        lora = current_lora()
        stats = self.step_stats

        def trip(i, carry):
            pool, out, acc = carry
            slot, r_gidx, r_ids, r_starts, r_lens, r_sidx = (
                jax.lax.dynamic_slice_in_dim(a, i, 1, axis=0)
                for a in (slots, gidx, chunk_ids, starts, lens, sidx))
            view = self._paged_view(
                pool, r_gidx, r_starts, slot=slot, valid=r_lens,
                **({"finish": jnp.take(ends, slot) > 0}
                   if self._reads_finish else {}))
            # the adapter index rides the SLOT plane: this row's entry
            mine = None if lora is None else dict(lora, idx={
                rb: jnp.take(ix, slot) for rb, ix in lora["idx"].items()})
            with lora_context(mine):
                last, view = batched_chunk_hidden(
                    self.model, params, view, r_ids, r_starts, r_lens)
            if stats is not None:
                acc = (stats.add_row(acc[0], stats.of_view(view), slot[0]),)
            return (self._paged_writeback(pool, view, r_sidx, r_starts,
                                          slot=slot),
                    jax.lax.dynamic_update_slice_in_dim(
                        out, last, slot[0], axis=0), acc)

        like = self._hidden_like
        out = jnp.zeros((self.max_slots, like.shape[-1]), like.dtype)
        # step statistics, where the model counts them: summed over the
        # trips, a part of their own behind the pool (else nothing)
        acc = () if stats is None else (stats.zero_rows(),)
        pool, out, acc = jax.lax.fori_loop(0, n_rows, trip,
                                           (pool, out, acc))
        first, last = self._prefill_tail(
            params, out, jnp.any(ends > 0), rng, temperature, top_k, top_p,
            greedy)
        plane = jnp.where(ends == 2, first, self._plane_in(tokens, fix))
        return first, last, plane, pool, *acc

    def _prefill_tail(self, params, hidden, finish, rng, temperature,
                      top_k, top_p, greedy):
        """The tail of a paged prefill program: ``hidden``
        (max_slots, hidden), the last-position states by slot, through
        ONE pass of the output head and the decode programs' sampler,
        under a ``cond`` on the traced ``finish``: three chunks in four
        end no prompt and need neither. A block-diffusion engine
        samples nothing from a prefill (its first block opens all-mask)
        and gets zeros for tokens. Returns ``(first tokens, logits)``."""
        def tail(hidden):
            logits = head_logits(self.model, params, hidden)
            if self.block is not None:
                return jnp.zeros((self.max_slots,), jnp.int32), logits
            first = sample_token_batched(
                rng, logits.astype(jnp.float32), temperature=temperature,
                top_k=top_k, top_p=top_p, greedy=greedy)
            return first.astype(jnp.int32), logits

        like = jax.eval_shape(tail, hidden)
        return jax.lax.cond(
            finish, tail,
            lambda _: jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), like),
            hidden)

    def _paged_mixed_fn(self, params, pool, slots, pgidx, chunk_ids,
                        starts, lens, psidx, n_rows, ends, first_rng,
                        gidx, index_vec, sidx, tokens, fix, rng,
                        temperature, top_k, top_p, greedy, *,
                        gmask=None):
        """The paged fused mixed step, ONE dispatch: the prefill half
        is :meth:`_paged_chunk_fn`'s loop over the rows that chunk and
        its tail (the slot plane's sampling arrays serve both halves;
        ``first_rng`` is the tail's key, ``rng`` the decode's); the
        decode half is :func:`decode_scan` over the slot plane
        (mid-prefill and idle rows decode garbage into the trash
        page). No decode row receives a chunk write. Returns ``(first
        tokens, last-position logits, (max_slots, 1) decode tokens, the
        last-token plane, pool)``: no row is in both halves, so the
        plane takes the first tokens and the decoded ones."""
        first, chunk_last, tokens, pool, *acc = self._paged_chunk_fn(
            params, pool, slots, pgidx, chunk_ids, starts, lens, psidx,
            n_rows, ends, first_rng, tokens, fix, temperature, top_k,
            top_p, greedy)
        view = self._paged_view(pool, gidx, index_vec,
                                **self._live_rows(sidx))
        toks, view = decode_scan(self.model, params, view, tokens, rng,
                                 temperature, top_k, top_p, greedy,
                                 gmask=gmask)
        return (first, chunk_last, toks,
                self._plane_out(tokens, toks[:, -1], sidx),
                self._paged_writeback(pool, view, sidx, index_vec),
                *acc, *self._view_stats(view))

    def _paged_decode_masked_fn(self, params, pool, gidx, index_vec,
                                sidx, tokens, fix, rng, temperature,
                                top_k, top_p, greedy, gmask):
        """Paged twin of ``_decode_masked_fn``: gather → masked decode
        body → window scatter, one dispatch (grammar on, paged layout —
        the 1-dispatch-per-step invariant is layout-independent)."""
        tokens = self._plane_in(tokens, fix)
        view = self._paged_view(pool, gidx, index_vec,
                                **self._live_rows(sidx))
        tok, view = self._decode_masked_fn(
            params, view, tokens, rng, temperature, top_k, top_p,
            greedy, gmask)
        return (tok, self._plane_out(tokens, tok, sidx),
                self._paged_writeback(pool, view, sidx, index_vec))

    def _paged_spec_masked_fn(self, params, pool, gidx, index_vec, sidx,
                              tokens, mask, gmasks):
        view = self._paged_view(pool, gidx, index_vec)
        out, n_acc, view = spec_verify_block(
            self.model, params, view, tokens, index_vec, mask,
            gmasks=gmasks)
        return out, n_acc, self._paged_writeback(
            pool, view, sidx, index_vec)

    def _paged_mixed_masked_fn(self, params, pool, slots, pgidx,
                               chunk_ids, starts, lens, psidx, n_rows,
                               ends, first_rng, gidx, index_vec, sidx,
                               tokens, fix, rng, temperature, top_k,
                               top_p, greedy, gmask):
        """Grammar-masked twin of :meth:`_paged_mixed_fn` (the mask
        applies to the decode half only): a separate program, so
        unconstrained steps never carry the mask."""
        return self._paged_mixed_fn(
            params, pool, slots, pgidx, chunk_ids, starts, lens, psidx,
            n_rows, ends, first_rng, gidx, index_vec, sidx, tokens, fix,
            rng, temperature, top_k, top_p, greedy, gmask=gmask)

    def _paged_write_rows_fn(self, pool, rows, sidx):
        """Scatter B bucket-width row sets (one-shot prefill output, a
        prefix/handoff entry's rows) into pages; ``rows`` may carry an
        ``index`` key (pool iteration ignores it). Layers held by slot
        take no rows (a model that has them prefills through the chunk
        program and shares no prefix)."""
        by_pages = self.paged.form == "pages"
        S, Wb = sidx.shape
        flat = sidx.reshape(-1)
        new = []
        for pl, rl, bounded in zip(pool, rows, self.paged.by_slot):
            d = {}
            for key, buf in pl.items():
                if bounded:
                    d[key] = buf
                    continue
                rb = rl[key]
                if by_pages:
                    d[key] = paged_kv.set_page_rows(
                        buf, flat, rb.reshape(S * Wb, -1))
                    continue
                d[key] = buf.at[flat].set(
                    rb.reshape((S * Wb,) + rb.shape[2:]).astype(
                        buf.dtype))
            new.append(d)
        return new

    def _paged_gather_rows_fn(self, pool, gidx):
        """Index-free rows list (1, W, ...) per layer — the page-wise
        twin of ``_slot_rows_fn`` for prefix/handoff entries (the paged
        layers' rows only)."""
        if self.paged.form == "pages":
            return [{key: paged_kv.take_page_rows(buf, gidx, *tails[key])
                     for key, buf in layer.items()}
                    for layer, tails in zip(pool, self.paged.tails)]
        S, W = gidx.shape
        flat = gidx.reshape(-1)
        return [
            {} if bounded else
            {key: jnp.take(buf, flat, axis=0).reshape(
                (S, W) + buf.shape[1:])
             for key, buf in layer.items()}
            for layer, bounded in zip(pool, self.paged.by_slot)
        ]

    def _paged_page_copy_fn(self, pool, src, dst):
        """Copy one physical page's rows (COW fork: a write would land
        in a page some other reader still maps)."""
        if self.paged.form == "pages":
            # a layer held by slot has no pages: its first axis is slots
            return [layer if bounded else
                    {key: paged_kv.copy_page(buf, src, dst)
                     for key, buf in layer.items()}
                    for layer, bounded in zip(pool, self.paged.by_slot)]
        P = self.paged.page_size
        new = []
        for layer, bounded in zip(pool, self.paged.by_slot):
            d = {}
            for key, buf in layer.items():
                if bounded:
                    d[key] = buf
                    continue
                rows = jax.lax.dynamic_slice_in_dim(buf, src * P, P,
                                                    axis=0)
                d[key] = jax.lax.dynamic_update_slice_in_dim(
                    buf, rows, dst * P, axis=0)
            new.append(d)
        return new

    # --- paged host-side plumbing -------------------------------------------

    def _paged_width(self, need: int) -> int:
        """Pow2-bucketed view width covering ``need`` rows (bounded by
        ``cache_len`` — feasibility gates guarantee ``need`` fits)."""
        w = self.paged.page_size
        while w < need:
            w *= 2
        w = min(w, self.cache_len)
        if w < need:
            raise AssertionError(
                f"paged view width {w} < needed {need} "
                f"(cache_len {self.cache_len})")
        return w

    def _paged_index_vec(self, W: int, wwin: int) -> np.ndarray:
        """Per-row pinned cache index for a decode-family dispatch:
        active rows at their true length (the caller sized ``W`` so
        their writes fit un-clamped), mid-prefill rows at ``done``,
        free rows at 0 — clamped so even dead in-view writes stay
        inside the view (their scatter targets are trash anyway).
        Reads only host slot state, nothing paged: the CONTIGUOUS
        fused spec round reuses it with ``W = cache_len`` so the
        slot-state → index convention has one definition."""
        idx = np.zeros((self.max_slots,), np.int32)
        for s in range(self.max_slots):
            if s in self.slot_prefill:
                idx[s] = self.slot_prefill[s]["done"]
            elif self.slot_req[s] is not None:
                idx[s] = int(self.slot_len[s])
        return np.minimum(idx, max(W - wwin, 0)).astype(np.int32)

    def _paged_cow_fork(self, slot: int, start: int, width: int) -> None:
        """Fork any shared page the write window
        ``[start, start + width)`` would touch. With full-page-only
        sharing no live path writes inside a shared page (the index
        caps hits below the last prompt position, suffixes start at the
        share boundary, and spec rewind never dips below the prompt) —
        this is the defensive half of the COW contract, kept exact so a
        future scheduler change degrades to a page copy instead of
        corrupting a neighbour's prefix."""
        if width <= 0:
            return
        P = self.paged.page_size
        pool = self.paged.pool
        bt = self.paged.block_tables
        for lp in range(start // P,
                        min((start + width - 1) // P + 1,
                            self.paged.pages_per_slot)):
            page = int(bt[slot, lp])
            if page == 0 or pool.refcount(page) <= 1:
                continue
            fresh = pool.try_alloc(1)
            while fresh is None:
                # pool dry mid-fork: apply preemption pressure until a
                # page frees, exactly like the reserve loops — a single
                # victim whose pages are all still shared frees nothing
                # (a victim is picked with no program unread)
                self._drain("preempt")
                victim = self._paged_pick_victim(exclude=slot)
                if victim is None:
                    raise RuntimeError(
                        "page pool exhausted during COW fork")
                self._paged_preempt(victim)
                fresh = pool.try_alloc(1)
            self.paged.kv = self._pg_page_copy(
                self.paged.kv, jnp.asarray(page, jnp.int32),
                jnp.asarray(fresh[0], jnp.int32))
            bt[slot, lp] = fresh[0]
            pool.release([page])

    def _paged_pick_victim(self, exclude: int | None = None) -> int | None:
        """Preemption policy: the YOUNGEST occupied slot (highest uid)
        other than ``exclude`` — least work lost, and its re-prefill is
        mostly a page-index hit since its pages are registered on the
        way out (vLLM preempts LIFO for the same reason)."""
        best, best_uid = None, -1
        for s in range(self.max_slots):
            if s == exclude or self.slot_req[s] is None:
                continue
            uid = self.slot_req[s].uid
            if uid > best_uid:
                best, best_uid = s, uid
        return best

    def _paged_preempt(self, slot: int) -> None:
        """Preempt ``slot`` by recompute: register its pages in the
        prefix index (so re-admission is mostly a page hit), release
        them, and put the request back at the HEAD of the queue —
        already-emitted tokens ride along via the resume fields, so the
        client stream continues where it left off."""
        req = self.slot_req[slot]
        st = self.slot_prefill.pop(slot, None)
        if st is None and self.slot_ready[slot]:
            hist = self.slot_hist[slot]
            req.resume_last = hist[-1]
            req.resume_budget = int(self.slot_budget[slot])
            req.prompt_ids = list(hist[:-1])
            self._paged_register_pages(hist[:-1], slot, req.adapter)
        elif st is not None and st["done"] > 0:
            # mid-prefill: nothing emitted — requeue as a fresh prompt,
            # but keep the already-computed full pages reusable
            self._paged_register_pages(req.prompt_ids[:st["done"]], slot,
                                       req.adapter)
        self.paged.release_slot(slot)
        self.slot_req[slot] = None
        self.slot_ready[slot] = False
        self.slot_budget[slot] = 0
        self.slot_closing[slot] = None
        self.slot_hist[slot] = None
        # the adapter pin rides the requeue (req.adapter_ref stays
        # held); only the SLOT's stamp clears
        self.slot_adapter[slot] = None
        # the grammar cursor itself stays on req.constraint_state —
        # re-admission resumes from the exact grammar position
        self.slot_constraint[slot] = None
        if self.draft_model is not None:
            # force a full draft-cache re-sync if this slot is reused
            # for this request (its target KV is being recomputed)
            self._draft_uid[slot] = -1
        self.preemptions += 1
        self._hbm.note_reclaim("kv_pool.pages", "preempt")
        # the re-admission's wait + recompute are charged to the
        # preempt_recompute critical-path segment from this stamp on;
        # the queue-wait origin moves here too (the slotted time just
        # spent is already booked to its dispatch segments)
        req.requeue_time = time.monotonic()
        req.cp_queue_origin = req.requeue_time
        with self.pending.mutex:
            self.pending.queue.appendleft(req)
        self._log.info(
            "preempted slot %d (uid %d) under page-pool pressure; "
            "request requeued for recompute (resume at %d tokens)",
            slot, req.uid, len(req.prompt_ids))

    def _paged_reserve_active(self, active: list[int],
                              width: int) -> list[int]:
        """Reserve ``width`` more positions for every ready slot before
        a decode-family dispatch; preempted victims drop out of
        ``active``, and a slot that cannot grow even as the last
        occupant finishes with the contiguous layout's ``cache``
        reason. Returns the surviving active list."""
        out = list(active)
        for s in list(out):
            if s not in out or self.slot_req[s] is None:
                continue
            while not self.paged.extend(s, int(self.slot_len[s]) + width):
                victim = self._paged_pick_victim(exclude=s)
                if victim is None:
                    self._finish_slot(s, "cache")
                    if s in out:
                        out.remove(s)
                    break
                self._paged_preempt(victim)
                if victim in out:
                    out.remove(victim)
        return [s for s in out if self.slot_req[s] is not None
                and self.slot_ready[s]]

    def _pulse_view(self, W: int, n_slots: int | None = None) -> None:
        """Ledger-pulse this dispatch's transient gather view (account
        ``transient_view``): W tokens × the viewed rows at the pool's
        byte rate. XLA frees the view inside the dispatch, so only the
        account's high-water mark moves — the pool+view coexistence
        peak ROADMAP item 1's in-place paged attention reclaims."""
        self._hbm.pulse("transient_view", self.paged.view_bytes(W, n_slots))

    def _paged_view_idx(self, W: int, slots=None) -> np.ndarray:
        """:meth:`PagedKV.view_idx` for a view this step gathers, booked
        where the pool gathers whole pages (``view_pages_gathered``, the
        step record's ``view_pages``; a flat pool's records have no such
        field)."""
        idx = self.paged.view_idx(W, slots)
        if self.paged.form == "pages":
            self.view_pages_gathered += idx.size
            self.steptrace.note_extra(view_pages=idx.size)
        return idx

    def _paged_decode_plan(self, active: list[int], W: int):
        """Index arguments ``(gidx, index_vec, sidx)`` of a slot-plane
        decode at view width ``W``: every slot's pages gathered, each
        ``active`` row's new row scattered back (everything else to
        the trash page). Forks shared pages the writes would touch. A
        model that reads its pages in place (``_reads_pages``) gets the
        whole block table for ``gidx``, whatever ``W`` and the lengths:
        nothing is gathered (the step's record says ``view_pages`` 0
        for this plane)."""
        if self._reads_pages:
            W = self.cache_len
        idxv = self._paged_index_vec(W, 1)
        valid = np.zeros((self.max_slots,), np.int32)
        for s in active:
            valid[s] = 1
            self._paged_cow_fork(s, int(self.slot_len[s]), 1)
        if self.step_stats is not None:
            self.step_stats.note_decode_view(active, W)
        if self._reads_pages:
            self.steptrace.note_extra(view_pages=0)
            gidx = self.paged.block_tables.astype(np.int32)     # a copy
        else:
            gidx = self._paged_view_idx(W)
        return (jnp.asarray(gidx), jnp.asarray(idxv),
                jnp.asarray(self.paged.scatter_idx(idxv, valid, 1)))

    def _paged_decode_dispatch(self, f: _Flight, active: list[int],
                               sub, gmask=None, lora=None) -> None:
        """Issue one paged decode dispatch (the ``_decode_fn`` body, so
        the rng use matches the contiguous program exactly). Pages for
        the writes were reserved by the caller. ``gmask`` (constrained
        decoding) routes to the masked twin. ``lora`` (multi-LoRA)
        routes to the adapter twin of whichever program would run;
        both compose. The sampled tokens, shape (max_slots, 1), are
        ``f.toks``."""
        W = self.cache_len
        if not self._reads_pages:       # else: no view, and no width
            W = self._paged_width(
                max(int(self.slot_len[s]) for s in active) + 1)
            self._pulse_view(W)
        gidx, idxv, sidx = self._paged_decode_plan(active, W)
        args = (*self._plane_args(), sub, *self._sampling_args(active))
        kw = {} if lora is None else {"lora": lora}
        if gmask is not None:
            fn = (self._pg_decode_masked if lora is None
                  else self._pg_decode_masked_lora)
            tok, self._tokens_dev, self.paged.kv = fn(
                self.params, self.paged.kv, gidx, idxv, sidx, *args,
                jnp.asarray(gmask), **kw)
            f.toks = tok[:, None]
            return
        fn = self._pg_decode if lora is None else self._pg_decode_lora
        tok, self._tokens_dev, self.paged.kv, *counted = fn(
            self.params, self.paged.kv, gidx, idxv, sidx, *args, **kw)
        f.toks = tok[:, None]
        if self.step_stats is not None:
            f.stats = self.step_stats.pend("decode", counted)

    def _plane_args(self) -> tuple:
        """``(tokens, fix)`` of a paged program: the device's last-token
        plane and the tokens only the host knows, which this program
        puts into it (so they are handed over once)."""
        fix, self._tokens_fix = self._tokens_fix, np.full(
            (self.max_slots,), -1, np.int32)
        return self._tokens_dev, jnp.asarray(fix)

    def _paged_register_pages(self, token_ids, slot: int,
                              adapter: str | None = None) -> None:
        """Index every FULL page of ``token_ids`` (whose KV fills
        ``slot``'s first pages) for refcounted sharing. ``adapter``
        namespaces the chain keys (multi-LoRA prefix isolation)."""
        if self.prefix_cache is None:
            return
        nfull = len(token_ids) // self.paged.page_size
        if nfull <= 0:
            return
        pages = self.paged.slot_pages(slot)[:nfull]
        if len(pages) == nfull:
            self.prefix_cache.register(
                self._ns_ids(adapter,
                             token_ids[:nfull * self.paged.page_size]),
                pages)

    def _paged_gather_entry(self, slot: int, plen: int, last_logits):
        """Page-aligned prefix entry for ``slot``'s first ``plen``
        positions — rows span ceil(plen/P)*P, not a pow2 bucket nor
        ``cache_len``, so handoff/offload ship only live pages."""
        from llm_in_practise_tpu.serve import prefix_cache as pc
        from llm_in_practise_tpu.serve.paged_kv import pages_for

        width = pages_for(plen, self.paged.page_size) * self.paged.page_size
        gidx = self.paged.row_gather_idx(slot, width)
        rows = self._pg_gather_rows(self.paged.kv, jnp.asarray(gidx))
        return pc.PrefixEntry(length=plen, bucket=width, rows=rows,
                              last_logits=last_logits,
                              page_size=self.paged.page_size)

    def _paged_insert_entry(self, slot: int, entry, length: int) -> None:
        """Scatter a row-based entry's first ``length`` positions into
        ``slot``'s (already reserved) pages. Rows are padded on host to
        a pow2 bucket so the jitted scatter keeps a bounded compile
        set whatever widths the tiers shipped."""
        self._paged_cow_fork(slot, 0, length)
        Wb = self._bucket_for(length)
        padded = []
        for layer in entry.rows:
            d = {}
            for key, arr in layer.items():
                if key == "index":
                    continue
                # tier/handoff entries reach a paged engine as HOST
                # numpy (TieredKV.lookup(device=False), HostEntry), so
                # this materializes nothing from the device
                arr = np.asarray(arr)  # graftlint: disable=host-sync
                out = np.zeros((1, Wb) + arr.shape[2:], arr.dtype)
                out[:, :min(length, arr.shape[1])] = (
                    arr[:, :min(length, arr.shape[1])])
                d[key] = out
            padded.append(d)
        sidx = self.paged.rows_scatter_idx([slot], [length], Wb)
        self.paged.kv = self._pg_write_rows(
            self.paged.kv, padded, jnp.asarray(sidx))

    # --- public API ----------------------------------------------------------

    def _shed(self, req: Request) -> Request:
        """Fail a request fast with ``finish_reason="queue_full"``: the
        stream closes immediately with zero tokens, the caller (API
        layer / gateway) turns that into 429 + retry-elsewhere."""
        req.finish_time = time.monotonic()
        req.finish_reason = "queue_full"
        self._record_finished(req)
        req.tokens.put(_FINISH)
        with self.stats.lock:
            self.stats.requests_shed += 1
        return req

    def submit(self, prompt_ids, params: SamplingParams | None = None, *,
               kv_entry=None, handoff_id: str | None = None,
               trace=None, adapter: str | None = None,
               session_id: str | None = None) -> Request:
        """``kv_entry`` (optional): a :class:`~.kv_pool.HostEntry` claimed
        from a handoff store — validated and uploaded HERE, on the
        caller's (HTTP) thread, so the engine loop admits it as a pure
        direct insert. ``handoff_id`` (optional): prefill-only request —
        publish the prompt KV under this id instead of decoding.
        ``trace`` (optional): a :class:`~..obs.trace.TraceContext` the
        engine parents this request's phase spans to.
        ``adapter`` (optional): registered LoRA adapter name to decode
        under (serve/multi_lora.py); unknown names raise ValueError on
        this thread, before anything is queued.
        ``session_id`` (optional): conversation handle — on finish the
        turn's KV pages stay pinned under it (serve/sessions.py) and
        admission consults the session store's pending fleet pulls."""
        params = params or SamplingParams()
        prompt_ids = list(map(int, prompt_ids))
        max_prompt = self.cache_len - 2
        if len(prompt_ids) > max_prompt:  # sliding-window crop (reference
            prompt_ids = prompt_ids[-max_prompt:]  # minigpt/generate.py:18-20)
        block_open = []
        if self.block is not None:
            self.block.check_submit(params, kv_entry=kv_entry,
                                    handoff_id=handoff_id, adapter=adapter,
                                    session_id=session_id)
            prompt_ids, block_open = self.block.split_prompt(prompt_ids)
        req = Request(next(self._uid), prompt_ids, params, engine=self,
                      handoff_id=handoff_id, trace=trace, adapter=adapter,
                      session_id=session_id, block_open=block_open)
        if session_id is not None and self.session_store is not None:
            self.session_store.touch(session_id)
        if (self.paged is not None
                and not self.paged.fits_ever(len(prompt_ids) + 1)):
            # the prompt can NEVER fit the page pool (prompt pages + the
            # first decode page exceed capacity even on an empty pool) —
            # fail synchronously with a reason the API layer maps to a
            # 422, instead of letting the request age out of the queue
            # as a generic queue_full after queue_timeout_s
            self.rejected_too_large += 1
            with self.stats.lock:
                self.stats.requests_total += 1
            req.finish_time = time.monotonic()
            req.finish_reason = "too_large"
            self._record_finished(req)
            req.tokens.put(_FINISH)
            return req
        if adapter is not None:
            # pin the adapter for this request's whole lifetime — a
            # refcounted row can't be evicted (or hot-swapped) while a
            # request decodes under it; _record_finished releases
            if self.adapter_registry is None:
                raise ValueError(
                    f"adapter {adapter!r} requested but the engine has "
                    "no adapter_registry")
            try:
                self.adapter_registry.acquire(adapter)
            except KeyError:
                raise ValueError(
                    f"unknown adapter {adapter!r}") from None
            req.adapter_ref = True
        # the upload must land on the request BEFORE it is queued — the
        # engine thread may admit it the instant the put releases
        if kv_entry is not None:
            t0 = time.monotonic()
            req.kv_entry = self._accept_external_kv(kv_entry, prompt_ids)
            # validate + device upload of the claimed entry — the
            # decode-side half of the handoff wire cost (the kv-pool
            # server cross-checks with kvpool_handoff_wire_seconds)
            req.cp_add("handoff_wire", time.monotonic() - t0)
        with self.stats.lock:
            self.stats.requests_total += 1
        with self._submit_lock:
            if (self.max_queue is not None
                    and self.pending.qsize() >= self.max_queue):
                shed = True
            else:
                shed = False
                self.pending.put(req)
        if shed:
            # the caller (api layer) re-pins a claimed handoff entry on
            # this path so the retry elsewhere can still use it
            return self._shed(req)
        with self.stats.lock:
            self.stats.queue_depth = self.pending.qsize()
        self._wake.set()
        return req

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.cache_len

    # --- multi-LoRA plumbing (serve/multi_lora.py, ISSUE 15) -----------------

    def _ns_ids(self, adapter: str | None, token_ids) -> list[int]:
        """Prefix-cache key namespace: tokens shifted by the adapter's
        registry generation (``t + (ns << 32)``) — length-preserving and
        injective (Python ints don't narrow), so BOTH cache layouts'
        token-tuple keys (PrefixLRU windows, kv-pool tiers, per-page
        paged chains) isolate tenants without any cache-side change.
        LoRA targets include v_proj by default, so adapter KV differs
        from base KV row-for-row — cross-tenant hits would be silent
        corruption, and a hot-swapped adapter name must miss its own
        stale entries (fresh ns per register covers that). Base requests
        (ns 0) keep the identity mapping: existing keys, entries and
        cross-restart pool contents stay valid."""
        ns = (self.adapter_registry.ns_of(adapter)
              if self.adapter_registry is not None and adapter is not None
              else 0)
        if ns == 0:
            return token_ids if isinstance(token_ids, list) \
                else list(token_ids)
        shift = ns << 32
        return [int(t) + shift for t in token_ids]

    def _lora_args(self):
        """Gathered-BGMV jit args for a SLOT-WIDE dispatch (decode /
        mixed / spec / chunk_batch rows are the max_slots slot plane),
        or None when every slot is base — the caller then runs the base
        executable and the twin never traces. Computed OUTSIDE the
        dispatch_wait scope (the gmask idiom): the bank snapshot is
        host work, booked as ``adapter_gather``."""
        reg = self.adapter_registry
        if reg is None or all(a is None for a in self.slot_adapter):
            return None
        with self.steptrace.scope("adapter_gather"):
            return reg.dispatch_args(list(self.slot_adapter))

    def _lora_args_for(self, adapters: list[str | None]):
        """Gathered-BGMV jit args for a dispatch whose batch rows are
        REQUESTS (grouped prefill) or a single slot, not the slot
        plane."""
        reg = self.adapter_registry
        if reg is None or all(a is None for a in adapters):
            return None
        with self.steptrace.scope("adapter_gather"):
            return reg.dispatch_args(list(adapters))

    def _trace_phase(self, req: Request, name: str, duration_s: float,
                     **attrs) -> None:
        """Record one engine phase span for a traced request. Untraced
        requests (direct engine use, benches) cost one ``is None``."""
        if req.trace is None:
            return
        self.tracer.record(name, req.trace, duration_s=duration_s,
                           uid=req.uid, **attrs)

    @staticmethod
    def _note_cache_outcome(req: Request, hit, plen: int) -> None:
        """Label the request's warm-vs-cold TTFT outcome from the
        prefix-/handoff-hit the admission path resolved. First admission
        wins: a preempt-resume re-admission page-hits its OWN registered
        pages and must not relabel a cold request as warm."""
        if req.cache_outcome is not None or req.resume_last is not None:
            return
        if hit is None:
            req.cache_outcome = "cold"
        elif getattr(hit, "length", 0) >= plen:
            req.cache_outcome = "hit"
        else:
            req.cache_outcome = "partial"

    @staticmethod
    def _cp_admission(req: Request, dt: float, pre: float) -> None:
        """The admission segment: the admit wall ``dt`` MINUS the
        dispatch windows booked meanwhile (``cp_window_s`` was ``pre``
        before: the request's own prefill, and other requests' windows
        it sat through)."""
        req.cp_add("admission", max(0.0, dt - (req.cp_window_s - pre)))

    def _record_finished(self, req: Request) -> None:
        """Finalize the request's critical-path breakdown and remember
        it for ``GET /debug/requests``. ``host_gap`` is the residual
        wall time no attributed segment claims; every dispatch window
        the request sat through is attributed (CP_SEGMENTS), so the
        residual is the engine thread between windows. Runs on
        whichever thread finishes the request (engine, publisher, HTTP
        shed path)."""
        wall = (req.finish_time or time.monotonic()) - req.submit_time
        if req.finish_reason == "queue_full" and not req.cp:
            # a shed spent its whole life waiting; say so
            req.cp["queue_wait"] = wall
        # the front end's overlays, from the handler's two instants
        if req.api_body_time is not None:
            req.cp["api_pre_submit"] = max(
                0.0, req.submit_time - req.api_body_time)
        if (req.api_first_flush_time is not None
                and req.first_token_time is not None):
            req.cp["api_first_flush"] = max(
                0.0, req.api_first_flush_time - req.first_token_time)
        attributed = sum(v for k, v in req.cp.items()
                         if k not in CP_OVERLAYS)
        req.cp["host_gap"] = max(0.0, wall - attributed)
        with self.stats.lock:
            cp = self.stats.critical_path
            for seg, dt in req.cp.items():
                # stream_flush aggregates through note_stream_flush on
                # the handler thread — the ONLY aggregate writer for
                # that segment; summing it here too would double-book a
                # stream that closed before the engine finished (client
                # disconnect mid-decode)
                if seg in cp and seg != "stream_flush":
                    cp[seg] += dt
        # the ring must not pin KV: a shed request can still hold the
        # device/host entry uploaded at submit() (admission, which
        # nulls it, never ran) — 128 retained multi-MB buffers under
        # sustained overload is an OOM, not a debug view
        req.kv_entry = None
        # multi-LoRA: drop the submit-time adapter pin and book the
        # tenant's generated tokens (llm_tenant_tokens_total{adapter=…}).
        # This is the SINGLE finish funnel — sheds, handoff publishes
        # and normal finishes all pass here exactly once; preempt
        # requeues do NOT, so the ref rides the requeue.
        if req.adapter_ref:
            req.adapter_ref = False
            reg = self.adapter_registry
            if reg is not None:
                reg.release(req.adapter)
                reg.note_tokens(req.adapter, req.n_generated)
        self.finished.append(req)

    def _window_close(self, kind: str, own=(),
                      holders=None) -> tuple[float, float]:
        """Close the oldest dispatch window ``steptrace.window_begin``
        opened: its results are on the host. Books the window in the
        step record and to EVERY request holding a slot (the rule above
        CP_SEGMENTS; ``holders``: those that held one when the program
        was issued, where it was read a step later). ``kind``:
        ``"prefill"`` — the window advanced the prompts of the ``own``
        requests (one-shot, chunk, or the fused mixed step) — or
        ``"decode"`` — a plain decode window the ``own`` requests rode.
        Returns ``(window_s, issue_s)``, from the previous window's end
        where the two overlapped: a request's windows tile."""
        dt, issue_s = self.steptrace.window_end()
        mine, other = (("prefill_dispatch", "prefill_stall")
                       if kind == "prefill"
                       else ("decode_dispatch", "decode_interleave"))
        booked = set()
        for req in own:
            req.cp_window(mine, dt, issue_s)
            booked.add(req.uid)
        for req in (self.slot_req if holders is None else holders):
            # (a holder at issue whose stream has ended since sat
            # through nothing more)
            if (req is not None and req.uid not in booked
                    and req.finish_time is None):
                req.cp_window(other, dt, issue_s)
                booked.add(req.uid)
        return dt, issue_s

    def _note_device_phase(self, phase: str, *, tokens: int,
                           attended_keys: float, weight_passes: float,
                           kv_read_tokens: float, dt: float) -> None:
        """Book one dispatch's device-plane sample (obs/cost.py → the
        llm_dispatch_mfu / llm_dispatch_hbm_bw_util gauges). ``dt`` is
        the dispatch window (issue + result fetch) on this thread, as
        ``_window_close`` returned it; with no cost model only
        tokens-per-dispatch is recorded. Draft-model dispatches are not
        booked (the cost model covers the target model; the draft's
        work would inflate both utilizations)."""
        cm = self.cost_model
        mfu = bw = None
        if cm is not None and dt > 0:
            mfu = cm.mfu(cm.step_flops(tokens, attended_keys), dt)
            bw = cm.hbm_util(
                cm.step_bytes(weight_passes, kv_read_tokens, tokens), dt)
        if cm is not None and self.tp > 1:
            # TP collective attribution: every forward position pays
            # the row-parallel activation all-reduces — analytic
            # per-chip wire bytes + lower-bound ICI seconds
            # (llm_collective_{bytes,seconds}_total)
            cb = cm.collective_bytes(
                tokens, quantized=self.tp_quantized_collectives)
            self.collective_bytes_total += cb
            self.collective_seconds_total += cm.collective_seconds(cb)
        self.dispatch_meter.note_phase(phase, tokens=tokens, duration_s=dt,
                                       mfu=mfu, hbm_bw_util=bw)

    def _admit(self) -> bool:
        """Move pending requests into free slots. Plain one-shot prefills
        (no prefix hit, no chunking) are collected and run as BATCHED
        dispatches; prefix hits and chunked prompts take their own paths."""
        admitted = False
        self._paged_admit_blocked = False
        # snapshot the knob: it is the blessed runtime attribute (the
        # serve bench flips it post-warmup from another thread) and a
        # mid-step disable to None must not turn a passed `is not None`
        # check into a float<=None TypeError further down
        timeout_s = self.queue_timeout_s
        if timeout_s is not None:
            # shed stale requests every engine step, not only when a
            # slot frees — a client whose deadline passed should fail AT
            # the deadline, not after burning a full queue wait. FIFO
            # order means staleness is monotone from the head.
            now = time.monotonic()
            with self.steptrace.scope("queue_drain"):
                while True:
                    with self.pending.mutex:
                        head = (self.pending.queue[0]
                                if self.pending.queue else None)
                        if (head is None
                                or head.resume_last is not None
                                or now - head.submit_time <= timeout_s):
                            # preempted-resume requests are exempt: their
                            # stream already started, so a deadline shed
                            # would truncate a live response
                            break
                        self.pending.queue.popleft()
                    self._shed(head)
        batch: list[tuple[int, Request, int]] = []
        deferred: list[tuple[int, Request, int]] = []
        seen: set[tuple[int, ...]] = set()
        for slot in range(self.max_slots):
            if self.slot_req[slot] is not None:
                continue
            if self._paged_admit_blocked:
                # the page pool could not cover the previous admission
                # this step — later queue entries would fail the same
                # reservation (and double-count admission telemetry)
                break
            req = None
            with self.steptrace.scope("queue_drain"):
                while req is None:
                    try:
                        req = self.pending.get_nowait()
                    except queue.Empty:
                        break
                    if (timeout_s is not None
                            and req.resume_last is None
                            and time.monotonic() - req.submit_time
                            > timeout_s):
                        # waited past the deadline: the client is better
                        # served by a fast 429 it can retry elsewhere
                        # than by a TTFT already worse than any SLA
                        self._shed(req)
                        req = None
            if req is None:
                break
            # queue wait = submit → a slot freed for it; under sustained
            # load this span is where a request's time actually goes
            self._trace_phase(req, "engine.queue_wait",
                              time.monotonic() - req.submit_time,
                              slot=slot)
            cp_base = req.cp_queue_origin
            if cp_base is None:
                # first pop: a claimed-KV upload at submit() runs
                # BEFORE queueing and is already booked to
                # handoff_wire — shift the origin so queue_wait
                # doesn't re-claim that window (the segments must
                # partition, not overlap)
                cp_base = req.submit_time + req.cp.get("handoff_wire", 0.0)
            req.cp_add("queue_wait",
                       max(0.0, time.monotonic() - cp_base))
            # re-arm: if admission blocks (dry page pool) and requeues
            # this request, the next pop books only [here, next pop]
            req.cp_queue_origin = time.monotonic()
            plen = len(req.prompt_ids)
            hit = self._lookup_prefix(req, plen)
            if (self.role == "decode"
                    and (hit is None or hit.length < plen)):
                # graceful degradation, but visible: actual prefill
                # work on a decode replica is exactly the interference
                # disaggregation removes. Counted HERE — where the
                # prefill is really about to run — so neither sheds nor
                # full prefix/handoff hits inflate the signal.
                self.local_prefills += 1
                if not self._decode_prefill_logged:
                    self._decode_prefill_logged = True
                    self._log.warning(
                        "decode-role engine is prefilling locally "
                        "(handoff entry lost or request arrived without "
                        "one); serving continues but this replica is no "
                        "longer interference-free — see "
                        "llm_local_prefills_total")
            if (hit is None and not self._should_chunk(0, plen)
                    and not self._slot_state):
                self.slot_req[slot] = req   # reserve; activated post-batch
                self.slot_adapter[slot] = req.adapter
                self.slot_ready[slot] = False
                cacheable = (self.prefix_cache is not None
                             and plen >= self.prefix_cache.min_prefix)
                if cacheable and (req.adapter,
                                  tuple(req.prompt_ids)) in seen:
                    # duplicate of a prompt prefilling THIS burst: after
                    # the batch stores its prefix entry this becomes a
                    # full-prefix hit — keep the sequential path's
                    # intra-burst reuse instead of prefilling it again
                    # (its cache label comes from that later lookup)
                    deferred.append((slot, req, plen))
                else:
                    if cacheable:
                        seen.add((req.adapter, tuple(req.prompt_ids)))
                    self._note_cache_outcome(req, None, plen)
                    batch.append((slot, req, plen))
            else:
                t0 = time.monotonic()
                path = ("kv_direct_insert"
                        if hit is not None and hit.length == plen
                        else "prefill")
                pre = req.cp_window_s
                self._begin_prefill(req, slot, plen, hit=hit)
                dt = time.monotonic() - t0
                self._trace_phase(req, "engine.admit", dt, slot=slot,
                                  path=path, prompt_tokens=plen)
                self._cp_admission(req, dt, pre)
            admitted = True
        if batch:
            t0 = time.monotonic()
            pre = {req.uid: req.cp_window_s for _, req, _ in batch}
            self._prefill_batch(batch)
            dt = time.monotonic() - t0
            for slot, req, plen in batch:
                self._trace_phase(req, "engine.admit", dt, slot=slot,
                                  path="oneshot_batch", prompt_tokens=plen,
                                  batched=len(batch))
                self._cp_admission(req, dt, pre[req.uid])
        for slot, req, plen in deferred:
            t0 = time.monotonic()
            pre = req.cp_window_s
            self._begin_prefill(req, slot, plen)  # fresh lookup: now a hit
            dt = time.monotonic() - t0
            self._trace_phase(req, "engine.admit", dt,
                              slot=slot, path="deferred_prefix_hit",
                              prompt_tokens=plen)
            self._cp_admission(req, dt, pre)
        with self.stats.lock:
            self.stats.queue_depth = self.pending.qsize()
            self.stats.active_slots = sum(r is not None for r in self.slot_req)
        return admitted

    def _prefill_batch(self, batch: list[tuple[int, "Request", int]]) -> None:
        """One-shot prefill for several admitted requests in as few
        dispatches as possible: group by bucket, split each group into
        power-of-two sub-batches (compiled variants bounded at
        log2(max_slots) per bucket), sample every first token in ONE
        batched call."""
        self._drain("oneshot_prefill")
        if self.paged is not None:
            # page-granular admission: reserve ACTUAL prompt pages (+1
            # decode token) per member; a dry pool requeues the member
            # and blocks further admission this step
            kept, blocked = [], []
            for slot, req, plen in batch:
                if (not self._paged_admit_blocked
                        and self.paged.extend(slot, plen + 1)):
                    kept.append((slot, req, plen))
                else:
                    self.slot_req[slot] = None
                    self.slot_adapter[slot] = None
                    self.slot_ready[slot] = False
                    self._paged_admit_blocked = True
                    blocked.append(req)
            # requeue in REVERSE so the oldest blocked member lands at
            # the queue head (appendleft in forward order would invert
            # FIFO — and the timeout-shed loop assumes head-monotone
            # staleness)
            with self.pending.mutex:
                for req in reversed(blocked):
                    self.pending.queue.appendleft(req)
            batch = kept
            if not batch:
                return
        by_bucket: dict[int, list[tuple[int, Request, int]]] = {}
        for slot, req, plen in batch:
            by_bucket.setdefault(self._bucket_for(plen), []).append(
                (slot, req, plen))
        for bucket, group in by_bucket.items():
            i = 0
            while i < len(group):
                size = 1 << ((len(group) - i).bit_length() - 1)
                part = group[i:i + size]
                i += size
                with self.steptrace.scope("index_build"):
                    ids = np.zeros((size, bucket), np.int32)
                    lens = np.zeros((size,), np.int32)
                    for j, (_, req, plen) in enumerate(part):
                        ids[j, :plen] = req.prompt_ids
                        lens[j] = plen
                    # per-REQUEST adapter rows (the one dispatch whose
                    # batch dim is requests, not the slot plane)
                    lora = self._lora_args_for(
                        [r.adapter for _, r, _ in part])
                    kw = {} if lora is None else {"lora": lora}
                    pf = (self._prefill if lora is None
                          else self._prefill_lora)
                with self.steptrace.scope("dispatch_wait"):
                    self.steptrace.window_begin("prefill")
                    last, pre = pf(
                        self.params, jnp.asarray(ids), jnp.asarray(lens),
                        **kw)
                    if self.paged is not None:
                        sidx = self.paged.rows_scatter_idx(
                            [p[0] for p in part], [p[2] for p in part],
                            bucket)
                        self.paged.kv = self._pg_write_rows(
                            self.paged.kv, pre, jnp.asarray(sidx))
                    else:
                        slot_ids = np.array([p[0] for p in part],
                                            np.int32)
                        self.cache = self._insert_batch(
                            self.cache, pre, jnp.asarray(slot_ids),
                            jnp.asarray(lens))
                    first = None
                    if self.block is None:
                        first = self._first_tokens(
                            [r for _, r, _ in part], last)
                    self.steptrace.window_issued()
                    with self.steptrace.fetch():
                        if first is None:
                            # nothing is sampled from a block-diffusion
                            # prefill: the first block opens all-mask
                            jax.block_until_ready(last)
                        else:
                            first = np.asarray(first)   # forces the chain
                    # every member waited the whole batched dispatch
                    dt, _ = self._window_close(
                        "prefill", [r for _, r, _ in part])
                    # device plane: useful (un-padded) tokens only, so
                    # bucket padding shows up as lost MFU — which it is
                    keys = sum(CostModel.chunk_keys(p, 0)
                               for _, _, p in part)
                    self._note_device_phase(
                        "prefill",
                        tokens=sum(p for _, _, p in part),
                        attended_keys=keys,
                        weight_passes=1, kv_read_tokens=keys,
                        dt=dt)
                with self.steptrace.scope("sample_commit"):
                    for j, (slot, req, plen) in enumerate(part):
                        if self.paged is not None:
                            # rows are in pages now — register them
                            # instead of slicing copies (handoff
                            # gathers page-wise)
                            row_slices = None
                            self._paged_store_prefix(req, plen, slot,
                                                     last[j:j + 1])
                        else:
                            row_slices = [
                                {k: v[j:j + 1] for k, v in layer.items()
                                 if k != "index"} for layer in pre]
                            self._store_prefix(req, plen, row_slices,
                                               last[j:j + 1])
                        if req.handoff_id is not None:
                            # the group's bucket IS _bucket_for(plen),
                            # so these rows are already handoff-width —
                            # skip the redundant _slot_rows gather
                            self._complete_handoff(slot, req, plen,
                                                   last[j:j + 1],
                                                   rows=row_slices)
                        elif first is None:
                            self.block.activate(slot, req, plen)
                        else:
                            self._activate_with_token(slot, req, plen,
                                                      int(first[j]))

    def _complete_handoff(self, slot: int, req: Request, plen: int,
                          last_logits, rows=None) -> None:
        """Prefill-role completion: the prompt's KV rows are in ``slot``
        — queue them (plus the last-position logits the decode replica
        samples the first token from) for publication under the
        request's handoff id, finish the request WITHOUT decoding, and
        free the slot. The engine thread pays only the row gather (one
        dispatch, skipped when the batch/chunked paths already hold the
        rows); the device→host copy and the TCP put run on a dedicated
        publisher thread — a slow or dead pool server must stall the
        WAITING handoff request (whose consumer blocks on ``_FINISH``
        until the publish lands), never the engine loop that other
        requests' decode blocks run on. ``rows``: bucket-width
        index-free row dicts already sliced from the prefill cache."""
        from llm_in_practise_tpu.serve import prefix_cache as pc

        with self.steptrace.scope("publish"):
            if self.paged is not None:
                # page-wise handoff: the entry spans ceil(plen/P)*P rows
                # — only live pages ship over the wire, not a pow2
                # bucket (a 200-token prompt is 13 16-row pages = 208
                # rows, where the bucket path shipped 256). The gather
                # COPIES the page rows into fresh buffers, so the
                # slot's pages free right here.
                entry = self._paged_gather_entry(slot, plen, last_logits)
                self.paged.release_slot(slot)
            else:
                bucket = self._bucket_for(plen)
                if rows is None:
                    rows = self._slot_rows(self.cache,
                                           jnp.asarray(slot, jnp.int32),
                                           bucket=bucket)
                # _slot_rows / the batch slices COPY the rows into fresh
                # buffers, so the entry is independent of the slot,
                # which frees right here
                entry = pc.PrefixEntry(length=plen, bucket=bucket,
                                       rows=rows,
                                       last_logits=last_logits)
            self.slot_req[slot] = None
            self.slot_ready[slot] = False
            self.slot_budget[slot] = 0
            self.slot_closing[slot] = None
            self.slot_hist[slot] = None
            self.slot_adapter[slot] = None
            if not self._publishers:
                self._publishers = [
                    threading.Thread(target=self._run_publisher,
                                     daemon=True)
                    for _ in range(self._n_publishers)]
                for t in self._publishers:
                    t.start()
            self._book_thread_states(req)
            self._publish_queue.put((req, plen, entry))

    def _run_publisher(self) -> None:
        """Handoff publisher loop: device→host copy + store put, off the
        engine thread. Finishes each request only once its entry is
        pinned (or the publish definitively failed), so the router's
        wait on the prefill response still means 'the KV is claimable'.
        Several of these run concurrently — see ``_n_publishers``."""
        from llm_in_practise_tpu.serve.kv_pool import entry_to_host

        while True:
            req, plen, entry = self._publish_queue.get()
            t0 = time.monotonic()
            staged = 0
            try:
                if self.handoff is None:
                    raise RuntimeError("engine has no handoff store")
                host = entry_to_host(entry)
                # ledger account handoff_staging (host plane): the
                # entry's RAM between the device→host copy and the
                # pool put — freed below whether the put lands or not
                staged = host_entry_bytes(host)
                self._hbm.book("handoff_staging", staged)
                self.handoff.publish(req.handoff_id, host)
            except Exception as e:  # noqa: BLE001 — transport/pool
                # refusal: the request must still finish (the caller
                # re-prefills at a serving replica)
                with self._publish_lock:
                    self.handoff_publish_failed += 1
                self._log.warning("handoff publish %s failed: %s: %s",
                                  req.handoff_id, type(e).__name__, e)
                req.finish_reason = "handoff_failed"
            else:
                with self._publish_lock:
                    self.handoff_published += 1
                req.finish_reason = "handoff"
            if staged:
                self._hbm.book("handoff_staging", -staged)
            # device→host copy + store put — the KV-transfer cost the
            # disaggregation trade pays; its span is how a dashboard
            # shows handoff overhead per request
            self._trace_phase(req, "handoff.publish",
                              time.monotonic() - t0,
                              handoff_id=req.handoff_id,
                              prompt_tokens=plen,
                              ok=req.finish_reason == "handoff")
            req.cp_add("handoff_wire", time.monotonic() - t0)
            req.finish_time = time.monotonic()
            # KV-claimable time is this request's TTFT analog: per-role
            # llm_ttft_seconds on a prefill replica = prefill service
            req.first_token_time = req.finish_time
            self._record_finished(req)
            req.tokens.put(_FINISH)
            self.stats.observe_finished(req)

    def _first_from_program(self, req: Request) -> bool:
        """Does the paged chunk / mixed program that ends ``req``'s
        prompt sample its first token? Not where the host reads the
        logits first (a grammar's start state), where there is nothing
        to sample (a resumed stream's next token is already out; a
        handed-off prompt decodes elsewhere), nor in a block-diffusion
        engine."""
        return (self.block is None and req.handoff_id is None
                and req.resume_last is None
                and req.params.constraint is None)

    def _keeps_prefill_logits(self, req: Request) -> bool:
        """Does the host read the last-position logits of ``req``'s
        chunked prefill? The contiguous layout samples from them and
        stores them in its prefix entries; the paged layout only off the
        program path (:meth:`_first_from_program`) and for a
        write-through pool entry."""
        if self.block is not None:
            return False
        return (self.paged is None or not self._first_from_program(req)
                or (self.kv_pool is not None
                    and getattr(self.kv_pool, "offload_on_put", False)))

    def _first_tokens(self, reqs, last_logits):
        """First tokens of ``reqs`` from their (len(reqs), vocab)
        prefill logits, issued as ONE jitted call (a device array: the
        caller fetches it). Constrained members' tokens obey their
        grammar start states; zero mask rows leave the rest untouched."""
        self.rng, sub = jax.random.split(self.rng)
        p = [r.params for r in reqs]
        args = (np.array([q.temperature for q in p], np.float32),
                np.array([q.top_k for q in p], np.int32),
                np.array([q.top_p for q in p], np.float32),
                np.array([q.greedy for q in p], bool))
        if any(q.constraint is not None for q in p):
            args += (self._grammar_mask_rows(
                [self._ensure_constraint(r) for r in reqs]),)
        return self._sample_first(sub, last_logits, *args)

    def _note_first_token(self, path: str) -> None:
        """A prompt that finished in a chunk, mixed or suffix program
        got its first token from the ``program`` or from the ``host``
        fallback (``llm_first_tokens_total{path}``)."""
        self.first_tokens[path] += 1
        self.steptrace.note_first_token(path)

    def _activate(self, slot: int, req: Request, plen: int, last_logits,
                  rows=None, first: int | None = None):
        """Slot bookkeeping once the prompt's KV is in place. ``first``
        is the token the prefill program sampled for this row
        (:meth:`_first_from_program`); without one the first token is
        sampled here from the prefill logits. ``rows`` forwards
        already-gathered KV rows to the handoff path (chunked prefill
        gathers them for the prefix store anyway)."""
        if req.handoff_id is not None:
            return self._complete_handoff(slot, req, plen, last_logits,
                                          rows=rows)
        if self.block is not None:
            return self.block.activate(slot, req, plen)
        if req.resume_last is not None:
            # preemption resume: the "next" token was already emitted
            # before the preempt — no sampling, no rng split (the
            # stream must not fork from what the client saw)
            return self._activate_with_token(slot, req, plen, 0)
        on_device = first is not None
        if first is None:
            self._drain("host_first_token")
            first = int(np.asarray(
                self._first_tokens([req], last_logits))[0])
        self._activate_with_token(slot, req, plen, first,
                                  on_device=on_device)

    def _activate_with_token(self, slot: int, req: Request, plen: int,
                             first_id: int, *, on_device: bool = False):
        """Both halves of an activation at once: the slot's state, then
        the first token's (``on_device``: a paged program sampled it and
        the last-token plane holds it)."""
        why = self._activate_slot(slot, req, plen)
        self._first_token_out(slot, req, first_id, why, on_device=on_device)

    def _activate_slot(self, slot: int, req: Request, plen: int):
        """The half of an activation that needs no token VALUE: the slot
        decodes from ``plen`` on. Known when the program that ends the
        prompt is issued. Returns why the stream ends with its first
        token (:meth:`_closing_reason`), or None."""
        resumed = req.resume_last is not None
        self.slot_req[slot] = req
        self.slot_ready[slot] = True
        self.slot_len[slot] = plen
        # preemption resume (paged layout): the prompt now IS the full
        # emitted history minus the resume token, whose KV is the next
        # decode's to write
        self.slot_budget[slot] = (req.resume_budget if resumed
                                  else req.params.max_tokens - 1)
        self._slot_sampling(slot, req.params)
        # constrained decoding: install the request's grammar cursor
        # (resume keeps the preempt-time position — already advanced
        # over everything the client saw, including the resume token)
        self.slot_constraint[slot] = self._ensure_constraint(req)
        # a resumed stream emits nothing here, so nothing ends it here
        why = self.slot_closing[slot] = (None if resumed
                                         else self._closing_reason(slot))
        return why

    def _first_token_out(self, slot: int, req: Request, first_id: int,
                         why: str | None, *, on_device: bool) -> None:
        """The half of an activation that needs the first token's value:
        the stream's first item, the history, the TTFT stamp. Done when
        the program that sampled it is read; ``why``: what
        :meth:`_activate_slot` found then (``slot_closing`` may by now
        hold a later program's verdict)."""
        resumed = req.resume_last is not None
        if resumed:
            # nothing is emitted and the TTFT stamp is the original one
            first_id = req.resume_last
            req.resume_last = None
        else:
            req.first_token_time = time.monotonic()
        self.slot_last_token[slot] = first_id
        if not on_device or resumed:
            self._tokens_fix[slot] = first_id
        self.slot_hist[slot] = list(req.prompt_ids) + [first_id]
        if not resumed:
            cs = self.slot_constraint[slot]
            self._emit(slot, first_id, why)
            self._constraint_commit(slot, cs, first_id)

    def _slot_sampling(self, slot: int, params: SamplingParams) -> None:
        """``slot``'s row of the sampling plane. Written when the slot
        starts prefilling, not at activation: the program that ends its
        prompt samples its first token from this row."""
        self._temperature[slot] = params.temperature
        self._top_k[slot] = params.top_k
        self._top_p[slot] = params.top_p
        self._greedy[slot] = params.greedy

    def _sampling_args(self, rows: list[int], *, runs: bool = True):
        """The sampler's ``(temperature, top_k, top_p, greedy)`` arrays
        for a dispatch in which ``rows`` are sampled: the rows that
        decode and, in a paged chunk or mixed program, the rows whose
        prompt ends there. Every other row of the
        plane goes in as greedy: its token is discarded, and what a
        finished request left in its flags (or the initial ``False``)
        must not choose the sampler's body for the live rows
        (``infer/sampling.py::sampler_tier``). Books the body chosen,
        unless the program will not run its sampler (``runs``: a chunk
        program that ends no prompt)."""
        greedy = np.ones((self.max_slots,), bool)
        greedy[rows] = self._greedy[rows]
        if runs:
            self.steptrace.note_sampler_tier(
                sampler_tier_name(greedy, self._top_k, self._top_p))
        return (jnp.asarray(self._temperature), jnp.asarray(self._top_k),
                jnp.asarray(self._top_p), jnp.asarray(greedy))

    def _chunk_span(self, rem: int) -> int:
        """Padded length the chunked path would write for ``rem`` tokens."""
        c = self.chunked_prefill
        return -(-rem // c) * c

    def _oneshot_fits(self, done: int, rem: int) -> bool:
        return done + self._bucket_for(rem) <= self.cache_len

    def _chunked_fits(self, done: int, rem: int) -> bool:
        return (self.chunked_prefill is not None
                and done + self._chunk_span(rem) <= self.cache_len)

    def _should_chunk(self, done: int, rem: int) -> bool:
        """Chunk when the remainder is long (the point of interleaving) OR
        when only the chunk span fits the cache. Single source of truth
        for both admission paths (_admit and _begin_prefill)."""
        return self._chunked_fits(done, rem) and (
            rem > self.chunked_prefill or not self._oneshot_fits(done, rem)
        )

    def _accept_external_kv(self, host, prompt_ids):
        """Validate a claimed handoff :class:`~.kv_pool.HostEntry` and
        upload it as a device PrefixEntry (on the caller's thread), or
        ``None`` (counted) when it cannot seed a slot here — wrong cache
        layout/length means replica config drift, and a rejected entry
        degrades to local prefill rather than corrupting the slot."""
        from llm_in_practise_tpu.serve.disagg import usable_for_engine
        from llm_in_practise_tpu.serve.kv_pool import entry_to_device

        why = usable_for_engine(host, prompt_ids, self)
        if why is not None:
            self.kv_rejected += 1
            self._log.warning("rejecting handed-off KV entry: %s", why)
            return None
        if self.paged is not None:
            # keep the entry HOST-side: paged admission scatters it
            # page-by-page into the slot's reserved pages (no whole-
            # entry device buffer ever exists)
            return host
        return entry_to_device(host)

    def _lookup_prefix(self, req: Request, plen: int):
        if self.paged is not None:
            return self._paged_lookup(req, plen)
        ext = req.kv_entry
        if ext is not None:
            # handed-off KV (disaggregated serving): already validated
            # full-length at submit — admission is a pure direct insert,
            # no prefill dispatch, no mid-prefill rows on this replica
            req.kv_entry = None
            self.kv_admitted += 1
            return ext

        def usable(entry) -> bool:
            # rows an older replica wrote to a shared pool may be in the
            # stacked layout — their shapes are transposed relative to
            # this engine's writes and would scatter garbage
            if getattr(entry, "slot_axis", 0) != 0:
                return False
            # rows from another engine (shared pool) may be padded to a
            # bucket this engine's cache can't hold — the insert/suffix
            # scatters would clamp and corrupt the slot. Page-aligned
            # widths are judged POST-pow2-padding (entry_to_device pads
            # them so the jitted insert keeps a bounded compile set).
            from llm_in_practise_tpu.serve.kv_pool import effective_bucket

            if effective_bucket(entry) > self.cache_len:
                return False
            # every padded write the remaining prefill would do must land
            # inside cache_len, or the scatter clamps and corrupts the
            # prefix KV — either the one-shot bucket or the chunk span fits
            if entry.length == plen:
                return True
            rem = plen - entry.length
            return (self._oneshot_fits(entry.length, rem)
                    or self._chunked_fits(entry.length, rem))

        if self.prefix_cache is None:
            return None
        # multi-LoRA: adapter-namespaced key tokens — tenants (whose
        # adapters rewrite v_proj, hence the KV rows themselves) can
        # never hit each other's entries, including the base model's
        key_ids = self._ns_ids(req.adapter, req.prompt_ids)
        hit = self.prefix_cache.lookup(key_ids, usable)
        if hit is not None or self.kv_pool is None:
            return hit
        # L1 miss: cascade into the host/remote pool; a hit is promoted
        # back into L1 so the hot set migrates toward HBM. ``usable``
        # reads only entry metadata (length/bucket/slot_axis), so it
        # filters host entries before the device upload (and remote
        # entries before promotion).
        hit = self.kv_pool.lookup(key_ids, usable=usable)
        if hit is None:
            return None
        self.prefix_cache.put(key_ids[: hit.length], hit)
        return hit

    def _paged_lookup(self, req: Request, plen: int):
        """Paged admission's prefix resolution, best hit first:

        1. a claimed handoff entry (full-length host rows, validated at
           submit) — the disagg direct-insert path;
        2. the page index — partial-prefix hits at PAGE granularity,
           zero copies: the matched physical pages are refcounted into
           this slot's block table (the all-or-nothing direct-insert
           limitation this layout removes);
        3. the kv-pool tiers (host/remote row entries), fetched
           host-side and page-scattered at admission; their pages are
           then registered so the NEXT request hits tier 2.
        """
        from llm_in_practise_tpu.serve.paged_kv import PagedHit

        ext = req.kv_entry
        if ext is not None:
            req.kv_entry = None
            self.kv_admitted += 1
            return PagedHit(length=ext.length, entry=ext,
                            last_logits=ext.last_logits, external=True)
        pages = []
        if self.prefix_cache is not None:
            key_ids = self._ns_ids(req.adapter, req.prompt_ids)
            pages = self.prefix_cache.lookup(key_ids)
        # a fleet-pulled session entry (serve/sessions.py) outranks a
        # SHORTER local page hit; when it wins, the pool references the
        # index lookup took for us are handed straight back
        if self.session_store is not None and req.session_id is not None:
            hit = self._session_pull_hit(
                req, plen, len(pages) * self.paged.page_size)
            if hit is not None:
                if pages:
                    self.paged.pool.release(pages)
                return hit
        if pages:
            return PagedHit(length=len(pages) * self.paged.page_size,
                            pages=pages)
        if self.kv_pool is None or self.prefix_cache is None:
            return None

        def usable(entry) -> bool:
            # layout must match (slot axis 0), and every padded write
            # the remaining suffix prefill would do must land inside
            # cache_len — the paged one-shot suffix runs a
            # bucket_for(rem)-wide chunk at `done`, so the fit law is
            # the SAME as the contiguous filter (only the entry-bucket
            # cap is dropped: the page scatter writes positions, not
            # padded buckets)
            if getattr(entry, "slot_axis", 0) != 0:
                return False
            if entry.length >= plen:
                return entry.length == plen
            rem = plen - entry.length
            return (self._oneshot_fits(entry.length, rem)
                    or self._chunked_fits(entry.length, rem))

        from llm_in_practise_tpu.serve.kv_pool import TieredKV

        if isinstance(self.kv_pool, TieredKV):
            # host-side entries: the rows are page-scattered at
            # admission, so a whole-entry device upload would be waste
            host = self.kv_pool.lookup(key_ids, usable=usable,
                                       device=False)
        else:
            # bare pools (HostKVPool etc.) have no device kwarg and
            # already return host entries
            host = self.kv_pool.lookup(key_ids, usable=usable)
        if host is None:
            return None
        return PagedHit(
            length=host.length, entry=host,
            last_logits=host.last_logits if host.length == plen else None)

    def _session_pull_hit(self, req: Request, plen: int, page_len: int):
        """A usable :class:`~.paged_kv.PagedHit` from the session
        store's pending fleet pull for this request, or ``None``. The
        entry rides the tier-entry admission path (host rows scattered
        into reserved pages), so the SAME fit law applies; consume-once
        — an entry that loses to a longer page hit or fails the fit
        law is dropped (the local re-prefill degradation)."""
        from llm_in_practise_tpu.serve.paged_kv import PagedHit

        pulled = self.session_store.take_pending(req.session_id,
                                                 req.prompt_ids)
        if pulled is None:
            return None
        host, n = pulled
        if host.last_logits is None and n >= plen:
            # no stored logits for the final position: keep one token
            # to recompute (the page-index hit applies the same cap)
            n = plen - 1
        if getattr(host, "slot_axis", 0) != 0 or n <= page_len or n <= 0:
            return None
        if n < plen and not (self._oneshot_fits(n, plen - n)
                             or self._chunked_fits(n, plen - n)):
            return None
        return PagedHit(
            length=n, entry=host,
            last_logits=host.last_logits if n == plen else None)

    def _paged_begin_prefill(self, req: Request, slot: int, plen: int,
                             hit) -> None:
        """Paged admission for one request: reserve ACTUAL pages
        (prompt + first decode token — not a cache_len worst case), map
        or scatter whatever prefix the lookup found, then chunk or
        one-shot the suffix. A dry pool requeues the request and blocks
        further admission this step (decode-side growth may preempt;
        admission never does)."""
        P = self.paged.page_size
        self._note_cache_outcome(req, hit, plen)
        if hit is not None and hit.pages is not None:
            # a page hit whose suffix neither chunks nor fits a one-shot
            # bucket inside cache_len shrinks page by page first (the
            # paged analog of the contiguous usable() fit filter)
            done, rem = hit.length, plen - hit.length
            while (done > 0 and not self._should_chunk(done, rem)
                   and done + self._bucket_for(rem) > self.cache_len):
                done -= P
                rem += P
            if done < hit.length:
                self.paged.pool.release(hit.pages[done // P:])
                hit = (dataclasses.replace(hit, length=done,
                                           pages=hit.pages[:done // P])
                       if done > 0 else None)
            if hit is not None:
                self.paged.map_shared(slot, hit.pages)
        if not self.paged.extend(slot, plen + 1):
            # not admissible right now: hand the shared refs back, put
            # the request at the queue head, stop admitting this step
            # (decode-side growth may preempt; admission never does)
            self.paged.release_slot(slot)
            self.slot_req[slot] = None
            self.slot_adapter[slot] = None
            if hit is not None and hit.entry is not None and hit.external:
                # a handoff claim is consume-once: stash it back on the
                # request (and un-count the consumption) or the retry
                # pays a full local prefill for an entry we still hold
                req.kv_entry = hit.entry
                self.kv_admitted -= 1
            self._paged_admit_blocked = True
            with self.pending.mutex:
                self.pending.queue.appendleft(req)
            return
        done = hit.length if hit is not None else 0
        if hit is not None and hit.entry is not None:
            self._drain("kv_pull")
            self._paged_insert_entry(slot, hit.entry, hit.length)
            # promote the tier hit into the page index: the next
            # request with this prefix shares pages instead of
            # re-fetching rows
            self._paged_register_pages(req.prompt_ids[:hit.length], slot,
                                       req.adapter)
            if hit.length == plen:
                self._activate(slot, req, plen, hit.last_logits)
                return
        rem = plen - done
        self._slot_sampling(slot, req.params)
        if self._should_chunk(done, rem):
            self.slot_req[slot] = req
            self.slot_ready[slot] = False
            self.slot_prefill[slot] = {"req": req, "plen": plen,
                                       "done": done, "last_logits": None}
            return
        first, last_logits = self._paged_suffix(
            slot, req.prompt_ids[done:], done, req)
        # store the finished prompt like every other completion path:
        # register its pages for sharing + tier write-through (the
        # contiguous twin does this in _finish_prefill)
        self._paged_store_prefix(req, plen, slot, last_logits)
        self._activate_prefilled(slot, req, plen, last_logits, first=first)

    def _paged_suffix(self, slot: int, suffix, done: int, req: Request):
        """One-shot prefill of ``req``'s ``suffix`` into ``slot`` at
        ``done`` through the paged chunk program (the dedicated
        contiguous ``_prefill_suffix`` program has no paged twin — the
        chunk body is the same pinned-index math). The suffix ends the
        prompt, so the program samples its first token where it may
        (:meth:`_first_from_program`). Returns ``(first token or None,
        the last-position logits row or None)``; the dispatch is booked
        into the request's critical-path breakdown."""
        self._drain("oneshot_prefill")
        C = self._bucket_for(len(suffix))
        # a ONE-row call of the paged chunk program: it gathers the
        # owning slot's pages, not a W-wide view of every slot, which is
        # what makes a warm follow-up turn cheaper than its cold
        # re-prefill (the view gather, not the attention, dominates a
        # short suffix over a long prefix). Adapters ride along: the
        # program picks the row's index out of the slot plane.
        lora = self._lora_args()
        with self.steptrace.scope("index_build"):
            W = self._paged_width(done + C)
            self._pulse_view(W, 1)
            rows = self._paged_chunk_rows(
                [(slot, done, suffix)], W, C, n_rows=1)
            if self.step_stats is not None:
                self.step_stats.note_chunk_rows(
                    [(slot, {"done": done}, suffix)], 1)
            tail, sampled = self._tail_key([(slot, req)])
        kw = {} if lora is None else {"lora": lora}
        with self.steptrace.scope("dispatch_wait"):
            self.steptrace.window_begin("prefill")
            fn = self._pg_chunk if lora is None else self._pg_chunk_lora
            first, last, self._tokens_dev, self.paged.kv, *counted = fn(
                self.params, self.paged.kv, *rows, *tail,
                *self._plane_args(), *self._sampling_args(sampled), **kw)
            stats = self.step_stats
            pended = stats and stats.pend("chunk", counted, last,
                                          [(slot, req)])
            self.steptrace.window_issued()
            # force before the window closes, exactly like
            # _prefill_into_slot
            with self.steptrace.fetch():
                first, parts = jax.device_get(
                    (first, stats and stats.counted(pended)))
            dt, _ = self._window_close("prefill", (req,))
            if stats is not None:
                stats.book(pended, parts)
            keys = CostModel.chunk_keys(len(suffix), done)
            self._note_device_phase(
                "prefill", tokens=len(suffix), attended_keys=keys,
                weight_passes=1, kv_read_tokens=keys, dt=dt)
        return (int(first[slot]) if sampled else None,
                last[slot:slot + 1] if self._keeps_prefill_logits(req)
                else None)

    def _tail_key(self, finishing) -> tuple:
        """``(ends, key)`` of a paged chunk program's tail
        (:meth:`_prefill_tail`) for the rows ``finishing`` = ``[(slot,
        request), ...]`` whose prompt ends in it (``ends`` by slot: 1,
        or 2 where the program samples the row's first token), and the
        slots among them whose first token the program samples. The key
        is split off the engine's only then: nothing is drawn from it
        otherwise."""
        ends = np.zeros((self.max_slots,), np.int32)
        sampled = []
        for slot, req in finishing:
            ends[slot] = 1
            if self._first_from_program(req):
                ends[slot] = 2
                sampled.append(slot)
        key = self.rng
        if sampled:
            self.rng, key = jax.random.split(self.rng)
        return (jnp.asarray(ends), key), sampled

    def _activate_prefilled(self, slot: int, req: Request, plen: int,
                            last_logits, *, first: int | None,
                            rows=None) -> None:
        """:meth:`_activate` for a prompt that a chunk, mixed or suffix
        program finished, booked by where its first token comes from."""
        if req.handoff_id is None and self.block is None:
            self._note_first_token("host" if first is None else "program")
        self._activate(slot, req, plen, last_logits, rows=rows, first=first)

    _UNSET = object()

    def _begin_prefill(self, req: Request, slot: int, plen: int,
                       hit=_UNSET) -> None:
        """Route one admitted request: full prefix hit → direct insert;
        long remainder (chunked prefill on) → incremental, one chunk per
        engine step so running slots keep decoding; otherwise one-shot.
        ``hit`` may be passed by ``_admit`` (which already looked it up)."""
        # stamp the slot's adapter BEFORE any prefill dispatch — the
        # suffix/chunk programs below read the slot plane for their
        # gathered-BGMV indices
        self.slot_adapter[slot] = req.adapter
        if self.paged is not None:
            if hit is self._UNSET:
                hit = self._lookup_prefix(req, plen)
            return self._paged_begin_prefill(req, slot, plen, hit)
        if hit is self._UNSET:
            hit = self._lookup_prefix(req, plen)
        self._note_cache_outcome(req, hit, plen)
        if hit is not None and hit.length == plen:
            self.cache = self._insert_rows(
                self.cache, hit.rows, slot, jnp.asarray(plen, jnp.int32))
            self._activate(slot, req, plen, hit.last_logits)
            return
        done = hit.length if hit is not None else 0
        rem = plen - done
        # a hit that fits neither way was already filtered by
        # _lookup_prefix's usable()
        if self._should_chunk(done, rem):
            # Chunks write DIRECTLY into the slot's cache rows — no
            # per-prefill full-length mini cache (at 8B/8K that was
            # 1.2 GiB per in-flight prefill, the long-context OOM); the
            # only transient is one slot-slice inside the jitted chunk.
            # Garbage rows other dispatches write into the reserved slot
            # (single-step decode / speculative drift at its device
            # index) are always overwritten by the chunk that owns that
            # range — or, beyond the prompt, by real decode in order —
            # before any query can attend them (causal masking keys off
            # absolute position).
            if hit is not None:
                self.cache = self._insert_rows(
                    self.cache, hit.rows, slot,
                    jnp.asarray(done, jnp.int32))
            self.slot_req[slot] = req   # slot reserved, not yet decodable
            self.slot_ready[slot] = False
            self._slot_sampling(slot, req.params)
            self.slot_prefill[slot] = {"req": req, "plen": plen, "done": done,
                                       "last_logits": None}
            return
        last_logits = self._prefill_into_slot(req, slot, plen, hit)
        self._activate(slot, req, plen, last_logits)

    def _chunk_entries(self) -> list:
        """``(slot, state, next chunk)`` of the mid-prefill rows one
        chunk dispatch advances, in slot order: all of them, unless the
        paged layout's :data:`CHUNK_TOKENS_PER_STEP` holds fewer chunks;
        then those with the fewest chunks left (``slot_prefill`` keeps
        admission order and the sort is stable: ties go to the
        oldest)."""
        C = self.chunked_prefill
        slots = list(self.slot_prefill)
        rows = max(1, CHUNK_TOKENS_PER_STEP // C)
        if self.paged is not None and len(slots) > rows:
            def chunks_left(slot):
                st = self.slot_prefill[slot]
                return -(-(st["plen"] - st["done"]) // C)
            slots = sorted(slots, key=chunks_left)[:rows]
        entries = []
        for slot in sorted(slots):
            st = self.slot_prefill[slot]
            entries.append(
                (slot, st, st["req"].prompt_ids[st["done"]: st["done"] + C]))
        return entries

    def _advance_prefills(self, budget: int = 1) -> bool:
        """Advance the in-flight chunked prefills (:meth:`_chunk_entries`)
        by one chunk per budget unit, then finalize finished prompts.
        Multiple mid-prefill slots advance TOGETHER in one batched
        dispatch (:meth:`_chunk_batch_fn`) — concurrent long prompts
        no longer serialize per slot — while a single prefill keeps
        the 1-slot program (and, with budget > 1, gets several chunks
        per step, so ``prefill_budget`` still bounds a lone prompt's
        TTFT at ~chunks/budget steps). The paged layout's chunk program
        is issued and read at once here (:meth:`_issue_chunk`,
        :meth:`_retire`): this is the path of a step that dispatches
        more than one program."""
        progressed = False
        if self.paged is not None:
            while budget > 0 and self.slot_prefill:
                self._retire(self._issue_chunk())
                budget -= 1
                progressed = True
            return progressed
        while budget > 0 and self.slot_prefill:
            with self.steptrace.scope("index_build"):
                entries = self._chunk_entries()
                C = self.chunked_prefill
                # whole-cache batching needs every row's C-wide write window
                # inside cache_len — a clamped scatter on a near-full ACTIVE
                # row would overwrite attended KV. Rare tail case: fall back
                # to sequential single-slot chunks.
                batchable = len(entries) > 1 and all(
                    int(self.slot_len[s]) + C <= self.cache_len
                    for s in range(self.max_slots)
                    if s not in self.slot_prefill
                    and self.slot_req[s] is not None  # free rows are dead
                )
                # device-plane accounting reads each chunk's pre-advance
                # context; compute before the branches mutate st["done"]
                pf_tokens = sum(len(c) for _, _, c in entries)
                pf_keys = sum(CostModel.chunk_keys(len(c), st["done"])
                              for _, st, c in entries)
                lora = self._lora_args()   # slot-plane (batched chunk rows)
                kw = {} if lora is None else {"lora": lora}
            with self.steptrace.scope("dispatch_wait"):
                self.steptrace.window_begin("prefill")
                if batchable:
                    tok, starts, lens = self._chunk_batch_rows(entries)
                    self._note_chunk_rows(len(entries), self.max_slots)
                    fn = (self._chunk_batch if lora is None
                          else self._chunk_batch_lora)
                    last, self.cache = fn(
                        self.params, self.cache, jnp.asarray(tok),
                        jnp.asarray(starts), jnp.asarray(lens), **kw)
                    self._chunks_done(entries, last)
                else:
                    self._note_chunk_rows(len(entries), len(entries))
                    for slot, st, chunk in entries:
                        # the 1-row program wants a 1-row index array
                        sl = self._lora_args_for([st["req"].adapter])
                        skw = {} if sl is None else {"lora": sl}
                        fn = (self._chunk_slot if sl is None
                              else self._chunk_slot_lora)
                        padded = np.zeros((1, C), np.int32)
                        padded[0, :len(chunk)] = chunk
                        st["last_logits"], self.cache = fn(
                            self.params, self.cache, jnp.asarray(padded),
                            jnp.asarray(slot, jnp.int32),
                            jnp.asarray(st["done"], jnp.int32),
                            jnp.asarray(len(chunk), jnp.int32),
                            **skw,
                        )
                        st["done"] += len(chunk)
                    last = [st["last_logits"] for _, st, _ in entries]
                self.steptrace.window_issued()
                # force the programs' results before the window
                # closes: on an async backend issue time alone would
                # inflate the prefill MFU/BW gauges
                # ~device-time/dispatch-time-fold (the decode and fused
                # paths force every dispatch the same way). KV writes
                # land in the same program, so this waits only for work
                # the next chunk depends on anyway.
                with self.steptrace.fetch():
                    jax.block_until_ready(last)
                # every mid-prefill request waited the whole dispatch
                dt, issue_s = self._window_close(
                    "prefill", [st["req"] for _, st, _ in entries])
                self._trace_chunks(entries, dt, issue_s, batched=batchable)
                self._note_device_phase(
                    "prefill", tokens=pf_tokens, attended_keys=pf_keys,
                    weight_passes=1 if batchable else len(entries),
                    kv_read_tokens=pf_keys, dt=dt)
            budget -= 1
            progressed = True
            with self.steptrace.scope("sample_commit"):
                self._finalize_prefills()
        return progressed

    def _trace_chunks(self, entries, dt: float, issue_s: float, *,
                      batched: bool, fused: bool = False) -> None:
        """One ``engine.prefill_chunk`` span per traced mid-prefill row.
        The duration is the whole dispatch window (the results were
        forced before it closed); ``issue_s`` is the part of it before
        the jitted call returned, as ``/debug/requests`` books it under
        ``dispatch_issue``."""
        for slot, st, chunk in entries:
            self._trace_phase(st["req"], "engine.prefill_chunk", dt,
                              slot=slot, done=st["done"],
                              chunk_tokens=len(chunk), batched=batched,
                              fused=fused, issue_s=issue_s)

    def _chunk_batch_rows(self, entries):
        """Host arrays (tok, starts, lens) for a whole-cache batched
        chunk dispatch of the CONTIGUOUS layout — shared by its
        sequential batched path and its fused mixed step (the paged
        layout computes only the rows that chunk and has no dead
        writes: :meth:`_paged_chunk_rows`). Non-prefill rows get zero
        tokens at their own
        index: garbage KV beyond it, overwritten in order before any
        query attends it; min() keeps the dead write window of FREE
        rows inside the cache (occupied rows already fit by the
        caller's precheck — ``batchable`` / ``_mixed_feasible`` — so
        their min() is a no-op)."""
        C = self.chunked_prefill
        tok = np.zeros((self.max_slots, C), np.int32)
        starts = np.zeros((self.max_slots,), np.int32)
        lens = np.zeros((self.max_slots,), np.int32)
        for s in range(self.max_slots):
            if s not in self.slot_prefill:
                starts[s] = min(int(self.slot_len[s]),
                                self.cache_len - C)
        for slot, st, chunk in entries:
            tok[slot, :len(chunk)] = chunk
            starts[slot] = st["done"]
            lens[slot] = len(chunk)
        return tok, starts, lens

    def _paged_chunk_rows(self, rows, W: int, C: int, n_rows=None):
        """Host arguments of :meth:`_paged_chunk_fn` for ``rows`` =
        ``[(slot, done, chunk tokens), ...]``: each row's slot id, pool
        gather / window-scatter indices, padded tokens, ``starts`` and
        ``lens``, and the trip count ``len(rows)``. The arrays hold
        ``n_rows`` rows (default ``max_slots``) whatever ``len(rows)``
        is, so the compile key holds no row count; the program never
        visits the padding. Forks any shared page a row's window
        touches, so read ``self.paged.kv`` AFTER this call."""
        S = self.max_slots
        R = S if n_rows is None else n_rows
        k = len(rows)
        slots = np.zeros((R,), np.int32)
        tok = np.zeros((R, C), np.int32)
        starts = np.zeros((R,), np.int32)
        lens = np.zeros((R,), np.int32)
        sidx = np.zeros((R, C), np.int32)
        plane_starts = np.zeros((S,), np.int32)
        plane_valid = np.zeros((S,), np.int32)
        for i, (slot, done, chunk) in enumerate(rows):
            slots[i] = slot
            tok[i, :len(chunk)] = chunk
            starts[i] = plane_starts[slot] = done
            lens[i] = plane_valid[slot] = len(chunk)
            self._paged_cow_fork(slot, done, len(chunk))
        vidx = self._paged_view_idx(W, slots[:k])
        gidx = np.zeros((R,) + vidx.shape[1:], np.int32)
        gidx[:k] = vidx
        sidx[:k] = self.paged.scatter_idx(
            plane_starts, plane_valid, C)[slots[:k]]
        return tuple(jnp.asarray(a) for a in (
            slots, gidx, tok, starts, lens, sidx, np.int32(k)))

    def _paged_entry_rows(self, entries, W: int):
        """:meth:`_paged_chunk_rows` for the mid-prefill ``entries`` of a
        chunk or fused mixed dispatch, booked in the chunk-row counters
        (the device computes exactly the rows that chunk)."""
        self._note_chunk_rows(len(entries), len(entries))
        if self.step_stats is not None:
            self.step_stats.note_chunk_rows(
                entries, len(self._finishing(entries)))
        return self._paged_chunk_rows(
            [(slot, st["done"], chunk) for slot, st, chunk in entries],
            W, self.chunked_prefill)

    def _note_chunk_rows(self, rows: int, row_slots: int) -> None:
        """Book one chunk dispatch: ``rows`` prompts advanced a chunk,
        the device computed ``row_slots`` rows for them (the
        contiguous slot plane's idle rows included). Their ratio is the useful share of the
        prefill plane."""
        self.prefill_chunk_rows += rows
        self.prefill_chunk_row_slots += row_slots
        self.steptrace.note_chunk_rows(rows, row_slots)

    def _issue_chunk(self) -> _Flight:
        """Issue the paged chunk program: the mid-prefill rows
        (:meth:`_chunk_entries`) advance one chunk against the PAGE POOL
        in a single dispatch: the program gathers one chunking row's
        pages at a time, runs the shared ``batched_chunk_hidden`` body
        on that view and scatters the row's real chunk window back to
        its pages. Rows that do not chunk cost nothing. No per-chunk
        page reservation is needed: admission reserved the WHOLE
        prompt's pages (+1 decode token) before the slot entered
        ``slot_prefill``. Returns the program in flight; its first
        tokens by slot are read in :meth:`_retire`."""
        with self.steptrace.scope("index_build"):
            entries = self._chunk_entries()
            C = self.chunked_prefill
            # device-plane accounting reads each chunk's pre-advance
            # context; compute before the chunk is booked
            pf_keys = sum(CostModel.chunk_keys(len(c), st["done"])
                          for _, st, c in entries)
            book = {"pf_tokens": sum(len(c) for _, _, c in entries),
                    "pf_keys": pf_keys}
            lora = self._lora_args()   # slot-plane (batched chunk rows)
            kw = {} if lora is None else {"lora": lora}
        with self.steptrace.scope("dispatch_wait"):
            f = self._window_open("prefill", _Flight("chunk", book=book))
            W = self._paged_width(
                max(st["done"] for _, st, _ in entries) + C)
            self._pulse_view(W, 1)
            fn = self._pg_chunk if lora is None else self._pg_chunk_lora
            # a statement of its own: building the rows may fork a shared
            # page, which REBINDS the (donated) pool read below
            rows = self._paged_entry_rows(entries, W)
            finishing = self._finishing(entries)
            tail, sampled = self._tail_key(finishing)
            f.first, last, self._tokens_dev, self.paged.kv, *counted = fn(
                self.params, self.paged.kv, *rows, *tail,
                *self._plane_args(),
                *self._sampling_args(sampled, runs=bool(finishing)), **kw)
            self._prompts_issued(f, "chunk", entries, finishing, last,
                                 counted)
            self.steptrace.window_issued()
        return f

    def _window_open(self, phase: str, f: _Flight) -> _Flight:
        """Open ``f``'s dispatch window, book whether it is issued ahead
        of an unread program (where it is not, the step's end books
        why), and note who holds a slot now."""
        self.steptrace.window_begin(phase)
        if self._flight is not None:
            self.steptrace.note_ahead()
        f.holders = [r for r in self.slot_req if r is not None]
        return f

    def _prompts_issued(self, f: _Flight, kind: str, entries, finishing,
                        last, counted) -> None:
        """The host's half of a chunk dispatch that needs no token
        VALUE, done at issue: the chunks are booked as fed, and a prompt
        the program ends in a first token of its own sampling is live
        from here on (the next program decodes it). One whose first
        token the host samples stays in ``slot_prefill`` for
        :meth:`_finalize_prefills`, and makes ``f`` a program that is
        read at once."""
        if self.step_stats is not None:
            f.stats = self.step_stats.pend(kind, counted, last, finishing)
        f.entries = entries
        self._chunks_done(entries, last)
        for slot, req in finishing:
            if not self._first_from_program(req):
                f.host_first = True
                continue
            st = self.slot_prefill.pop(slot)
            f.finished.append(
                (slot, req, st, self._activate_slot(slot, req, st["plen"])))

    @staticmethod
    def _finishing(entries) -> list:
        """``(slot, request)`` of the ``entries`` whose next chunk is
        their prompt's last (read BEFORE the chunk is booked)."""
        return [(slot, st["req"]) for slot, st, chunk in entries
                if st["done"] + len(chunk) >= st["plen"]]

    def _chunks_done(self, entries, last) -> None:
        """Book one chunk each of ``entries`` as fed. ``last``
        (max_slots, vocab) are the dispatch's last-position logits by
        slot: a row is sliced out of them only for a prompt that this
        chunk finishes and whose logits the host reads
        (:meth:`_keeps_prefill_logits`)."""
        for slot, req in self._finishing(entries):
            if self._keeps_prefill_logits(req):
                self.slot_prefill[slot]["last_logits"] = last[slot:slot + 1]
        for _, st, chunk in entries:
            st["done"] += len(chunk)

    def _finalize_prefills(self) -> None:
        """Activate every chunked prefill whose prompt is fully fed and
        whose first token the HOST samples from the prefill logits —
        shared tail of the sequential and fused mixed-step paths (a
        prompt that a paged program ended in a first token of its own
        was activated when the program was issued, and its token is read
        in :meth:`_retire`)."""
        for slot in list(self.slot_prefill):
            st = self.slot_prefill[slot]
            if st["done"] < st["plen"]:
                continue
            req, plen = st["req"], st["plen"]
            del self.slot_prefill[slot]
            # rows are already in the slot; store the prefix entry
            # from them (the index is plen — set by the final chunk)
            rows = None
            if self.paged is not None:
                self._paged_store_prefix(req, plen, slot,
                                         st["last_logits"])
            elif self.prefix_cache is not None:
                rows = self._slot_rows(
                    self.cache, jnp.asarray(slot, jnp.int32),
                    bucket=self._bucket_for(plen))
                self._store_prefix(req, plen, rows,
                                   st["last_logits"],
                                   rows_ready=True)
            # the gathered rows ride through to the handoff path so a
            # chunked handoff doesn't pay the gather dispatch twice
            self._activate_prefilled(slot, req, plen, st["last_logits"],
                                     first=None, rows=rows)

    def _paged_store_prefix(self, req: Request, plen: int, slot: int,
                            last_logits) -> None:
        """Paged twin of ``_store_prefix``: the prompt's KV is already
        in ``slot``'s pages, so "storing" the prefix is registering the
        full pages in the sharing index (zero copies) plus the optional
        kv-pool write-through of a page-aligned row entry. Write-through
        is duck-typed: a lookup-only pool (bare HostKVPool) simply gets
        no copies."""
        if self.prefix_cache is not None:
            self._paged_register_pages(req.prompt_ids[:plen], slot,
                                       req.adapter)
        if (self.kv_pool is not None
                and getattr(self.kv_pool, "offload_on_put", False)):
            self.kv_pool.offload(
                self._ns_ids(req.adapter, req.prompt_ids[:plen]),
                self._paged_gather_entry(slot, plen, last_logits))

    def _store_prefix(self, req: Request, plen: int, pre_cache,
                      last_logits, *, rows_ready: bool = False) -> None:
        """Store a finished prompt's prefix entry (L1 + optional pool
        write-through). ``pre_cache`` must be a 1-row cache/rows list;
        ``rows_ready=True`` means it is ALREADY bucket-width index-free
        rows (the chunked path's ``_slot_rows`` output) — re-slicing
        would dispatch identity copies per layer."""
        from llm_in_practise_tpu.serve import prefix_cache as pc

        if self.prefix_cache is None:
            return
        bucket = self._bucket_for(plen)
        entry = pc.PrefixEntry(
            length=plen, bucket=bucket,
            rows=(pre_cache if rows_ready
                  else pc.slice_cache_rows(pre_cache, bucket)),
            last_logits=last_logits,
        )
        key_ids = self._ns_ids(req.adapter, req.prompt_ids)
        self.prefix_cache.put(key_ids, entry)
        if self.kv_pool is not None and self.kv_pool.offload_on_put:
            # LMCache streaming write-through: the pool copy means a
            # sibling / restarted engine starts with this prefix warm.
            self.kv_pool.offload(key_ids[:plen], entry)

    def _finish_prefill(self, req: Request, slot: int, plen: int,
                        pre_cache, last_logits) -> None:
        """Store the finished prompt's prefix entry and move its KV rows
        into the slot — shared tail of the suffix/chunked prefill paths."""
        self._store_prefix(req, plen, pre_cache, last_logits)
        self.cache = self._insert(
            self.cache, pre_cache, slot, jnp.asarray(plen, jnp.int32)
        )

    def _prefill_into_slot(self, req: Request, slot: int, plen: int, hit):
        """One-shot prefill (reusing any cached prefix rows) into ``slot``;
        returns the last-position logits."""
        with self.steptrace.scope("dispatch_wait"):
            return self._prefill_into_slot_timed(req, slot, plen, hit)

    def _prefill_into_slot_timed(self, req, slot, plen, hit):
        lora = self._lora_args_for([req.adapter])
        kw = {} if lora is None else {"lora": lora}
        self.steptrace.window_begin("prefill")
        if hit is not None:
            suffix = req.prompt_ids[hit.length:]
            sbucket = self._bucket_for(len(suffix))
            padded = np.zeros((1, sbucket), np.int32)
            padded[0, :len(suffix)] = suffix
            fn = (self._prefill_suffix if lora is None
                  else self._prefill_suffix_lora)
            last_logits, pre_cache = fn(
                self.params, hit.rows, jnp.asarray(hit.length, jnp.int32),
                jnp.asarray(padded), jnp.asarray(len(suffix), jnp.int32),
                **kw)
            new, start = len(suffix), hit.length
        else:
            bucket = self._bucket_for(plen)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = req.prompt_ids
            fn = self._prefill if lora is None else self._prefill_lora
            last_logits, pre_cache = fn(
                self.params, jnp.asarray(padded),
                jnp.asarray([plen], jnp.int32), **kw
            )
            new, start = plen, 0
        self.steptrace.window_issued()
        # force + close the window BEFORE the insert/prefix-store work
        # so this sample covers exactly the prefill forward, same
        # boundary as the chunked/fused paths (async-backend honesty —
        # see _advance_prefills); the logits feed the first-token
        # sample on this same call path anyway
        with self.steptrace.fetch():
            jax.block_until_ready(last_logits)
        dt, _ = self._window_close("prefill", (req,))
        keys = CostModel.chunk_keys(new, start)
        self._note_device_phase(
            "prefill", tokens=new, attended_keys=keys,
            weight_passes=1, kv_read_tokens=keys, dt=dt)
        self._finish_prefill(req, slot, plen, pre_cache, last_logits)
        return last_logits

    def _finish_slot(self, slot: int, reason: str) -> None:
        """Finish ``slot``'s request with ``reason`` and free the slot —
        the single exit for decode completions (eos/length/cache) and
        the paged pool's last-occupant exhaustion. In the paged layout
        the slot's full pages are registered for sharing on the way out
        (a follow-up turn reuses the whole conversation's KV) and the
        block table releases its references — the churn test pins that
        this leaks nothing.

        A row whose stream ends (in EOS) while a program issued AFTER the
        one being read still decodes it finishes towards the client
        here, and keeps its slot and pages until that program is read
        (:meth:`_retire`): no page is freed, registered for sharing or
        handed to another request while a program that can write it is
        in flight."""
        req = self.slot_req[slot]
        req.finish_time = time.monotonic()
        req.finish_reason = reason
        if req.first_token_time is not None:
            # the decode phase: first token → finish (TPOT × tokens).
            # Recorded BEFORE _FINISH is released: a consumer that
            # saw the stream end must find the span in the ring.
            self._trace_phase(
                req, "engine.decode",
                req.finish_time - req.first_token_time,
                slot=slot, tokens=req.n_generated,
                finish_reason=req.finish_reason,
                # the issue parts of every window booked to the request,
                # as /debug/requests shows them under dispatch_issue
                issue_s=req.cp.get("dispatch_issue", 0.0))
        held = self._ahead is not None and self._ahead.decodes(slot)
        if not held:
            self._release_pages(slot, req)
        self._book_thread_states(req)
        # breakdown finalized BEFORE _FINISH is released: a consumer
        # that saw the stream end must find the request in the
        # /debug/requests ring (same ordering rule as the decode span)
        self._record_finished(req)
        req.tokens.put(_FINISH)
        self.stats.observe_finished(req)
        if held:
            self.slot_ready[slot] = False
            self.slot_constraint[slot] = None
            self._zombies.add(slot)
        else:
            self._clear_slot(slot)

    def _book_thread_states(self, req: Request) -> None:
        """``req`` leaves the engine thread's hands inside this step (it
        finishes, or goes to the publisher): book the step's thread
        states SO FAR to it now, before its breakdown is final, and not
        again at the step's end."""
        states = self.steptrace.thread_states()
        if states is not None:
            req.cp_thread_states(states)
            self._step_held.pop(req.uid, None)

    def _clear_slot(self, slot: int) -> None:
        """``slot`` is free: nothing of its request is left in it."""
        self.slot_req[slot] = None
        self.slot_ready[slot] = False
        self.slot_budget[slot] = 0
        self.slot_closing[slot] = None
        self.slot_constraint[slot] = None
        self.slot_adapter[slot] = None
        self._zombies.discard(slot)

    def _release_pages(self, slot: int, req: Request) -> None:
        """The pages' half of a finish: the paged layout registers the
        slot's full pages for sharing and drops its references; a
        session's store hears of the turn."""
        if self.paged is not None:
            hist = self.slot_hist[slot]
            if hist:
                self._paged_register_pages(hist[:-1], slot, req.adapter)
                if (self.session_store is not None
                        and req.session_id is not None):
                    # sessions pin + publish BEFORE release_slot: the
                    # block table still maps the pages, so the pin's
                    # share() can never race a refcount-zero free
                    self._session_note_finish(slot, req, hist[:-1])
            self.paged.release_slot(slot)
        elif (self.session_store is not None
                and req.session_id is not None):
            # contiguous layout: no pages to pin — the store tracks the
            # conversation's token history and turn accounting only
            # (warm turns ride the row-based PrefixCache's LRU)
            hist = self.slot_hist[slot]
            self.session_store.note_finish(
                req.session_id, hist[:-1] if hist else req.prompt_ids,
                [], adapter=req.adapter,
                cache_outcome=req.cache_outcome)

    def _session_note_finish(self, slot: int, req: Request,
                             token_ids) -> None:
        """Session pin + fleet publish for a finishing paged slot
        (serve/sessions.py, ISSUE 17). ``token_ids`` is the KV-valid
        conversation history (``hist[:-1]`` — the final emitted token's
        KV was never written). Pins the full-page chain prefix under
        the session id, then — in fleet mode — gathers a page-aligned
        copy on THIS thread (the pages are still slot-mapped) and hands
        it to the store's publisher thread for the device→host copy +
        pool put, mirroring the disagg publisher split."""
        P = self.paged.page_size
        nfull = len(token_ids) // P
        pages = self.paged.slot_pages(slot)[:nfull] if nfull > 0 else []
        self.session_store.note_finish(
            req.session_id, token_ids, pages, adapter=req.adapter,
            cache_outcome=req.cache_outcome)
        if self.handoff is not None and nfull > 0:
            with self.steptrace.scope("publish"):
                # no last_logits: the entry is a page-aligned PARTIAL
                # prefix by design — the claiming replica recomputes at
                # least the suffix, which yields fresh logits
                entry = self._paged_gather_entry(slot, nfull * P, None)
            self.session_store.publish(
                req.session_id, token_ids[:nfull * P], entry)

    def _closing_reason(self, slot: int) -> str | None:
        """Why ``slot``'s stream ends with the token it just took, as
        far as the host can tell without the token: no budget left, or
        (cache_len guard) the emitted token's write, the next decode,
        would not fit."""
        if self.slot_budget[slot] <= 0:
            return "length"
        if self.slot_len[slot] + 1 >= self.cache_len:
            return "cache"
        return None

    def _emit(self, slot: int, token_id: int, why: str | None):
        """Stream one token out; end the stream on EOS, or where the
        row's deterministic end falls on this token (``why``:
        :meth:`_closing_reason` as it stood when the token's program was
        issued)."""
        req = self.slot_req[slot]
        hit_eos = self.eos_id is not None and token_id == self.eos_id
        if not hit_eos:
            req.tokens.put(token_id)
            req.n_generated += 1
        if hit_eos or why is not None:
            self._finish_slot(slot, "stop" if hit_eos else why)

    def _draft(self, hist: list[int], k: int) -> list[int] | None:
        """Prompt-lookup draft: find the most recent earlier occurrence of
        the trailing n-gram and propose the k tokens that followed it.
        Vectorized — this runs on the host between every decode step."""
        window = np.asarray(hist[-2048:], np.int32)   # bound the scan
        for n in range(self.speculative_ngram, 0, -1):
            if window.size <= n:
                continue
            pat = window[-n:]
            # candidate start positions, excluding the trailing n-gram
            # itself; match = all n positions equal at once
            limit = window.size - n
            hitmask = window[:limit] == pat[0]
            for j in range(1, n):
                hitmask &= window[j:limit + j] == pat[j]
            hits = np.nonzero(hitmask)[0]
            if hits.size:
                i = int(hits[-1])             # most recent occurrence
                cont = window[i + n: i + n + k].tolist()
                if cont:
                    return cont              # un-padded; caller zero-fills
        return None

    def _spec_applicable(self, active: list[int]) -> bool:
        """Whether the speculative verify step CAN run this step —
        shared by :meth:`_try_speculative` and the mixed-step
        composition decision (the two must never diverge: composition
        skips the fused dispatch on the promise that a verify runs
        instead)."""
        k = self.speculative_k
        if k is None:
            return False
        if not all(self._greedy[s] for s in active):
            return False                      # lossless only under greedy
        # every write of the wide step must land inside the cache — the
        # per-slot scatter clamps at the end and would corrupt tail
        # rows. That bound applies to mid-prefill rows too: the verify
        # writes k+1 dead rows at each one's device index (= done), and
        # a clamp there would shift backward over already-attended
        # prompt KV (in-bounds dead writes are fine — the owning chunk
        # overwrites them before any query attends).
        return (all(self.slot_len[s] + k + 1 <= self.cache_len
                    for s in active)
                and all(st["done"] + k + 1 <= self.cache_len
                        for st in self.slot_prefill.values()))

    def _try_speculative(self, active: list[int]) -> bool:
        """One FUSED speculative round: draft k tokens per slot (ngram
        or draft model), then verify + accept inside ONE jitted
        dispatch (serve/mixed_step.spec_verify_block). Returns False
        when the spec path doesn't apply this step (caller falls back
        to plain decode)."""
        k = self.speculative_k
        with self.steptrace.scope("plan"):
            applicable = self._spec_applicable(active)
        if not applicable:
            return False
        # draft BEFORE touching the page pool: drafting needs no pool
        # pages (ngram is host-side; the draft model's cache is its own
        # contiguous buffer), so a draft-miss step returns to the plain
        # path without having preempted or cache-finished anybody for a
        # k+1 reservation that would never be used
        with self.steptrace.scope("draft_propose"):
            if self.draft_model is not None:
                drafts = self._draft_model_propose(active, k)
            else:
                drafts = {}
                for s in active:
                    d = self._draft(self.slot_hist[s], k)
                    if d is not None:
                        drafts[s] = d         # un-padded, 1..k tokens
        if not drafts:
            return False                      # nothing to verify; plain step
        if self.paged is not None:
            # the fused round writes k+1 rows per slot: reserve the
            # pages up front (preempting youngest slots if dry) — the
            # speculative watermark of any preempted slot is reset in
            # _paged_preempt, so a recycled draft cache re-syncs
            with self.steptrace.scope("admit"):
                active = self._paged_reserve_active(active, k + 1)
            if not active:
                return True
            drafts = {s: d for s, d in drafts.items() if s in active}
        with self.steptrace.scope("index_build"):
            tokens = np.zeros((self.max_slots, k + 1), np.int32)
            tokens[:, 0] = self.slot_last_token
            for s, d in drafts.items():
                tokens[s, 1: 1 + len(d)] = d
            mask = np.zeros((self.max_slots,), np.int32)
            mask[active] = 1
        # grammar composition (ISSUE 12): stage k+1 per-position masks
        # by tentatively advancing each constrained slot's automaton
        # over its drafts — the on-device acceptance cumprod then
        # rejects grammar-forbidden drafts like argmax mismatches.
        with self.steptrace.scope("index_build"):
            gmasks = self._grammar_spec_masks(active, tokens, k, drafts)
            # multi-LoRA: the verify IS the target forward, so the
            # adapter delta rides the spec twins; the drafts above
            # stayed base-model
            lora = self._lora_args()
            kw = {} if lora is None else {"lora": lora}
        with self.steptrace.scope("dispatch_wait"):
            self.steptrace.window_begin("decode")
            if self.paged is not None:
                W = self._paged_width(
                    max(int(self.slot_len[s]) for s in active) + k + 1)
                self._pulse_view(W)
                idxv = self._paged_index_vec(W, k + 1)
                valid = np.zeros((self.max_slots,), np.int32)
                for s in active:
                    valid[s] = k + 1
                    self._paged_cow_fork(s, int(self.slot_len[s]), k + 1)
                if gmasks is not None:
                    fn = (self._pg_spec_masked if lora is None
                          else self._pg_spec_masked_lora)
                    out, n_acc, self.paged.kv = fn(
                        self.params, self.paged.kv,
                        jnp.asarray(self._paged_view_idx(W)),
                        jnp.asarray(idxv),
                        jnp.asarray(self.paged.scatter_idx(
                            idxv, valid, k + 1)),
                        jnp.asarray(tokens), jnp.asarray(mask),
                        jnp.asarray(gmasks), **kw)
                else:
                    fn = (self._pg_spec if lora is None
                          else self._pg_spec_lora)
                    out, n_acc, self.paged.kv = fn(
                        self.params, self.paged.kv,
                        jnp.asarray(self._paged_view_idx(W)),
                        jnp.asarray(idxv),
                        jnp.asarray(self.paged.scatter_idx(idxv, valid,
                                                           k + 1)),
                        jnp.asarray(tokens), jnp.asarray(mask), **kw)
            elif gmasks is not None:
                fn = (self._decode_spec_masked if lora is None
                      else self._decode_spec_masked_lora)
                base = self._paged_index_vec(self.cache_len, k + 1)
                out, n_acc, self.cache = fn(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(base), jnp.asarray(mask),
                    jnp.asarray(gmasks), **kw)
            else:
                # per-row pinned index: the slot-state → index
                # convention lives in ONE place (_paged_index_vec reads
                # only host slot state — nothing paged about it); here
                # the "view" is the whole contiguous cache, so
                # W = cache_len. Free rows' dead k+1 write window is
                # clamped inside the cache; live rows already fit
                # (_spec_applicable), so their clamp is a no-op.
                base = self._paged_index_vec(self.cache_len, k + 1)
                fn = (self._decode_spec if lora is None
                      else self._decode_spec_lora)
                out, n_acc, self.cache = fn(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(base), jnp.asarray(mask), **kw)
            self.steptrace.window_issued()
            with self.steptrace.fetch():
                out_host = np.asarray(out)
                acc_host = np.asarray(n_acc)
            dt, _ = self._window_close(
                "decode", [self.slot_req[s] for s in active])
            # the verify is ONE wide forward over k+1 positions per slot
            # (that width amortizing the weight read is the whole spec
            # bet — the decode MFU gauge shows it paying off or not).
            # Useful positions only: an undrafted/short-draft slot's zero
            # padding is wasted work and must read as lost MFU, same
            # convention as the spec_proposed/spec_accepted counters
            # below.
            useful = {s: len(drafts.get(s, ())) + 1 for s in active}
            keys = sum(CostModel.block_keys(useful[s],
                                            int(self.slot_len[s]))
                       for s in active)
            self._note_device_phase(
                "decode", tokens=sum(useful.values()),
                attended_keys=keys, weight_passes=1,
                kv_read_tokens=keys, dt=dt)
        self.spec_rounds += 1
        with self.steptrace.scope("sample_commit"):
            for s in active:
                n_acc_s = int(acc_host[s])
                # metrics over real drafted positions only — zero
                # padding (and undrafted slots' zero fill) must not
                # inflate either counter
                n_drafted = len(drafts.get(s, ()))
                self.spec_proposed += n_drafted
                self.spec_accepted += min(n_acc_s, n_drafted)
                for j in range(n_acc_s + 1):
                    if self.slot_req[s] is None:
                        break                 # finished mid-burst (eos/len)
                    self._commit_token(s, int(out_host[s, j]))
                    self.spec_round_tokens += 1
        return True

    def _commit_token(self, slot: int, tok: int) -> None:
        """Book one generated token into a slot, both halves at once
        (the paths that read a program before they issue the next: the
        speculative round, the contiguous layout). The token is the
        host's: the next paged program puts it into the plane."""
        why = self._advance_row(slot)
        self._tokens_fix[slot] = tok
        self._commit_value(slot, tok, why)

    def _advance_row(self, slot: int) -> str | None:
        """The half of a commit that needs no token VALUE: budget and
        length move by the token the row takes (where its budget or its
        cache room ends with it, that is why it closes, and no later
        program takes the row). Done when the program is issued.
        Returns why the row ends with this token, or None."""
        self.slot_budget[slot] -= 1
        self.slot_len[slot] += 1
        why = self.slot_closing[slot] = self._closing_reason(slot)
        return why

    def _commit_value(self, slot: int, tok: int, why: str | None) -> None:
        """The half of a commit that needs the token: last-token mirror,
        spec history, grammar advance, and emission (which may finish
        the slot, on EOS or for the deterministic ``why``). Done when
        the program is read."""
        self.slot_last_token[slot] = tok
        if self.slot_hist[slot] is not None:
            self.slot_hist[slot].append(tok)
        # capture before _emit: an eos/budget finish clears the slot's
        # constraint reference, but the cursor must still advance (it
        # lives on the request and the stream's last token is part of
        # the grammar position a preempt-resume would continue from)
        cs = self.slot_constraint[slot]
        self._emit(slot, tok, why)
        self._constraint_commit(slot, cs, tok)

    def _update_active_stats(self) -> None:
        with self.steptrace.scope("sample_commit"), self.stats.lock:
            self.stats.active_slots = sum(
                r is not None for r in self.slot_req)

    def _ready_slots(self) -> list[int]:
        """The rows the next decode takes: ready, and not at their
        deterministic end (such a row only waits for its last program to
        be read)."""
        return [s for s, r in enumerate(self.slot_req)
                if r is not None and self.slot_ready[s]
                and self.slot_closing[s] is None]

    # --- grammar (constrained decoding, serve/constrain.py) ------------------

    def _ensure_constraint(self, req: Request):
        """This request's live grammar cursor, minted from the compiled
        automaton on first touch (activation). The lazy automaton-state
        compile the mint may trigger books under ``grammar_compile``
        (the PR 11 coverage gate must see it, not an ``other`` blob)."""
        if req.constraint_state is None and req.params.constraint is not None:
            with self.steptrace.scope("grammar_compile"):
                req.constraint_state = req.params.constraint.cursor()
        return req.constraint_state

    def _constrained_active(self, active: list[int]) -> bool:
        return any(self.slot_constraint[s] is not None for s in active)

    def _grammar_mask_rows(self, cursors) -> np.ndarray:
        """(len(cursors), vocab) float32 additive mask rows — None
        entries get zero rows. The ONE staging-accounting site: wall
        time books into llm_grammar_mask_seconds_total under the
        ``grammar_mask`` activity, lazy vocab-wide state compiles (the
        dominant grammar cost) under ``grammar_compile``. At least one
        cursor must be non-None."""
        t0 = time.monotonic()
        with self.steptrace.scope("grammar_mask"):
            out = np.zeros(
                (len(cursors),
                 next(c.vocab_size for c in cursors if c is not None)),
                np.float32)
            for j, cs in enumerate(cursors):
                if cs is None:
                    continue
                if cs.needs_compile():
                    with self.steptrace.scope("grammar_compile"):
                        cs.auto.ensure(cs.cur)
                out[j] = cs.mask_row()
        self.grammar_mask_seconds_total += time.monotonic() - t0
        return out

    def _grammar_masks(self, active: list[int]):
        """(max_slots, vocab) float32 additive mask for this step's
        decode — each constrained slot's automaton-state row, zeros for
        unconstrained slots — or None when no active slot is
        constrained (the unmasked programs then run untouched). The
        slot_constraint vector IS the constrained-active set: cursors
        install at activation and clear at finish/preempt."""
        if not self._constrained_active(active):
            return None
        return self._grammar_mask_rows(self.slot_constraint)

    def _grammar_spec_masks(self, active: list[int], tokens, k: int,
                            drafts: dict):
        """(max_slots, k+1, vocab) staged masks for a fused spec round:
        the host advances each constrained slot's grammar TENTATIVELY
        over its drafted tokens — position ``j`` gets the state after
        the first ``j`` drafts, so the masked verify's acceptance
        cumprod truncates at a grammar-forbidden draft exactly like an
        argmax mismatch (serve/mixed_step.spec_verify_block). Rejected
        drafted tokens count into llm_spec_grammar_rejects_total.
        Returns None when no active slot is constrained."""
        rows = [(s, self.slot_constraint[s]) for s in active
                if self.slot_constraint[s] is not None]
        if not rows:
            return None
        t0 = time.monotonic()
        with self.steptrace.scope("grammar_mask"):
            gmasks = np.zeros(
                (self.max_slots, k + 1, rows[0][1].vocab_size),
                np.float32)
            for s, cs in rows:
                auto, cur = cs.auto, cs.cur
                n_drafted = len(drafts.get(s, ()))
                for j in range(k + 1):
                    if not auto.compiled(cur):
                        with self.steptrace.scope("grammar_compile"):
                            auto.ensure(cur)
                    gmasks[s, j] = auto.mask(cur)
                    if j >= k:
                        break
                    # stage through position j+1's input token — a real
                    # draft or the zero padding (padding acts as an
                    # implicit draft on the unmasked path too); a
                    # forbidden token ends the staging: positions past
                    # it can never be accepted (cumprod is already 0),
                    # so their zero rows are inert
                    nxt = auto.step(cur, int(tokens[s, j + 1]))
                    if nxt is None:
                        if j < n_drafted:
                            self.spec_grammar_rejects += 1
                        break
                    cur = nxt
        self.grammar_mask_seconds_total += time.monotonic() - t0
        return gmasks

    def _constraint_commit(self, slot: int, cs, tok: int) -> None:
        """Advance ``slot``'s grammar cursor over an emitted token; a
        completed value finishes the stream (``finish_reason="stop"``)
        — deterministic, and independent of whether the vocab has an
        EOS id at all. An explicit EOS emission is the grammar's own
        allowed stop (accepting states admit it) and is not consumed."""
        if cs is None:
            return
        if self.eos_id is not None and tok == self.eos_id:
            return
        if cs.advance(tok) and self.slot_req[slot] is not None:
            self._finish_slot(slot, "stop")

    def _mixed_feasible(self, active: list[int]) -> tuple[bool, str]:
        """Can this step run as ONE fused dispatch? The bounds are the
        scatter-clamp invariants documented in serve/mixed_step.py; a
        miss falls back to the sequential two-dispatch path (rare tail:
        rows butting against the cache end)."""
        C = self.chunked_prefill
        for slot, st in self.slot_prefill.items():
            if st["done"] + C + 1 > self.cache_len:
                return False, (
                    "prefill row near the cache end: "
                    f"slot {slot} done {st['done']} + chunk {C} + "
                    f"1 > cache_len {self.cache_len}")
        if self.paged is not None:
            # no decode row receives a chunk write in this layout; its
            # own new row must fit
            for s in active:
                if int(self.slot_len[s]) + 1 > self.cache_len:
                    return False, (
                        "decode row lacks its write window: "
                        f"slot {s} len {int(self.slot_len[s])} + 1 "
                        f"> cache_len {self.cache_len}")
            return True, ""
        for s in range(self.max_slots):
            # contiguous layout: every occupied non-prefill row receives
            # the dead chunk write at its own index (free rows clamp;
            # occupied rows must fit exactly) — same bound as the
            # batched chunk path
            if s in self.slot_prefill or self.slot_req[s] is None:
                continue
            if int(self.slot_len[s]) + C > self.cache_len:
                return False, (
                    "decode row lacks the chunk dead-write window: "
                    f"slot {s} len {int(self.slot_len[s])} + chunk {C} "
                    f"> cache_len {self.cache_len}")
        return True, ""

    def _mixed_dispatch(self, active: list[int]):
        """Issue the fused mixed-batch program: the step's mid-prefill
        rows (:meth:`_chunk_entries`) advance one chunk AND every ready
        row decodes a token, in ONE device dispatch
        (serve/mixed_step.py). Host bookkeeping mirrors the sequential
        paths exactly: chunk results feed ``slot_prefill``/finalization,
        decode tokens commit per slot.
        The paged program is issued here and read in :meth:`_retire`
        (a step later where nothing forbids it: :meth:`_fly`).
        Returns False (nothing dispatched) only when paged page
        reservation drained either half — the caller falls through to
        the sequential paths for this step — and ``_REPLAN`` where the
        reservation would have to preempt or finish a row while a
        program is unread (it has been read now: plan again)."""
        C = self.chunked_prefill
        if self.paged is not None:
            # reserve the decode half's writes: one row per ready slot
            # (may preempt youngest). The prefill half needs nothing —
            # admission reserved every prompt page up front, and the
            # decode's garbage row above each prefill watermark
            # scatters to the trash page.
            with self.steptrace.scope("admit"):
                if not self._reserve_ahead(active, 1):
                    return _REPLAN
                active = self._paged_reserve_active(active, 1)
            if not active or not self.slot_prefill:
                return False
        with self.steptrace.scope("index_build"):
            entries = self._chunk_entries()
            if self.paged is None:
                tok, starts, lens = self._chunk_batch_rows(entries)
                advance = np.zeros((self.max_slots,), np.int32)
                advance[active] = 1
            # constrained decoding: the decode half of the fused step masks
            # each grammar slot's logits; mid-prefill rows need nothing —
            # a constrained row's first token samples at finalization,
            # where the host applies the start-state mask
            # (_first_from_program)
            gmask = self._grammar_masks(active)
            # multi-LoRA: slot-plane adapter rows cover BOTH halves of the
            # fused program (the paged prefill half picks its rows' out
            # of the plane)
            lora = self._lora_args()
            kw = {} if lora is None else {"lora": lora}
            # per-phase device accounting for the ONE fused dispatch: the
            # wall time is split between prefill and decode in proportion
            # to each half's FLOPs (token-count fallback without a cost
            # model) — arxiv 2311.03687's phase dissection must survive the
            # fusion that merged the phases into one program
            book = {
                "pf_tokens": sum(len(c) for _, _, c in entries),
                "pf_keys": sum(CostModel.chunk_keys(len(c), st["done"])
                               for _, st, c in entries),
                "dc_tokens": len(active),
                "dc_keys": sum(
                    CostModel.block_keys(1, int(self.slot_len[s]))
                    for s in active)}
        self.mixed_blocks += 1
        if self.paged is not None:
            self._fly(self._issue_mixed(active, entries, gmask, lora, book))
            return True
        # one scope spans through the two note_device_phase calls below
        # (their dt shares must land inside it so the device deduction
        # balances) — and the dispatch calls themselves, so a raising
        # dispatch can't leak an open scope frame
        with self.steptrace.scope("dispatch_wait"):
            self.steptrace.window_begin("mixed")
            self.rng, sub = jax.random.split(self.rng)
            sampling = (jnp.asarray(self.slot_last_token), sub,
                        *self._sampling_args(active))
            if gmask is not None:
                sampling += (jnp.asarray(gmask),)
            self._note_chunk_rows(len(entries), self.max_slots)
            if gmask is not None:
                fn = (self._mixed_masked if lora is None
                      else self._mixed_masked_lora)
            else:
                fn = self._mixed if lora is None else self._mixed_lora
            chunk_last, toks, self.cache = fn(
                self.params, self.cache, jnp.asarray(tok),
                jnp.asarray(starts), jnp.asarray(lens),
                jnp.asarray(advance), *sampling, **kw)
            self.steptrace.window_issued()
            # ONE fetch forces the dispatch's results
            with self.steptrace.fetch():
                toks_host = np.asarray(toks)
            # the window advanced the mid-prefill rows' prompts; every
            # decode member sat through the whole fused dispatch for
            # them (prefill_stall, not decode_dispatch)
            dt, issue_s = self._window_close(
                "prefill", [st["req"] for _, st, _ in entries])
            self._chunks_done(entries, chunk_last)
            self._trace_chunks(entries, dt, issue_s, batched=True,
                               fused=True)
            self._note_mixed_phases(book, dt, passes=1)
        with self.steptrace.scope("sample_commit"):
            self._finalize_prefills()
            for slot in active:
                if self.slot_req[slot] is not None:
                    self._commit_token(slot, int(toks_host[slot, 0]))
        return True

    def _note_mixed_phases(self, book: dict, dt: float, *,
                           passes: int) -> None:
        """Book a fused dispatch's window ``dt`` to the two phases in
        proportion to each half's FLOPs (``book``: the halves' tokens
        and attended keys as issued)."""
        cm = self.cost_model
        if cm is not None:
            pf, df = (cm.step_flops(book["pf_tokens"], book["pf_keys"]),
                      cm.step_flops(book["dc_tokens"], book["dc_keys"]))
            share = pf / (pf + df) if pf + df > 0 else 0.5
        else:
            share = book["pf_tokens"] / max(
                book["pf_tokens"] + book["dc_tokens"], 1)
        self._note_device_phase(
            "prefill", tokens=book["pf_tokens"],
            attended_keys=book["pf_keys"], weight_passes=passes,
            kv_read_tokens=book["pf_keys"], dt=dt * share)
        self._note_device_phase(
            "decode", tokens=book["dc_tokens"],
            attended_keys=book["dc_keys"], weight_passes=1,
            kv_read_tokens=book["dc_keys"], dt=dt * (1 - share))

    def _issue_mixed(self, active: list[int], entries, gmask, lora,
                     book: dict) -> _Flight:
        """Issue the PAGED fused mixed program (pages reserved, rows
        planned by :meth:`_mixed_dispatch`): the rows that decode take
        the last-token plane, the rows that chunk are the host's; the
        plane comes back with the decoded tokens and the finished
        prompts' first tokens in it."""
        C = self.chunked_prefill
        kw = {} if lora is None else {"lora": lora}
        with self.steptrace.scope("dispatch_wait"):
            f = self._window_open("mixed", _Flight("mixed", book=book))
            self.rng, sub = jax.random.split(self.rng)
            # the program samples the first token of the rows whose
            # prompt it ends: they choose the sampler's body with the
            # rows that decode
            finishing = self._finishing(entries)
            tail, sampled = self._tail_key(finishing)
            sampling = (*self._plane_args(), sub,
                        *self._sampling_args(active + sampled))
            if gmask is not None:
                sampling += (jnp.asarray(gmask),)
            # ONE view width for both halves: each prefill row's chunk
            # + the decode's row (done+C+1), and each occupied decode
            # row's len+C, capped at cache_len — no decode row
            # receives a chunk write any more, but the warm-up
            # builds the widths THIS rule gives (narrower decode
            # views are a change of their own)
            if self._reads_pages:
                # no view of the decode plane: the width is the chunk
                # rows' alone, each gathered for its own trip, and no
                # narrower than the SHORTEST row that decodes beside
                # them (its length after this step) would make the
                # rule below: a prompt's first chunks then build no
                # mixed program that a gathered engine would not
                # (PERF.md, PR 49: two of five in the agents' cell, 23 s
                # of a 150 s set-up)
                floor = min(int(self.slot_len[s]) for s in active) + 1 + C
                W = self._paged_width(max(
                    max(st["done"] for _, st, _ in entries) + C,
                    min(floor, self.cache_len)))
                self._pulse_view(W, 1)
            else:
                need = max(
                    [st["done"] + C + 1 for _, st, _ in entries]
                    + [min(int(self.slot_len[s]) + C, self.cache_len)
                       for s in self._ready_slots()] + [C + 1])
                W = self._paged_width(need)
                self._pulse_view(W)
            if gmask is not None:
                fn = (self._pg_mixed_masked if lora is None
                      else self._pg_mixed_masked_lora)
            else:
                fn = (self._pg_mixed if lora is None
                      else self._pg_mixed_lora)
            # statements of their own: either may fork a shared
            # page, which REBINDS the (donated) pool read below
            rows = self._paged_entry_rows(entries, W)
            plan = self._paged_decode_plan(active, W)
            (f.first, chunk_last, f.toks, self._tokens_dev, self.paged.kv,
             *counted) = fn(self.params, self.paged.kv, *rows, *tail,
                            *plan, *sampling, **kw)
            self._prompts_issued(f, "mixed", entries, finishing,
                                 chunk_last, counted)
            f.rows = [(s, self.slot_req[s], self._advance_row(s))
                      for s in active if self.slot_req[s] is not None]
            self.steptrace.window_issued()
        return f

    # --- issue and retire (one step of lookahead) ----------------------------
    #
    # A device step has two halves. ISSUE: admit, plan, reserve pages,
    # build indices, dispatch; it needs no token VALUE of the program
    # before it, because the last tokens stay on the device (the plane)
    # and lengths, budgets, pages and readiness are deterministic.
    # RETIRE: fetch the program's tokens (the step's one forcing point),
    # emit and finish, book. The loop is issue(n+1); retire(n): the host's
    # share of a step runs while the device computes. Where the next
    # plan does need values, the engine DRAINS (retires what is in
    # flight) and steps as it always did: issue, then retire at once.
    # A block-diffusion pass has the same two halves (its plane is the
    # rows' blocks, its schedule a count of revealed positions a row:
    # BlockDecoder.issue / .retire) and flies through the same loop.

    def _ahead_blocker(self) -> str | None:
        """Why this step's program may not be issued before the one in
        flight is read, from what the engine observes of itself; None:
        nothing forbids it. (Further reasons are found on the way and
        drain where they are found: an admission that dispatches, a
        page reservation that must preempt, a step of two programs.)"""
        if self.paged is None:
            return "contiguous"     # its programs take the host's tokens
        ready = self._ready_slots()
        if self.block is not None and self.block.dynamic[ready].any():
            return "block_dynamic"  # a pass's reveal decides the next
        if self._constrained_active(ready):
            return "grammar"        # the mask is a function of the token
        if self._spec_applicable(ready):
            return "speculative"    # drafts come from the history
        if self.session_store is not None and any(
                self.slot_req[s].session_id is not None for s in ready):
            return "session"        # its turn is pinned before _FINISH
        return None

    def _drain(self, why: str) -> None:
        """Retire what is in flight: the caller needs the engine's state
        as the serial loop has it (every token on the host, no row at a
        pending end, no page held for an unread program). ``why`` is the
        step's reason for not running ahead (the first one stands),
        whether or not a program was in flight."""
        if self._step_why is None:
            self._step_why = why
        f, self._flight = self._flight, None
        if f is not None:
            self._retire(f)

    def _reserve_ahead(self, active: list[int], n: int) -> bool:
        """May the step reserve ``n`` more positions a ready row while a
        program is unread? Only if no reservation has to preempt or
        finish a row; else the program is read first (False: plan the
        step again, as the serial loop would)."""
        if self._flight is None or all(
                self.paged.extend(s, int(self.slot_len[s]) + n)
                for s in active):
            return True
        self._drain("preempt")
        return False

    def _fly(self, f: _Flight) -> None:
        """``f`` was just issued: read the program before it (this is
        the host work the device no longer waits for), and leave ``f``
        in flight for the next step, unless that step's plan needs a
        value only ``f``'s reading gives (a first token the host
        samples)."""
        self._flew = True
        old, self._flight = self._flight, None
        if old is not None:
            self._ahead = f
            try:
                self._retire(old)
            finally:
                self._ahead = None
        if f.host_first:
            self._next_why = "host_first_token"
            self._retire(f)
        else:
            self._flight = f

    def _retire(self, f: _Flight) -> None:
        """Read the program ``f``: ONE fetch (its tokens, the first
        tokens of the prompts it ended, what it counted) forces its
        results, then the halves of activation and commit that need the
        values run: emission, EOS, finishes, the statistics' booking."""
        if f.kind == "block":
            self.block.retire(f)
            return self._update_active_stats()
        chunked = [st["req"] for _, st, _ in f.entries]
        with self.steptrace.scope("dispatch_wait"):
            stats = self.step_stats
            with self.steptrace.fetch():
                first, toks, parts = jax.device_get(
                    (f.first, f.toks, stats and stats.counted(f.stats)))
            # a window that advanced prompts is theirs (every decode
            # member sat through it for them: prefill_stall), else the
            # decode rows'
            dt, issue_s = self._window_close(
                "prefill" if chunked else "decode",
                chunked or [row[1] for row in f.rows], f.holders)
            book = f.book
            if f.kind == "mixed":
                self._trace_chunks(f.entries, dt, issue_s, batched=True,
                                   fused=True)
                # the paged loop streams the weights once a row
                self._note_mixed_phases(book, dt, passes=len(f.entries))
            elif f.kind == "chunk":
                self._trace_chunks(f.entries, dt, issue_s, batched=True)
                self._note_device_phase(
                    "prefill", tokens=book["pf_tokens"],
                    attended_keys=book["pf_keys"],
                    weight_passes=len(f.entries),
                    kv_read_tokens=book["pf_keys"], dt=dt)
            else:
                self._note_device_phase(
                    "decode", tokens=book["dc_tokens"],
                    attended_keys=book["dc_keys"], weight_passes=1,
                    kv_read_tokens=book["dc_keys"], dt=dt)
        with self.steptrace.scope("sample_commit"):
            for slot, req, st, why in f.finished:
                # rows are already in the slot; store the prefix entry
                # from them
                self._paged_store_prefix(req, st["plen"], slot,
                                         st["last_logits"])
                self._note_first_token("program")
                self._first_token_out(slot, req, int(first[slot]), why,
                                      on_device=True)
            if f.host_first:
                self._finalize_prefills()
            for slot, req, why in f.rows:
                if slot in self._zombies:
                    # its stream ended in EOS when the program before
                    # this one was read: the token goes nowhere, and the
                    # slot and its pages are free from here on
                    self.steptrace.note_discarded(1)
                    self._release_pages(slot, req)
                    self._clear_slot(slot)
                    continue
                self._commit_value(slot, int(toks[slot, 0]), why)
            if stats is not None:
                stats.book(f.stats, parts)
        self._update_active_stats()

    def _decode_paged(self, active: list[int], sub):
        """Issue the paged decode program for the ready rows ``active``
        (pages reserved by the caller). The rows take their
        deterministic step here; their tokens are read in
        :meth:`_retire`."""
        # constrained decoding: per-slot grammar mask rows, applied by
        # the masked twin program in the SAME single dispatch
        with self.steptrace.scope("index_build"):
            gmask = self._grammar_masks(active)
            lora = self._lora_args()
        with self.steptrace.scope("dispatch_wait"):
            f = self._window_open("decode", _Flight("decode", book={
                "dc_tokens": len(active),
                "dc_keys": sum(
                    CostModel.block_keys(1, int(self.slot_len[s]))
                    for s in active)}))
            self._paged_decode_dispatch(f, active, sub, gmask=gmask,
                                        lora=lora)
            f.rows = [(s, self.slot_req[s], self._advance_row(s))
                      for s in active if self.slot_req[s] is not None]
            self.steptrace.window_issued()
        self._fly(f)

    def _block_pass(self, active: list[int]):
        """Issue one block-diffusion pass over the ready rows ``active``
        (serve/block_step.py): reserve a block's pages a row, then the
        pass; it is read in :meth:`_retire`."""
        B = self.block.B
        with self.steptrace.scope("admit"):
            for s in list(active):
                if int(self.slot_len[s]) + B > self.cache_len:
                    # a fresh row with no room for a block (one that
                    # committed its last closed when that pass was issued)
                    self._finish_slot(s, "cache")
                    active.remove(s)
            if not self._reserve_ahead(active, B):
                return _REPLAN
            active = self._paged_reserve_active(active, B)
        if active:
            f = _Flight("block")
            self.block.issue(active, f)
            self._fly(f)
        else:
            self._update_active_stats()     # the rows ended here
        return True

    def step(self) -> bool:
        """One engine iteration. Returns False when fully idle."""
        t_lock = time.perf_counter()
        with self._lock:
            before = self.dispatch_meter.total
            # the flight recorder brackets the WHOLE step; what the
            # thread waited for the lock (submitters hold it briefly)
            # lies before the record and is a field of it
            self.steptrace.step_begin(
                lock_wait_s=time.perf_counter() - t_lock)
            if self.steptrace.enabled:
                self._step_held = {
                    r.uid: r for r in self.slot_req
                    if r is not None and r.finish_time is None}
            busy = False
            try:
                busy = self._step_locked()
                return busy
            finally:
                spent = self.dispatch_meter.total - before
                # idle background-loop polls (~10 Hz while waiting on
                # _wake) must not record 0-dispatch steps, or the
                # per-step rolling mean decays to 0 on any bursty
                # server and the metric stops meaning anything (the
                # steptrace ring follows the same rule)
                if busy or spent or self._read:
                    with self.steptrace.scope("sample_commit"):
                        self.dispatch_meter.note_step(spent)
                    self._book_step(self.steptrace.step_end(self.tracer))
                else:
                    self.steptrace.step_abort()

    def _book_step(self, rec: dict | None) -> None:
        """The step's record is closed: its wall by thread state goes to
        every request that held a slot during the step and is still the
        engine's (CP_THREAD_STATES)."""
        if rec is None:
            return
        held = self._step_held
        for r in self.slot_req:
            if r is not None and r.finish_time is None:
                held[r.uid] = r
        states = (rec["wall_s"], rec["cpu_s"], rec["blocked_s"],
                  rec["stalled_s"])
        for r in held.values():
            r.cp_thread_states(states)

    def _step_locked(self) -> bool:
        self._step_why, self._next_why = self._next_why, None
        self._flew = False
        self._read = self._flight is not None   # this step reads a program
        with self.steptrace.scope("admit"):
            self._admit()
        busy = self._plan_step()
        if busy is _REPLAN:
            # a reservation had to read the program in flight: the plan
            # was made on rows that may have ended; make it again
            busy = self._plan_step()
        if not self._flew:
            # nothing was issued: what is in flight is all there is to do
            self._drain("idle")
        self.steptrace.note_drain(self._step_why or "idle")
        # a program left unread is work (the next step reads it), and so
        # is a request that waited for the slot this step's reading freed
        return (bool(busy) or self._flight is not None
                or (self._read and self.pending.qsize() > 0))

    def _plan_step(self):
        budget = self.prefill_budget
        with self.steptrace.scope("plan"):
            why = self._ahead_blocker()
        if why is not None:
            self._drain(why)
        if self.block is not None:
            # block-diffusion model: chunks and block rows take two
            # dispatches (no fused mixed block step yet), and one pass
            # over the ready rows replaces the decode families below
            if self.slot_prefill:
                self._drain("two_dispatch")
            progressed = self._advance_prefills(budget)
            with self.steptrace.scope("plan"):
                active = self._ready_slots()
            if not active:
                return progressed or bool(self.slot_prefill)
            return self._block_pass(active)
        # A speculative engine keeps speculating while prompts prefill:
        # its verify step yields 1+accepted tokens per dispatch,
        # strictly more than the fused step's single token —
        # suspending it there would REGRESS mixed-load TPOT on
        # accepting workloads. Composition only applies when
        # speculation actually CAN run this step — non-greedy traffic
        # on a spec engine must not lose the fused step too.
        with self.steptrace.scope("plan"):
            active = self._ready_slots()
            spec_composes = (
                self._spec_applicable(active)
                # the verify runs AFTER this step's chunks advance each
                # prefill row (by up to budget chunks) — account for
                # that movement here, or near the cache tail the
                # composition promise breaks: the feasible fused
                # dispatch is skipped and _try_speculative then
                # declines post-advance, leaving 2 dispatches for 1
                # token
                and all(st["done"] + budget * self.chunked_prefill
                        + self.speculative_k + 1 <= self.cache_len
                        for st in self.slot_prefill.values())
            )
        pre_progress = False
        if (self.mixed_step and self.slot_prefill and active
                and not spec_composes):
            # Fused mixed-batch step: prefill chunks + the decode in
            # ONE dispatch.
            if budget > 1:
                # the fused program carries ONE chunk per dispatch;
                # spend the rest of the guaranteed prefill budget
                # sequentially first so the TTFT bound
                # (ceil(chunks/budget) steps) still holds — and
                # re-snapshot the ready set, since a prompt finishing
                # its last chunk here activates and must join this
                # step's decode (sequential-path parity)
                self._drain("two_dispatch")
                pre_progress = self._advance_prefills(budget - 1)
                budget = 1
                with self.steptrace.scope("plan"):
                    active = self._ready_slots()
            if self.slot_prefill and active:
                with self.steptrace.scope("plan"):
                    ok, why = self._mixed_feasible(active)
                if ok:
                    issued = self._mixed_dispatch(active)
                    if issued is _REPLAN:
                        return _REPLAN
                    if issued:
                        self._update_active_stats()
                        return True
                    # paged page reservation drained one half of the
                    # mixed sets: run this step's remainder on the
                    # sequential paths
                else:
                    # log each fallback KIND once (the detail after ':'
                    # varies per occurrence; keying the dedup on it
                    # would grow without bound on a long-running server)
                    kind = why.split(":", 1)[0]
                    if kind not in self._mixed_fallbacks_logged:
                        self._mixed_fallbacks_logged.add(kind)
                        self._log.info(
                            "fused mixed step fell back to sequential "
                            "dispatches: %s", why)
        if self.slot_prefill:
            if (self.paged is not None and budget == 1
                    and not self._ready_slots()):
                # nothing decodes: the chunk program is the step's one
                # program, and the next (chunk, mixed or decode) takes
                # the first tokens it samples from the plane
                self._fly(self._issue_chunk())
                return True
            # chunks, then the decode: two programs, the second
            # planned on what the first one's reading activates
            self._drain("two_dispatch")
        progressed = self._advance_prefills(budget) or pre_progress
        with self.steptrace.scope("plan"):
            active = self._ready_slots()
        if not active:
            return progressed or bool(self.slot_prefill)
        if self._try_speculative(active):
            self._update_active_stats()
            return True
        if self.paged is not None:
            with self.steptrace.scope("admit"):
                if not self._reserve_ahead(active, 1):
                    return _REPLAN
                active = self._paged_reserve_active(active, 1)
            if not active:
                return True  # reservation finished/preempted them all
        with self.steptrace.scope("index_build"):
            # the step's sampling key: a small eager device program
            self.rng, sub = jax.random.split(self.rng)
        if self.paged is not None:
            self._decode_paged(active, sub)
            return True
        # constrained decoding: per-slot grammar mask rows, applied by
        # the masked twin program in the SAME single dispatch
        with self.steptrace.scope("index_build"):
            gmask = self._grammar_masks(active)
            lora = self._lora_args()
            kw = {} if lora is None else {"lora": lora}
        with self.steptrace.scope("dispatch_wait"):
            self.steptrace.window_begin("decode")
            if gmask is not None:
                fn = (self._decode_masked if lora is None
                      else self._decode_masked_lora)
                next_tok, self.cache = fn(
                    self.params, self.cache,
                    jnp.asarray(self.slot_last_token),
                    sub,
                    *self._sampling_args(active),
                    jnp.asarray(gmask), **kw,
                )
            else:
                fn = self._decode if lora is None else self._decode_lora
                next_tok, self.cache = fn(
                    self.params, self.cache,
                    jnp.asarray(self.slot_last_token),
                    sub,
                    *self._sampling_args(active),
                    **kw,
                )
            self.steptrace.window_issued()
            with self.steptrace.fetch():
                next_host = np.asarray(next_tok)
            dt, _ = self._window_close(
                "decode", [self.slot_req[s] for s in active])
            keys = sum(CostModel.block_keys(1, int(self.slot_len[s]))
                       for s in active)
            self._note_device_phase(
                "decode", tokens=len(active), attended_keys=keys,
                weight_passes=1, kv_read_tokens=keys, dt=dt)
        with self.steptrace.scope("sample_commit"):
            for slot in active:
                self._commit_token(slot, int(next_host[slot]))
        self._update_active_stats()
        return True

    # --- background loop -----------------------------------------------------

    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            busy = self.step()
            if not busy:  # idle: block until a submit wakes us (don't spin)
                self._wake.wait(timeout=0.1)
                self._wake.clear()

    def _hbm_book(self, owner: str, n_bytes: int) -> None:
        """Book one durable allocation under ``owner`` and remember it
        so ``stop()`` frees exactly what ``__init__`` booked."""
        self._hbm.book(owner, n_bytes)
        self._hbm_booked[owner] = n_bytes

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        if self.session_store is not None:
            # drop every session pin (and stop the publisher) so pool
            # leak checks see only live-slot references after shutdown
            self.session_store.close()
        # return every ledger byte this engine booked (idempotent — a
        # second stop() finds the books already empty)
        for owner, n in self._hbm_booked.items():
            self._hbm.book(owner, -n)
        self._hbm_booked = {}
        if self.paged is not None:
            self.paged.close()

    def is_alive(self) -> bool:
        """True while the engine can still make progress on submitted
        requests: not stopped, and — when a background loop was started
        — its thread is actually running. The API layer polls this so a
        dead engine surfaces as a 5xx instead of a client blocking
        forever on a token queue no one will ever fill."""
        if self._stop.is_set():
            return False
        return self._thread is None or self._thread.is_alive()

    # --- introspection -------------------------------------------------------

    def debug_kv(self) -> dict:
        """The ``GET /debug/kv`` payload: page-pool occupancy, sharing,
        fragmentation, refcount histogram, and per-slot block-table
        sizes (docs/paged-kv.md). Contiguous engines report their fixed
        reservation so the endpoint exists under either layout."""
        if self.paged is None:
            return {
                "layout": "contiguous",
                "max_slots": self.max_slots,
                "cache_len": self.cache_len,
                "kv_tokens_reserved": self.max_slots * self.cache_len,
                "ledger_account": "kv.contiguous",
                "kv_bytes": self._hbm_booked.get("kv.contiguous", 0),
            }
        snap = self.paged.debug_snapshot()
        live = 0
        for s in range(self.max_slots):
            # lock-free read from HTTP/scrape threads: the engine thread
            # pops slot_prefill concurrently, so membership-then-
            # subscript would be a TOCTOU KeyError — snapshot with .get
            st = self.slot_prefill.get(s)
            if st is not None:
                live += int(st["done"])
            elif self.slot_req[s] is not None:
                live += int(self.slot_len[s])
        mapped_tokens = snap["pages_slot_mapped"] * self.paged.page_size
        snap["live_tokens"] = live
        # internal fragmentation: allocated-but-unfilled slack of the
        # slot-mapped pages (tail of each slot's last page + reserved
        # decode headroom) — the waste the CONTIGUOUS layout suffers at
        # (cache_len - context) per slot, shrunk to < page_size here
        snap["fragmentation"] = (
            round(1.0 - live / mapped_tokens, 4) if mapped_tokens else 0.0)
        snap["preemptions"] = self.preemptions
        snap["rejected_too_large"] = self.rejected_too_large
        # satellite of ISSUE 9: with a draft model and an explicit pool
        # budget, the draft cache's contiguous bytes were deducted from
        # the page pool (token-equivalent) so admission can't over-admit
        snap["draft_kv_reserved_tokens"] = self.draft_kv_reserved_tokens
        # the same reservation in bytes, as the ledger books it (account
        # kv.draft) — /debug/kv and /debug/hbm agree on the draft tax
        snap["draft_kv_account_bytes"] = self._hbm_booked.get("kv.draft", 0)
        if self.prefix_cache is not None:
            snap["prefix_index_entries"] = self.prefix_cache.n_entries
        return snap

    def debug_requests(self, limit: int = 64) -> dict:
        """The ``GET /debug/requests`` payload: the recent-finished ring
        with each request's critical-path breakdown (CP_SEGMENTS). The
        engine segments partition the request's submit→finish wall
        clock (``host_gap`` is the residual); ``stream_flush`` is the
        API-side SSE tail, measured concurrently with decode and
        reported alongside, and may still be absent for a stream whose
        handler hasn't closed yet. Reads are lock-free snapshots of the
        GIL-atomic deque (HTTP threads vs. the finishing threads)."""
        now = time.monotonic()
        out = []
        for r in list(self.finished)[-limit:]:
            wall = (r.finish_time - r.submit_time
                    if r.finish_time is not None else None)
            out.append({
                "uid": r.uid,
                "finish_reason": r.finish_reason,
                "prompt_tokens": len(r.prompt_ids),
                "completion_tokens": r.n_generated,
                "cache": r.cache_outcome,
                "ttft_s": (round(r.ttft_s, 6)
                           if r.ttft_s is not None else None),
                "wall_s": round(wall, 6) if wall is not None else None,
                "age_s": (round(now - r.finish_time, 3)
                          if r.finish_time is not None else None),
                "segments": {k: round(v, 6) for k, v in r.cp.items()},
            })
        return {
            "capacity": self.finished.maxlen,
            "segments": list(CP_SEGMENTS),
            "critical_path_seconds_total":
                {k: round(v, 6) for k, v in
                 self.stats.critical_path_snapshot().items()},
            "finished": out,
        }

    def debug_sessions(self) -> dict:
        """The ``GET /debug/sessions`` payload (serve/sessions.py) —
        pinned conversations, turn/eviction/pull accounting. Exists
        under every configuration so the endpoint never 404s on a
        replica that happens to run without the store."""
        if self.session_store is None:
            return {"enabled": False}
        return self.session_store.debug_snapshot()

    def page_capacity_detail(self, prompt_tokens: int) -> dict:
        """Why a prompt 422s: the page math for the API error body."""
        from llm_in_practise_tpu.serve.paged_kv import pages_for

        P = self.paged.page_size
        return {
            "prompt_tokens": prompt_tokens,
            "page_size": P,
            "pages_needed": pages_for(prompt_tokens + 1, P),
            "pages_capacity": self.paged.pool.capacity,
        }

    # --- convenience ---------------------------------------------------------

    def generate(self, prompt_ids, params: SamplingParams | None = None,
                 *, adapter: str | None = None) -> list[int]:
        """Blocking single-request helper (drives steps if no thread runs)."""
        req = self.submit(prompt_ids, params, adapter=adapter)
        if self._thread is None:
            while self.step():
                pass
        return req.result()


def shard_params_for_serving(params, strategy, mesh):
    """Place model params for sharded serving (TP/FSDP over ``mesh``) —
    the loading step vLLM does per tensor-parallel rank, here one
    device_put against the strategy's NamedShardings.

    Packed quantized trees (Int8/Int4/NF4/AWQ leaves from
    ``quant/io.load_packed``) are detected and placed through
    :func:`~llm_in_practise_tpu.quant.sharding.quant_tree_shardings`
    with the SAME strategy rule table — each component array of a
    packed leaf gets the sharding the bf16 weight would have, respecting
    the format's internal blocking (ISSUE 10: int8 14B loads
    shard-parallel instead of failing fast at the CLI)."""
    from llm_in_practise_tpu.quant.sharding import (
        QUANT_LEAVES,
        shard_quant_tree,
    )

    is_quant = lambda x: isinstance(x, QUANT_LEAVES)  # noqa: E731
    if any(is_quant(leaf) for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=is_quant)):
        return shard_quant_tree(params, mesh, strategy.effective_rules())
    return jax.device_put(params, strategy.param_shardings(params, mesh))
