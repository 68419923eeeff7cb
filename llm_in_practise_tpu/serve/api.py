"""OpenAI-compatible HTTP server over the continuous-batching engine.

Parity with the reference's FastAPI server
(``Scripts/inference/07-deepseek1.5b-api-infr.py``):

- ``POST /v1/chat/completions`` — non-streaming (``:105-161``) **and** SSE
  streaming, which the reference stubs out with a 501 (``:110-112``); here it
  is implemented (chunked ``data:`` events + ``[DONE]``), closing that gap
  the reference defers to vLLM.
- prompt build from OpenAI messages (``:37-57``) — ChatML via
  :func:`llm_in_practise_tpu.data.sft.render_chatml` plus the generation
  prompt suffix.
- usage accounting (``:118-152``), ``GET /v1/models``, ``GET /health``.
- ``POST /v1/embeddings`` — mean-pooled hidden states (the embedding
  service the reference's semantic cache / RAG stack call out to).
- ``GET /metrics`` — Prometheus text exposition rendered by the unified
  registry (:mod:`llm_in_practise_tpu.obs.registry`): queue depth, running
  requests, bucketed TTFT/TPOT histograms — mirroring the PromQL table
  ``LLM_on_Kubernetes/Inference_Platfrom/README.md:1676-1692``; see
  docs/observability.md for the catalog.
- ``GET /debug/traces`` — the request-span ring
  (:mod:`llm_in_practise_tpu.obs.trace`): per-request spans for queue
  wait, admission, prefill chunks, decode, handoff publish/claim, and
  stream flush, correlated across the gateway and the disaggregated
  replicas by a ``traceparent``-propagated trace id.

Built on the stdlib ``ThreadingHTTPServer`` — the serving runtime carries no
web-framework dependency; each connection gets an OS thread, generation
throughput is owned by the engine's single background loop.
"""

from __future__ import annotations

import dataclasses
import html
import json
import sys
import threading
import time
from http.server import ThreadingHTTPServer

import numpy as np

from llm_in_practise_tpu.data.sft import IM_START, render_chatml
from llm_in_practise_tpu.data.stream_decode import StreamDecoder
from llm_in_practise_tpu.infer.sampling import SAMPLER_TIERS
from llm_in_practise_tpu.obs.hbm import (
    get_ledger,
    host_entry_bytes,
    register_hbm_ledger,
)
from llm_in_practise_tpu.obs.registry import Registry
from llm_in_practise_tpu.obs.trace import get_tracer, parse_traceparent
from llm_in_practise_tpu.serve import constrain, schemas
from llm_in_practise_tpu.serve.engine import (
    DRAIN_REASONS,
    InferenceEngine,
    SamplingParams,
)
from llm_in_practise_tpu.serve.http_util import (
    JsonHandler,
    serve_obs_get,
    serve_obs_post,
)


def build_prompt(messages) -> str:
    """OpenAI messages -> ChatML generation prompt (reference ``:37-57``)."""
    rendered = render_chatml([{"role": m.role, "content": m.content} for m in messages])
    return rendered + f"\n{IM_START}assistant\n"


class OpenAIServer:
    """Wires engine + tokenizer + HTTP. ``tokenizer`` needs ``encode``/``decode``."""

    def __init__(
        self,
        engine: InferenceEngine,
        tokenizer,
        *,
        model_name: str = "llm-in-practise-tpu",
        prompt_builder=build_prompt,
        adapters: dict[str, InferenceEngine] | None = None,
        role: str = "both",
        handoff=None,
        tracer=None,
    ):
        from llm_in_practise_tpu.obs.meter import HandoffMeter
        from llm_in_practise_tpu.serve.disagg import validate_roles

        self.engine = engine
        self.tokenizer = tokenizer
        self.model_name = model_name
        self.prompt_builder = prompt_builder
        # Disaggregated serving (serve/disagg.py): ``role`` gates the
        # internal handoff endpoint and labels the per-role latency
        # metrics; ``handoff`` is the store prefill publishes into and
        # decode claims from (shared pool server, or LocalHandoff for
        # single-process setups).
        self.role = validate_roles(role)
        # decode claims from the same store the engine publishes into
        # unless the caller splits them explicitly
        self.handoff = (handoff if handoff is not None
                        else getattr(engine, "handoff", None))
        self.handoff_meter = HandoffMeter()
        # vLLM ``--enable-lora --lora-modules name=path`` parity: additional
        # model names served from adapter-merged weights, picked by the
        # request's ``model`` field (see serve/adapters.py).
        self.adapters = dict(adapters or {})
        self._httpd: ThreadingHTTPServer | None = None
        # lazily jitted /v1/embeddings pooler, keyed per engine: adapter
        # engines may carry different modules, and a pooler closing over
        # one engine's model must never run another's params
        self._embed_fns: dict[int, object] = {}
        # request tracing (obs/trace.py): the API layer mints/extends the
        # per-request TraceContext; the engine parents its phase spans to
        # it. Default = the process tracer, so colocated components share
        # one ring and GET /debug/traces sees the whole request.
        self.tracer = tracer if tracer is not None else get_tracer()
        # Structured output (serve/constrain.py, ISSUE 12): the
        # per-server grammar compile cache plus the per-engine decoded
        # vocab it compiles against. Handler threads compile; repeat
        # schemas (the agent-loop shape) hit the cache.
        self._constraints = constrain.ConstraintCompiler()
        self._vocab_lock = threading.Lock()
        self._constraint_vocabs: dict[int, list[str]] = {}  # guarded-by: _vocab_lock
        self._structured_lock = threading.Lock()
        # llm_structured_requests_total{kind=…}; scrapes read the ints
        # lock-free (monotone counters — the spec_* convention)
        self._structured_counts = {"json_object": 0, "json_schema": 0,
                                   "tool_call": 0}  # guarded-by: _structured_lock
        # the SSE handlers' incremental detokenisation
        # (data/stream_decode.py): llm_stream_detokenize_seconds_total
        # and llm_stream_token_events_total{text=…}, per-stream sums a
        # handler books at its stream's end (scrapes read lock-free)
        self._stream_lock = threading.Lock()
        self._stream_detokenize_s = 0.0  # guarded-by: _stream_lock
        self._stream_token_events = {"yes": 0, "held": 0}  # guarded-by: _stream_lock
        # unified metrics registry (obs/registry.py): scrape-time
        # callbacks over the live engine/meter counters — the ONE
        # exposition renderer, replacing the hand-formatted text block
        self.registry = self._build_registry()

    # --- structured output ----------------------------------------------------

    def _constraint_vocab(self, engine: InferenceEngine) -> tuple[list, int]:
        """Decoded per-id vocab pieces for ``engine`` (cached). Raises
        :class:`~llm_in_practise_tpu.serve.constrain.ConstraintError`
        when the model exposes no vocab size (structured output is then
        a 422 — the server cannot promise schema conformance)."""
        key = id(engine)
        with self._vocab_lock:
            got = self._constraint_vocabs.get(key)
        if got is None:
            vs = getattr(getattr(engine.model, "config", None),
                         "vocab_size", None)
            if vs is None:
                raise constrain.ConstraintError(
                    "this model exposes no vocab_size; structured "
                    "output is unavailable")
            got = constrain.vocab_strings(self.tokenizer, int(vs))
            with self._vocab_lock:
                self._constraint_vocabs[key] = got
        return got, key

    def _compile_constraint(self, engine: InferenceEngine,
                            req: "schemas.ChatCompletionRequest"):
        """Request fields → shared compiled automaton (or None). Raises
        ConstraintError on invalid/unsupported specs (HTTP 422)."""
        rf_type = (req.response_format or {}).get("type")
        if (rf_type in (None, "text")
                and req.tool_choice in (None, "auto", "none")):
            # unconstrained request (the SDK default response_format
            # {"type": "text"} included): never touch the vocab cache
            # — a model without vocab_size must still serve plain chat
            return None
        vocab, vocab_key = self._constraint_vocab(engine)
        return self._constraints.get(
            response_format=req.response_format, tools=req.tools,
            tool_choice=req.tool_choice, vocab=vocab,
            vocab_key=vocab_key, eos_id=engine.eos_id)

    def _note_structured(self, kind: str) -> None:
        with self._structured_lock:
            self._structured_counts[kind] = (
                self._structured_counts.get(kind, 0) + 1)

    def _note_stream_decode(self, seconds: float, n_text: int,
                            n_held: int) -> None:
        """A finished stream's detokeniser wall and token events (a
        handler thread, once a stream)."""
        with self._stream_lock:
            self._stream_detokenize_s += seconds
            self._stream_token_events["yes"] += n_text
            self._stream_token_events["held"] += n_held

    def engine_for(self, model: str | None) -> InferenceEngine | None:
        if model in (None, "", self.model_name):
            return self.engine
        return self.adapters.get(model)

    # --- request handling ----------------------------------------------------

    def handle_embeddings(self, body: dict, send_json):
        """``POST /v1/embeddings`` — OpenAI embeddings schema over
        mean-pooled final hidden states (``return_hidden``). This is the
        in-tree counterpart of the embedding service the reference's
        semantic cache and RAG stack call out to."""
        import jax
        import jax.numpy as jnp

        inputs = body.get("input")
        if isinstance(inputs, str):
            inputs = [inputs]
        def _ok(x):
            if isinstance(x, str):
                return True
            return (isinstance(x, list)
                    and all(isinstance(t, int) for t in x))

        if not isinstance(inputs, list) or not inputs or not all(
                _ok(x) for x in inputs):
            return send_json(422, {"error": {
                "message": "input must be a string, list of strings, or "
                           "list of integer token lists",
                "type": "invalid_request_error"}})
        engine = self.engine_for(body.get("model"))
        if engine is None:
            return send_json(404, {"error": {
                "message": f"model {body.get('model')!r} not found",
                "type": "invalid_request_error"}})

        embed_fn = self._embed_fns.get(id(engine))
        if embed_fn is None:
            model = engine.model

            def embed(params, ids, length):
                h = model.apply({"params": params}, ids,
                                deterministic=True, return_hidden=True)
                mask = (jnp.arange(ids.shape[1]) < length)[None, :, None]
                pooled = (h * mask).sum(axis=1) / jnp.maximum(length, 1)
                return pooled[0].astype(jnp.float32)

            # lazily built ONCE per engine and cached in self._embed_fns
            # (checked above) — later requests reuse the compiled pooler
            embed_fn = self._embed_fns[id(engine)] = jax.jit(embed)  # graftlint: disable=jit-in-handler

        data, total = [], 0
        for i, item in enumerate(inputs):
            ids = (list(item) if isinstance(item, list)
                   else self.tokenizer.encode(item))
            ids = ids[: engine.cache_len] or [0]
            total += len(ids)
            bucket = engine._bucket_for(len(ids))  # reuse prefill buckets
            padded = np.zeros((1, bucket), np.int32)
            padded[0, : len(ids)] = ids
            try:
                vec = np.asarray(embed_fn(
                    engine.params, jnp.asarray(padded),
                    jnp.asarray(len(ids), jnp.int32)), np.float64)
            except TypeError:
                return send_json(501, {"error": {
                    "message": "this model does not expose hidden states "
                               "(return_hidden)",
                    "type": "unsupported_error"}})
            norm = float(np.linalg.norm(vec)) or 1.0
            data.append({"object": "embedding", "index": i,
                         "embedding": (vec / norm).tolist()})
        return send_json(200, {
            "object": "list",
            "data": data,
            "model": body.get("model") or self.model_name,
            "usage": {"prompt_tokens": total, "total_tokens": total},
        })

    def handle_prefill(self, body: dict, send_json, trace=None):
        """``POST /internal/handoff/prefill`` — the prefill half of
        disaggregated serving (serve/disagg.py). Runs prefill only,
        publishes the prompt KV into the handoff store, and returns the
        handoff id the router passes to a decode replica via
        ``kv_transfer_params``. Internal: only the gateway calls this
        (it is absent on pure-decode replicas). ``trace``: the gateway's
        TraceContext (from the ``traceparent`` header) — the prefill
        phase's engine spans join the request's trace."""
        from llm_in_practise_tpu.serve.disagg import new_handoff_id

        if self.role == "decode":
            return send_json(501, {"error": {
                "message": "decode replicas do not prefill for handoff",
                "type": "unsupported_error"}})
        try:
            req = schemas.ChatCompletionRequest.from_dict(
                dict(body, model=body.get("model") or self.model_name))
        except schemas.ValidationError as e:
            return send_json(422, {"error": {
                "message": str(e), "type": "invalid_request_error"}})
        engine = self.engine_for(req.model)
        if engine is None:
            return send_json(404, {"error": {
                "message": f"model {req.model!r} not found",
                "type": "invalid_request_error"}})
        if getattr(engine, "handoff", None) is None:
            # per-MODEL capability: an adapter engine without its own
            # handoff store must 501 here, not burn a prefill whose
            # publish is guaranteed to fail (the gateway treats 501 as
            # "serve undisaggregated", not as an upstream failure)
            return send_json(501, {"error": {
                "message": f"model {req.model!r} has no handoff store "
                           "on this replica",
                "type": "unsupported_error"}})
        prompt_ids = self.tokenizer.encode(self.prompt_builder(req.messages))
        hid = new_handoff_id()
        span = self.tracer.start_span("api.prefill", parent=trace,
                                      model=req.model, handoff_id=hid)
        from llm_in_practise_tpu.serve.engine import EngineDeadError

        outcome = "error"  # the span's finish_reason mirrors the HTTP
        # outcome (handle.finish_reason is None on engine death and
        # partial on sheds — /debug/traces must say what the caller saw)
        try:
            # inside the span's try: a submit failure (bad prompt, dead
            # engine thread) must end the span as an error, not leak it
            # unrecorded while do_POST answers 500
            handle = engine.submit(prompt_ids, SamplingParams(max_tokens=1),
                                   handoff_id=hid, trace=span.context())
            try:
                handle.result()  # drains to _FINISH; prefill emits no
                # tokens
            except EngineDeadError:
                outcome = "engine_dead"
                return send_json(503, {"error": {
                    "message": "engine is not running",
                    "type": "internal_error",
                    "code": "engine_dead"}})
            if handle.finish_reason == "too_large":
                outcome = "too_large"
                detail = engine.page_capacity_detail(len(prompt_ids))
                return send_json(422, {"error": {
                    "message": (
                        "prompt can never fit this replica's KV page "
                        f"pool ({detail['pages_needed']} pages needed "
                        f"vs {detail['pages_capacity']} capacity)"),
                    "type": "invalid_request_error",
                    "code": "prompt_too_large",
                    "detail": detail}})
            if handle.finish_reason == "queue_full":
                outcome = "queue_full"
                return send_json(429, {"error": {
                    "message": "prefill queue full — retry another replica",
                    "type": "rate_limit_error", "code": "queue_full"}})
            if handle.finish_reason != "handoff":
                outcome = "handoff_failed"
                return send_json(503, {"error": {
                    "message": "KV publish failed (pool unreachable or "
                               "handoff budget exhausted) — serve this "
                               "request undisaggregated",
                    "type": "internal_error", "code": "handoff_failed"}})
            outcome = "handoff"
            return send_json(200, {
                "handoff_id": hid,
                "prompt_tokens": len(handle.prompt_ids),
                "model": req.model,
            })
        finally:
            span.end(finish_reason=outcome)

    def handle_chat(self, body: dict, send_json, send_stream, trace=None,
                    session_id: str | None = None):
        # the body has been read (and JSON-decoded): the front end's
        # api_pre_submit overlay starts here
        t_body = time.monotonic()
        try:
            req = schemas.ChatCompletionRequest.from_dict(body)
        except schemas.ValidationError as e:
            return send_json(422, {"error": {"message": str(e), "type": "invalid_request_error"}})
        # session-native serving (serve/sessions.py, ISSUE 17): the
        # X-Session-ID header wins; the body field covers clients that
        # can't set headers. Ignored entirely on engines without a store.
        if session_id is None and isinstance(body.get("session_id"), str):
            session_id = body["session_id"]

        engine = self.engine_for(req.model)
        if engine is None:
            return send_json(404, {"error": {
                "message": f"model {req.model!r} not found; have "
                           f"{[self.model_name, *self.adapters]}",
                "type": "invalid_request_error",
            }})
        prompt = self.prompt_builder(req.messages)
        prompt_ids = self.tokenizer.encode(prompt)
        params = SamplingParams(
            temperature=req.temperature,
            top_k=req.top_k,
            top_p=req.top_p,
            greedy=req.temperature == 0.0,
            max_tokens=req.max_tokens,
        )
        # structured output (serve/constrain.py): compile the grammar
        # the engine will enforce in-dispatch; an invalid/unsupported
        # schema is a client error — 422 BEFORE any engine work
        constraint_kind = None
        try:
            automaton = self._compile_constraint(engine, req)
        except constrain.ConstraintError as e:
            return send_json(422, {"error": {
                "message": str(e), "type": "invalid_request_error",
                "code": "invalid_constraint"}})
        if (automaton is not None
                and getattr(engine, "block", None) is not None):
            return send_json(422, {"error": {
                "message": "structured output is not supported by a "
                           "block-diffusion model (a block reveals "
                           "positions out of order)",
                "type": "invalid_request_error",
                "code": "invalid_constraint"}})
        if automaton is not None:
            constraint_kind = automaton.kind
            self._note_structured(constraint_kind)
            params = dataclasses.replace(params, constraint=automaton)
        # disaggregated serving: a router that already prefilled this
        # prompt elsewhere points us at the pinned KV entry; a lost claim
        # (expired/claimed/unreachable) degrades to local prefill — the
        # engine counts it, the stream is correct either way
        kv_entry = None
        xfer = body.get("kv_transfer_params")
        # trace continuity: the traceparent header is primary; the
        # handoff body's ride-along copy covers intermediaries that
        # strip headers (the prefill→decode hop must stay one trace)
        ctx = trace
        if ctx is None and isinstance(xfer, dict) and xfer.get("trace"):
            ctx = parse_traceparent(str(xfer["trace"]))
        span = self.tracer.start_span(
            "api.chat", parent=ctx, model=req.model or self.model_name,
            stream=bool(req.stream),
            handed_off=bool(isinstance(xfer, dict)
                            and xfer.get("handoff_id")))
        try:
            if isinstance(xfer, dict) and xfer.get("handoff_id"):
                # claim from the target MODEL's store when it has one (each
                # model's handoff namespace is distinct — base vs adapters),
                # else the server-level store
                store = getattr(engine, "handoff", None) or self.handoff
                with self.tracer.span("handoff.claim", parent=span,
                                      handoff_id=str(xfer["handoff_id"])) as cs:
                    if store is not None:
                        kv_entry = store.claim(str(xfer["handoff_id"]))
                    cs.set(found=kv_entry is not None)
                self.handoff_meter.claim_outcome(kv_entry is not None)
                if kv_entry is not None:
                    # claim-side staging: the host entry lives only
                    # until admission scatters it — shorter than any
                    # scrape, so pulse (peak), don't book (level)
                    get_ledger().pulse("handoff_staging",
                                       host_entry_bytes(kv_entry))
            # session fleet miss path (serve/sessions.py): an unknown
            # session on this replica (ring rebalance / replica death
            # remapped it here) pulls its KV from the pool's handoff
            # namespace on THIS thread; a lost entry just means a local
            # re-prefill — counted, never an error
            sess_store = getattr(engine, "session_store", None)
            if session_id is not None and sess_store is not None \
                    and not sess_store.known(session_id):
                pool = getattr(engine, "handoff", None) or self.handoff
                if pool is not None:
                    from llm_in_practise_tpu.serve.sessions import (
                        session_hid,
                    )

                    with self.tracer.span("session.pull", parent=span,
                                          session=session_id) as ps:
                        pulled = pool.claim(session_hid(session_id))
                        ps.set(found=pulled is not None)
                    if pulled is not None:
                        sess_store.adopt(session_id, pulled)
                        get_ledger().pulse("handoff_staging",
                                           host_entry_bytes(pulled))
                    else:
                        sess_store.note_lost()
            handle = engine.submit(prompt_ids, params, kv_entry=kv_entry,
                                   trace=span.context(),
                                   session_id=session_id)
            # front-end instants: plain float attributes, which the
            # engine's finish funnel turns into the api_* overlays of
            # the request's critical path (never a cp insert from here)
            handle.api_body_time = t_body
            api_times = {"api_pre_submit_s": handle.submit_time - t_body}
            req_id = schemas.completion_id()

            def queue_full_429(message):
                # one shape for every shed path (max_queue at submit AND the
                # later queue_timeout sheds): the gateway's retry policy
                # keys on the status + code. A shed request never used its
                # claimed (claim-once) handoff entry, so re-pin it first —
                # the gateway's retry against another decode upstream then
                # claims it instead of paying prefill again, exactly when
                # the pool is saturated.
                if kv_entry is not None:
                    try:
                        store.publish(str(xfer["handoff_id"]), kv_entry)
                    except Exception as e:  # noqa: BLE001 — the retry will
                        # degrade to a local prefill; leave a trace of where
                        # the entry went (silent loss is undebuggable)
                        self.handoff_meter.note_repin(False)
                        from llm_in_practise_tpu.obs.logging import get_logger

                        get_logger("serve.api").warning(
                            "could not re-pin shed handoff entry %s (%s: "
                            "%s); the retry will re-prefill",
                            xfer["handoff_id"], type(e).__name__, e)
                    else:
                        self.handoff_meter.note_repin(True)
                span.end(status=429, finish_reason="queue_full")
                return send_json(429, {"error": {
                    "message": message + " — retry later or against "
                               "another replica",
                    "type": "rate_limit_error",
                    "code": "queue_full",
                }})

            # paged KV admission: a prompt that can NEVER fit the page
            # pool (prompt pages + 1 > capacity) is a client error, not
            # load — 422 with the page math, synchronously at submit,
            # instead of aging into a generic queue-full 429
            if handle.finish_reason == "too_large":
                detail = engine.page_capacity_detail(len(prompt_ids))
                span.end(status=422, finish_reason="too_large")
                return send_json(422, {"error": {
                    "message": (
                        "prompt can never fit this replica's KV page "
                        f"pool: {detail['pages_needed']} pages needed "
                        f"(prompt {detail['prompt_tokens']} tokens + 1 "
                        f"at page_size {detail['page_size']}) vs "
                        f"{detail['pages_capacity']} pages capacity"),
                    "type": "invalid_request_error",
                    "code": "prompt_too_large",
                    "detail": detail,
                }})
            # admission control: a max_queue rejection is synchronous at
            # submit — return 429 before any stream starts (vLLM/ingress
            # backpressure parity; the gateway's retry policy keys on 429).
            # A queue_timeout shed happens later and surfaces through the
            # normal finish path below.
            if handle.finish_reason == "queue_full":
                return queue_full_429("engine queue full")

            from llm_in_practise_tpu.serve.engine import _FINISH, EngineDeadError

            def engine_dead_503():
                span.end(status=503, finish_reason="engine_dead")
                return send_json(503, {"error": {
                    "message": "engine is not running — request cannot be "
                               "served; retry against another replica",
                    "type": "internal_error",
                    "code": "engine_dead",
                }})

            if req.stream:
                # hold the 200 until the request survives admission: a
                # queue_timeout shed must surface as a retriable 429, not a
                # silently empty SSE stream. Blocks until the first token
                # (or finish) — exactly when the first data chunk could be
                # sent anyway, so client-visible TTFT is unchanged. The
                # wait is liveness-bounded (Request.next_item): a dead
                # engine is a 503, not a client hanging with no headers.
                try:
                    first = handle.next_item()
                except EngineDeadError:
                    return engine_dead_503()
                if first is _FINISH and handle.finish_reason == "queue_full":
                    return queue_full_429("request timed out waiting for a slot")

                def chunks():
                    # flush_s sums only the yield→resume gaps (the
                    # consumer formatting + writing each SSE chunk) —
                    # engine decode waits happen inside next_item() and
                    # must NOT count, or this span would shadow
                    # engine.decode in the per-phase breakdown
                    flush_s = 0.0
                    n_chunks = 0
                    # the detokeniser's wall and the token events by
                    # outcome (text sent / held back), summed here and
                    # booked once, at the stream's end
                    dec = StreamDecoder(self.tokenizer)
                    detok_s = 0.0
                    n_text = n_held = 0
                    try:
                        t = time.monotonic()
                        yield schemas.chat_completion_chunk(
                            req_id=req_id, model=req.model, delta=None
                        )
                        # resumed: the first event is with the socket
                        now = time.monotonic()
                        handle.api_first_flush_time = now
                        if handle.first_token_time is not None:
                            api_times["api_first_flush_s"] = (
                                now - handle.first_token_time)
                        flush_s += now - t
                        n_chunks += 1

                        def stream_toks():
                            # mid-stream liveness: headers are out, so a dead
                            # engine propagates EngineDeadError into _sse's
                            # in-band error event instead of freezing the
                            # stream
                            tok = first
                            while tok is not _FINISH:
                                yield tok
                                tok = handle.next_item()
                        for tok in stream_toks():
                            # a short window through decode, not the
                            # stream's whole list: an event costs the
                            # same at any length, and carries whole
                            # characters (data/stream_decode.py)
                            t0 = time.monotonic()
                            delta = dec.push(tok)
                            t = time.monotonic()
                            detok_s += t - t0
                            if delta:
                                n_text += 1
                                yield schemas.chat_completion_chunk(
                                    req_id=req_id, model=req.model, delta=delta
                                )
                                flush_s += time.monotonic() - t
                                n_chunks += 1
                            else:
                                n_held += 1
                        # what the stream ended on without completing
                        # (an open character, as decode renders it)
                        t0 = time.monotonic()
                        delta = dec.finish()
                        t = time.monotonic()
                        detok_s += t - t0
                        if delta:
                            yield schemas.chat_completion_chunk(
                                req_id=req_id, model=req.model, delta=delta
                            )
                            n_chunks += 1
                        yield schemas.chat_completion_chunk(
                            req_id=req_id, model=req.model, delta=None,
                            finish_reason=handle.finish_reason or "stop",
                        )
                        flush_s += time.monotonic() - t
                        n_chunks += 1
                    finally:
                        # SSE write loop = the stream-flush phase; its span
                        # closes the trace's client-visible tail
                        self.tracer.record(
                            "api.stream_flush", span,
                            duration_s=flush_s,
                            chunks=n_chunks,
                            detokenize_s=detok_s, held=n_held)
                        exc = sys.exc_info()[1]
                        # critical-path: the stream tail joins the
                        # request's /debug/requests breakdown and the
                        # aggregate counter. Per-request cp is written
                        # ONLY on a clean stream end: the generator then
                        # saw _FINISH, which the engine releases after
                        # its last cp write (_record_finished), so this
                        # thread owns the dict. A disconnect
                        # (GeneratorExit) mid-decode would race the
                        # engine's writers — skip cp there (the debug
                        # view documents stream_flush as possibly
                        # absent) and book the aggregate only, which
                        # goes through note_stream_flush ONLY —
                        # _record_finished skips this segment. The
                        # write is still a dict SWAP, not an insert:
                        # /debug/requests readers may be iterating the
                        # old object.
                        if exc is None:
                            handle.cp = {
                                **handle.cp,
                                "stream_flush":
                                    handle.cp.get("stream_flush", 0.0)
                                    + flush_s,
                            }
                        engine.stats.note_stream_flush(flush_s)
                        self._note_stream_decode(detok_s, n_text, n_held)
                        # headers already went out as 200, but the span
                        # must say how the stream actually ended: a mid-
                        # flight engine death surfaces as an in-band
                        # error event, a client disconnect as
                        # GeneratorExit — neither is a clean "stop"
                        if exc is None:
                            span.end(status=200,
                                     finish_reason=handle.finish_reason
                                     or "stop", **api_times)
                        elif isinstance(exc, GeneratorExit):
                            span.end(status=200,
                                     finish_reason="client_disconnect",
                                     chunks_sent=n_chunks, **api_times)
                        else:
                            span.end(status=200,
                                     finish_reason="stream_error",
                                     error=type(exc).__name__,
                                     chunks_sent=n_chunks, **api_times)
                return send_stream(chunks())

            try:
                out_ids = handle.result()
            except EngineDeadError:
                return engine_dead_503()
            if handle.finish_reason == "queue_full":  # queue_timeout shed
                return queue_full_429("request timed out waiting for a slot")
            text = self.tokenizer.decode(out_ids)
            usage = schemas.Usage(len(prompt_ids), len(out_ids))
            tool_calls = None
            if (constraint_kind == "tool_call"
                    and handle.finish_reason == "stop"):
                # the grammar guarantees {"name": …, "arguments": {…}};
                # re-shape it into the OpenAI tool_calls wire format
                # (a "length"-truncated call stays raw content — the
                # client sees exactly what was generated)
                try:
                    call = json.loads(text)
                    tool_calls = [schemas.tool_call_entry(
                        call["name"],
                        json.dumps(call["arguments"],
                                   separators=(",", ":")))]
                except (ValueError, KeyError, TypeError):
                    tool_calls = None
            span.end(status=200, finish_reason=handle.finish_reason or "stop",
                     completion_tokens=len(out_ids), **api_times)
            return send_json(200, schemas.chat_completion_response(
                req_id=req_id, model=req.model, text=text,
                finish_reason=handle.finish_reason or "stop", usage=usage,
                tool_calls=tool_calls,
            ))
        except BaseException as e:
            # a handler exception (kv upload on submit, tokenizer
            # decode, ...) surfaces as do_POST's catch-all 500 — the
            # span must record the failure, not leak unrecorded
            span.end(status=500, finish_reason="error",
                     error=type(e).__name__)
            raise

    def _build_registry(self) -> Registry:
        """Every family reads the live engine/meter counters at scrape
        time — no double bookkeeping, one canonical renderer (TYPE
        header per family, strict label escaping; pinned by the
        exposition-parser tests)."""
        reg = Registry()
        eng = self.engine
        s = eng.stats
        # build identity (obs/buildinfo.py): the fleet collector keys
        # its per-version scoreboard and canary verdict on these labels
        from llm_in_practise_tpu.obs.buildinfo import register_build_info

        register_build_info(reg, {
            "server": "api",
            "model": self.model_name,
            "role": self.role,
            "max_slots": eng.max_slots,
            "cache_len": eng.cache_len,
            "kv_layout": "paged" if eng.paged is not None else "dense",
            "speculative_k": getattr(eng, "speculative_k", 0),
            "adapters": sorted(self.adapters),
        })
        reg.counter_func("llm_requests_total",
                         lambda: s.requests_total,
                         "requests submitted to the engine")
        reg.counter_func("llm_tokens_generated_total",
                         lambda: s.tokens_generated_total,
                         "output tokens emitted")
        reg.gauge_func("llm_num_requests_waiting", lambda: s.queue_depth,
                       "requests queued for a slot")
        reg.gauge_func("llm_num_requests_running", lambda: s.active_slots,
                       "requests occupying slots")
        reg.counter_func("llm_requests_shed_total",
                         lambda: s.requests_shed,
                         "requests shed by admission control")
        # dispatch accounting (docs/perf.md Findings 5/16/17): on a
        # dispatch-taxed host, dispatches/step IS the latency model —
        # the fused mixed step's win shows up here as ~1.0 under
        # simultaneous prefill+decode (it was 2 before)
        dm = eng.dispatch_meter
        reg.counter_func("llm_dispatches_total", lambda: dm.total,
                         "jitted engine-program launches")
        reg.gauge_func("llm_dispatches_per_step",
                       lambda: dm.mean_per_step,
                       "rolling mean dispatches per engine step")
        reg.counter_func("llm_mixed_blocks_total",
                         lambda: eng.mixed_blocks,
                         "fused prefill+decode dispatches")
        reg.counter_func("llm_prefill_chunk_rows_total",
                         lambda: eng.prefill_chunk_rows,
                         "prompt rows that advanced one prefill chunk")
        reg.counter_func("llm_prefill_chunk_row_slots_total",
                         lambda: eng.prefill_chunk_row_slots,
                         "rows the device computed for those chunks "
                         "(the contiguous slot plane's idle rows included)")
        reg.counter_func(
            "llm_first_tokens_total",
            lambda: [({"path": p}, n) for p, n in eng.first_tokens.items()],
            "prompts that finished in a chunk, fused mixed or suffix "
            "program, by where their first token was sampled: in that "
            "program, or by the host fallback from its logits (a "
            "grammar's start state, a resumed stream, the contiguous "
            "layout)")
        blk = getattr(eng, "block", None)
        if blk is not None:
            # block-diffusion decoding (serve/block_step.py): passes are
            # not tokens, so each has a counter of its own; the expert
            # load is what the block program's routing output counted
            for name, attr, doc in (
                    ("llm_block_passes_total", "passes",
                     "dispatches of the block-diffusion pass program"),
                    ("llm_block_row_passes_total", "row_passes",
                     "rows that really advanced in those passes"),
                    ("llm_block_row_passes_discarded_total",
                     "row_passes_discarded",
                     "rows a pass issued ahead ran past their stream's "
                     "EOS, dropped when it was read"),
                    ("llm_blocks_committed_total", "blocks_committed",
                     "blocks whose K/V were stored and tokens streamed"),
                    ("llm_block_tokens_committed_total", "tokens_committed",
                     "tokens streamed out of committed blocks")):
                reg.counter_func(name,
                                 lambda a=attr: getattr(blk, a), doc)
        load = getattr(eng, "routing_load", None)
        if load is not None:
            # expert load of a routed model (serve/step_stats.py): what
            # the block passes' routing output counted, or what the
            # decode / chunk / mixed programs of a model that holds a
            # share of its experts counted on the device
            for name, attr, doc in (
                    ("llm_moe_layer_passes_total", "layer_passes",
                     "runs of one routed layer over one batch of tokens "
                     "(a block pass, a decode step, a chunk row), summed "
                     "over layers"),
                    ("llm_moe_assignments_total", "assignments",
                     "(token, expert) pairs computed by the experts held "
                     "here, idle rows of the plane included"),
                    ("llm_moe_experts_touched_total", "experts_touched",
                     "distinct held experts that received a token, summed "
                     "over layers and passes"),
                    ("llm_moe_max_expert_load_total", "max_load",
                     "the busiest held expert's assignments, summed over "
                     "layers and passes"),
                    ("llm_moe_mean_expert_load_total", "mean_load",
                     "assignments / experts held, summed over layers and "
                     "passes (max / mean = the routing's imbalance)")):
                reg.counter_func(name,
                                 lambda a=attr: getattr(load, a), doc)
        stats = getattr(eng, "step_stats", None)
        if stats is not None:
            # one attended / view pair, under the family name that fits
            # the model's cache: llm_latent_* or, for a model whose window
            # layers are held by slot beside its paged global layers,
            # llm_global_* and the rings' rows (serve/step_stats.py)
            families = [
                (stats.attended_key,
                 "cache rows the decode steps' attention over the paged "
                 "layers needed (each active row's true length)"),
                (stats.view_key,
                 "cache rows those steps' gathered views held (slots x "
                 "pow2 width): attended / view is the share read for "
                 "something"),
                ("prefill_chunk_tokens",
                 "real prompt tokens the chunk and mixed programs' rows "
                 "advanced"),
                ("prefill_chunk_capacity",
                 "those rows' trips x the chunk's width: tokens / "
                 "capacity is how full a chunk-wide trip ran")]
            if stats.ring_rows:
                families.append((
                    "window_rows_attended",
                    "ring rows the decode steps' window layers attended "
                    "(each active row's min(length, window))"))
                families.append((
                    "window_ring_rows_read",
                    "ring rows those layers read: every slot's whole "
                    "ring, idle slots too"))
            if stats.page_block and not stats.shared:
                families.append((
                    "global_pages_read",
                    "pages of the paged layers (a model's global layers, a "
                    "latent model's every layer) the decode steps' readers "
                    "copied where they lie (a model that declares "
                    "reads_pages: no gathered view): live rows' lengths "
                    "up to whole blocks x the layers that read them"))
            if stats.state:
                # recurrent layers, under the state's own name (ssm: a
                # selective scan's; conv: a short convolution's tail),
                # booked by the host
                families += [
                    (stats.advanced_key,
                     "decode-plane rows x steps whose recurrent state "
                     "moved (the live rows)"),
                    (stats.held_key,
                     "decode-plane rows x steps the state was held for: "
                     "every slot, idle and mid-prefill ones unchanged")]
            if stats.shared:
                # a cross-decoder (models/phi4flash.py), booked by the host
                families += [
                    ("ssm_scan_tokens",
                     "real prompt positions the chunk rows' recurrent "
                     "layers scanned (a layer each)"),
                    ("self_decoder_rows",
                     "positions that passed the self-decoder: chunk "
                     "tokens and the decode plane's rows"),
                    ("cross_decoder_rows",
                     "positions that passed the cross-decoder: the "
                     "decode plane's rows and ONE of each prompt, its "
                     "last"),
                    ("cross_decoder_prefill_rows",
                     "prompt positions that passed the cross-decoder "
                     "(one a prompt: a chunk's other positions skip it)"),
                    ("shared_kv_rows_attended",
                     "rows of the one paged layer's view the decode "
                     "steps' readers attended: true lengths x the layers "
                     "that read it"),
                    ("shared_kv_pages_read",
                     "pages of the one paged layer the decode steps' "
                     "readers copied where they lie: live rows' lengths "
                     "up to whole blocks x the layers that read it (0 "
                     "while the layer is gathered into a view)")]
            for key, doc in families:
                reg.counter_func(f"llm_{key}_total",
                                 lambda a=key: getattr(stats, a), doc)
        if getattr(eng, "paged", None) is not None:
            reg.gauge_func(
                "llm_kv_row_bytes", lambda: eng.paged.row_bytes,
                "pool bytes one token position holds over all layers, as "
                "STORED (k and v heads; or one latent row a layer, padded "
                "to whole lane tiles where the pool is stored by pages)")
            reg.gauge_func(
                "llm_kv_global_pool_bytes", lambda: eng.paged.pool_bytes,
                "bytes of the page pools: the layers whose cache grows "
                "with the context (ledger account kv_pool.pages)")
            reg.gauge_func(
                "llm_kv_window_state_bytes",
                lambda: eng.paged.slot_state_bytes,
                "bytes of the layers held by slot (a sliding-window "
                "layer's ring: bounded whatever the context; ledger "
                "account kv.window_state); 0 for a model without them")
            reg.gauge_func(
                "llm_kv_recurrent_state_bytes",
                lambda: eng.paged.recurrent_state_bytes,
                "bytes, of llm_kv_window_state_bytes, that are a "
                "recurrent layer's state: replaced at every position, "
                "never appended, in a dtype of its own; 0 for a model "
                "without such layers")
            reg.counter_func(
                "llm_kv_view_pages_gathered_total",
                lambda: eng.view_pages_gathered,
                "pages the paged programs' views gathered whole (a pool "
                "stored by pages: a latent cache); 0 for a flat pool, "
                "whose views gather rows")
        # device plane (obs/cost.py + DispatchMeter.note_phase): live
        # per-phase MFU / HBM-bandwidth-utilization / tokens-per-
        # dispatch — the compute-vs-bandwidth-bound dial. Phases appear
        # as they first dispatch; without a cost model (uncovered model
        # family) the utilization gauges render no samples but the
        # token gauge still does.
        def _phase_gauge(field):
            def read():
                return [({"phase": phase}, snap[field])
                        for phase, snap in dm.phase_snapshot().items()
                        if snap.get(field) is not None]
            return read

        reg.gauge_func("llm_dispatch_mfu", _phase_gauge("mfu"),
                       "rolling per-dispatch model FLOP utilization "
                       "(useful FLOPs / wall time / chip peak)")
        reg.gauge_func("llm_dispatch_hbm_bw_util",
                       _phase_gauge("hbm_bw_util"),
                       "rolling per-dispatch HBM bandwidth utilization "
                       "(weights + KV traffic / wall time / peak BW)")
        reg.gauge_func("llm_dispatch_tokens_per_dispatch",
                       _phase_gauge("tokens_per_dispatch"),
                       "rolling mean tokens processed per dispatch")
        # compile telemetry (obs/prof.py CompileMeter over every jitted
        # engine program): a serving-time recompile is a latency cliff
        # this pair turns into an alertable counter
        cmeter = eng.compile_meter
        reg.counter_func("llm_compile_events_total",
                         lambda: cmeter.compile_events,
                         "jit executable-cache misses paid by the "
                         "serving thread")
        reg.counter_func("llm_compile_seconds_total",
                         lambda: cmeter.compile_seconds,
                         "cumulative seconds stalled in jit "
                         "trace/compile (persistent-cache loads "
                         "included)")
        # device memory telemetry — read LIVE at scrape; backends that
        # report no memory_stats (the CPU backend) render the
        # family with no samples (fail-open)
        def _hbm():
            from llm_in_practise_tpu.obs.cost import device_memory_stats

            stats = device_memory_stats()
            return [({"kind": kind}, value)
                    for kind, value in (("in_use",
                                         stats.get("bytes_in_use")),
                                        ("peak",
                                         stats.get("peak_bytes_in_use")),
                                        ("limit",
                                         stats.get("bytes_limit")))
                    if value is not None]

        reg.gauge_func("llm_device_hbm_bytes", _hbm,
                       "device memory from device.memory_stats(): "
                       "bytes in use / peak / limit")
        # HBM ownership ledger (obs/hbm.py, ISSUE 19): per-owner
        # attribution of the bytes the aggregate family above only
        # totals, plus the reconciliation residual between the two
        register_hbm_ledger(reg)
        # tensor-parallel plane (docs/serving-tp.md): the mesh extent
        # and the analytic per-chip collective attribution — wire bytes
        # of the row-parallel activation all-reduces and the
        # lower-bound seconds they cost at datasheet ICI bandwidth.
        # Registered unconditionally (zeros at tp=1) so dashboards and
        # the metric-docs census see one stable family set.
        reg.gauge_func("llm_tp_size", lambda: eng.tp,
                       "tensor-parallel extent of the serving mesh's "
                       "model axis (1 = single chip)")
        reg.counter_func("llm_collective_bytes_total",
                         lambda: eng.collective_bytes_total,
                         "analytic per-chip ICI wire bytes of the "
                         "row-parallel activation all-reduces "
                         "(halved under --tp-quantized-collectives)")
        reg.counter_func("llm_collective_seconds_total",
                         lambda: eng.collective_seconds_total,
                         "analytic lower-bound seconds those bytes "
                         "cost at datasheet ICI bandwidth (XLA "
                         "overlaps collectives with compute)")
        # SLO goodput (obs/meter.py GoodputMeter): tokens priced by
        # whether their request met the TTFT/TPOT SLOs; zero until
        # thresholds are configured (engine ttft_slo_s/tpot_slo_s)
        from llm_in_practise_tpu.obs.meter import register_goodput

        register_goodput(reg, s.goodput)
        # per-role latency labels (disaggregated serving): a prefill
        # replica's "TTFT" is KV-ready time, a decode replica's TPOT is
        # the interference-free number the split exists for. Plain
        # (unlabeled) series are kept for role=both so existing
        # dashboards/scrapes see the same names. Bucketed histograms
        # (was: full-history summaries) — PromQL quantiles come from
        # histogram_quantile() over the _bucket series.
        role_labels = {} if self.role == "both" else {"role": self.role}

        # warm-vs-cold TTFT attribution (ISSUE 11 satellite): the plain
        # series stays (dashboards/tests key on it); the cache-labeled
        # children split the SAME observations by the prefix-/handoff-
        # hit outcome at admission, so the warm-vs-cold win (perf.md
        # Finding 16's 1783→176 ms pair) is a live PromQL ratio
        def _ttft():
            out = [(role_labels, s.ttft)]
            out.extend(({**role_labels, "cache": k}, acc)
                       for k, acc in sorted(s.ttft_by_cache.items()))
            return out

        reg.histogram_func("llm_ttft_seconds", _ttft,
                           "time to first token (prefill replicas: "
                           "KV-claimable time); cache-labeled children "
                           "split by admission prefix/handoff outcome")
        reg.histogram_func("llm_tpot_seconds",
                           lambda: [(role_labels, s.tpot)],
                           "mean time per output token after the first")
        # host-gap plane (obs/steptrace.py, ISSUE 11): the per-step
        # engine-loop timeline — where the host spends the time between
        # dispatches, and the live device-busy/host-gap dial the
        # ROADMAP item-3 overlap refactor must move. All reads go
        # through the recorder's atomically swapped snapshot (single-
        # writer convention; a scrape never mixes two steps' totals).
        stp = eng.steptrace

        def _host_gap():
            snap = stp.snapshot()
            return [({"activity": a}, v)
                    for a, v in sorted(snap["host_seconds"].items())]

        reg.counter_func("llm_host_gap_seconds_total", _host_gap,
                         "engine-thread seconds between dispatches, by "
                         "host activity (queue_drain/admit/plan/"
                         "index_build/draft_propose/grammar_compile/"
                         "grammar_mask/dispatch_wait/sample_commit/"
                         "publish/other)")
        reg.counter_func(
            "llm_step_wall_seconds_total",
            lambda: stp.snapshot()["step_wall_seconds_total"],
            "cumulative engine step() wall seconds (non-idle steps)")
        reg.counter_func(
            "llm_engine_thread_seconds_total",
            lambda: [({"state": st}, v) for st, v in
                     sorted(stp.snapshot()["thread_seconds"].items())],
            "the engine thread's step wall seconds by what it did as a "
            "thread, covered by a running program or not: cpu (it ran), "
            "device_wait (blocked fetching a program's results), stalled "
            "(neither: no GIL, descheduled, asleep in a lock); the three "
            "sum to llm_step_wall_seconds_total")
        reg.counter_func(
            "llm_engine_steps_total",
            lambda: stp.snapshot()["steps"],
            "non-idle engine step() iterations recorded")
        reg.counter_func(
            "llm_sampler_steps_total",
            lambda: [({"tier": t}, stp.snapshot()["sampler_steps"].get(t, 0))
                     for t in SAMPLER_TIERS],
            "engine steps whose decode, fused mixed or block program ran "
            "the sampler, by the body its live rows' flags chose (argmax: "
            "all greedy; plain: temperature only; filtered: the "
            "full-vocabulary sort for top-k / top-p)")
        reg.counter_func(
            "llm_steps_ahead_total",
            lambda: stp.snapshot()["steps_ahead"],
            "engine steps whose program was issued while the one before "
            "it was unread (one step of lookahead: the host's share of a "
            "step runs while the device computes)")
        reg.counter_func(
            "llm_step_drains_total",
            lambda: [({"reason": r}, stp.snapshot()["step_drains"].get(r, 0))
                     for r in DRAIN_REASONS],
            "engine steps that did not run ahead, by why not (idle: "
            "nothing was in flight; else the state that made the engine "
            "read the program in flight first)")
        reg.counter_func(
            "llm_tokens_discarded_total",
            lambda: stp.snapshot()["tokens_discarded"],
            "tokens of rows a program ran past their EOS (issued before "
            "the EOS was read), dropped when the program was read")
        reg.counter_func(
            "llm_dispatch_issue_seconds_total",
            lambda: stp.snapshot()["dispatch_issue_seconds_total"],
            "dispatch windows' issue part: argument building, "
            "host-to-device copies and the Python dispatch, until the "
            "jitted call returned (host time; the device may be idle)")
        reg.counter_func(
            "llm_dispatch_wait_seconds_total",
            lambda: stp.snapshot()["dispatch_wait_seconds_total"],
            "dispatch windows' wait part: from the jitted call's return "
            "to its results on the host")
        reg.gauge_func(
            "llm_device_busy_fraction",
            lambda: stp.snapshot()["device_busy_fraction"],
            "rolling fraction of step wall time inside dispatch windows "
            "(issue + wait on the host clock, last 50 steps): an upper "
            "bound on device-busy time, not a device measurement")
        reg.gauge_func(
            "llm_host_gap_fraction",
            lambda: stp.snapshot()["host_gap_fraction"],
            "rolling fraction of step wall time the chip waited on "
            "Python (1 - device_busy; the item-3 overlap target)")
        # per-request critical-path aggregate: every finished request's
        # wall time decomposed into segments (GET /debug/requests has
        # the per-request view)
        reg.counter_func(
            "llm_request_critical_path_seconds_total",
            lambda: [({"segment": seg}, v) for seg, v in
                     sorted(s.critical_path_snapshot().items())],
            "finished requests' wall seconds by critical-path segment")
        # disaggregation accounting: published/claimed say the handoff
        # plane works; lost + local re-prefills say how often the decode
        # pool fell back to doing prefill itself (the llm-d health signal)
        hm = self.handoff_meter
        reg.counter_func(
            "llm_handoff_total",
            lambda: [({"event": "published"}, eng.handoff_published),
                     ({"event": "publish_failed"},
                      eng.handoff_publish_failed),
                     ({"event": "claimed"}, hm.claimed),
                     ({"event": "kv_admitted"}, eng.kv_admitted),
                     ({"event": "kv_rejected"}, eng.kv_rejected),
                     ({"event": "repinned"}, hm.repinned),
                     ({"event": "repin_failed"}, hm.repin_failed)],
            "disaggregated KV handoff events")
        reg.counter_func("llm_handoff_lost_total", lambda: hm.lost,
                         "handoff ids that resolved to no entry")
        reg.counter_func("llm_local_prefills_total",
                         lambda: eng.local_prefills,
                         "prefills a decode-role replica ran itself")
        # session-native serving (serve/sessions.py, ISSUE 17): read the
        # store LIVE at scrape — registered unconditionally so the
        # metric-docs census and dashboards see one stable family set;
        # no store → families present, no samples
        def _sess(reader):
            def read():
                st = getattr(eng, "session_store", None)
                return [] if st is None else reader(st.counters())
            return read

        reg.gauge_func("llm_sessions_active",
                       _sess(lambda c: [({}, c["active"])]),
                       "conversations with server-held KV pinned on "
                       "this replica")
        reg.gauge_func("llm_session_pinned_pages",
                       _sess(lambda c: [({}, c["pinned_pages"])]),
                       "KV pages refcount-pinned under session handles")
        reg.counter_func(
            "llm_session_turns_total",
            _sess(lambda c: [({"cache": k}, v)
                             for k, v in sorted(c["turns"].items())]),
            "finished session turns by admission cache outcome "
            "(hit / partial / cold)")
        reg.counter_func(
            "llm_session_evictions_total",
            _sess(lambda c: [({"reason": k}, v)
                             for k, v in sorted(c["evictions"].items())]),
            "session pin evictions (ttl / pressure / capacity)")
        reg.counter_func(
            "llm_session_pulls_total",
            _sess(lambda c: [({"event": k}, v)
                             for k, v in sorted(c["pulls"].items())]),
            "fleet warm-path events (published / publish_failed / "
            "claimed / lost)")
        # read eng.prefix_cache LIVE at scrape time: benches and serving
        # setups attach/replace the cache after server construction,
        # and the pre-registry exposition tracked that; no cache →
        # family present, no samples
        def _pc(attr):
            def read():
                pc = eng.prefix_cache
                return [] if pc is None else [({}, getattr(pc, attr))]
            return read

        reg.counter_func("llm_prefix_cache_hits_total", _pc("hits"))
        reg.counter_func("llm_prefix_cache_full_hits_total",
                         _pc("full_hits"))
        reg.counter_func("llm_prefix_cache_misses_total", _pc("misses"))
        reg.counter_func("llm_prefix_cache_tokens_saved_total",
                         _pc("tokens_saved"))
        reg.gauge_func("llm_prefix_cache_tokens", _pc("cached_tokens"))
        if getattr(eng, "paged", None) is not None:
            # paged KV plane (docs/paged-kv.md): occupancy is THE
            # admission signal — free pages are admittable tokens, the
            # shared count is prefix reuse working, and preemptions
            # mean the pool is undersized for the offered load
            pool = eng.paged.pool

            def _pages():
                free = pool.free_pages
                shared = pool.shared_pages
                return [({"state": "free"}, free),
                        ({"state": "used"}, pool.capacity - free),
                        ({"state": "shared"}, shared)]

            reg.gauge_func("llm_kv_pages", _pages,
                           "page-pool occupancy by state (shared = "
                           "refcount > 1, also counted in used)")
            reg.gauge_func("llm_kv_pages_total", lambda: pool.capacity,
                           "allocatable pages in the pool")
            reg.gauge_func("llm_kv_page_size",
                           lambda: pool.page_size,
                           "tokens per KV page")
            reg.gauge_func(
                "llm_kv_page_fragmentation",
                lambda: [({}, eng.debug_kv().get("fragmentation", 0.0))],
                "allocated-but-unfilled token slack of slot-mapped "
                "pages (contiguous layouts waste cache_len - context "
                "per slot; paged keeps this under one page)")
            reg.counter_func("llm_kv_preemptions_total",
                             lambda: eng.preemptions,
                             "slots preempted (recompute-resume) under "
                             "page-pool pressure")
            reg.counter_func("llm_kv_rejected_too_large_total",
                             lambda: eng.rejected_too_large,
                             "prompts refused at submit: pages needed "
                             "exceed pool capacity (HTTP 422)")
        if eng.speculative_k is not None:
            # speculation plane (ISSUE 9): proposed/accepted drafted
            # tokens, fused verify dispatches, the tokens those
            # dispatches committed (accepted + bonus), and
            # a ready-made acceptance-rate gauge — the live "is the
            # spec bet paying" dial next to llm_dispatch_hbm_bw_util
            reg.counter_func("llm_spec_proposed_total",
                             lambda: eng.spec_proposed,
                             "drafted tokens submitted to verify")
            reg.counter_func("llm_spec_accepted_total",
                             lambda: eng.spec_accepted,
                             "drafted tokens the verify accepted")
            reg.counter_func("llm_spec_rounds_total",
                             lambda: eng.spec_rounds,
                             "fused spec-verify dispatches issued")
            reg.counter_func("llm_spec_round_tokens_total",
                             lambda: eng.spec_round_tokens,
                             "tokens committed by spec dispatches "
                             "(accepted + bonus)")

            def _acceptance():
                proposed = eng.spec_proposed     # snapshot: torn reads
                accepted = eng.spec_accepted     # stay <= 1.0
                if proposed <= 0:
                    return []
                return [({}, min(accepted / proposed, 1.0))]

            reg.gauge_func("llm_spec_acceptance_rate", _acceptance,
                           "lifetime accepted/proposed drafted tokens "
                           "(no samples until the first draft)")
        # structured output (serve/constrain.py, ISSUE 12): registered
        # unconditionally — zeros until the first constrained request,
        # so dashboards and the metric-docs census see one stable set
        sc = self._structured_counts
        reg.counter_func(
            "llm_structured_requests_total",
            lambda: [({"kind": k}, v) for k, v in sorted(sc.items())],
            "requests that carried a grammar constraint, by kind "
            "(json_object / json_schema / tool_call)")
        # streaming (handle_chat's chunks()): what the detokeniser
        # costs and how often a token's text is held back; booked at a
        # stream's END, so a scrape mid-stream reads finished streams
        ev = self._stream_token_events  # graftlint: disable=guarded-by — monotone counters, read lock-free at scrape (the spec_* convention)
        reg.counter_func(
            "llm_stream_detokenize_seconds_total",
            lambda: self._stream_detokenize_s,  # graftlint: disable=guarded-by — monotone float, GIL-atomic read at scrape
            "handler-thread seconds inside the SSE streams' incremental "
            "detokeniser (StreamDecoder.push / finish), summed a stream "
            "and booked at its end")
        reg.counter_func(
            "llm_stream_token_events_total",
            lambda: [({"text": k}, v) for k, v in sorted(ev.items())],
            "tokens handed to streams' detokenisers, by whether text "
            "went out with them (yes) or was held back (held: an open "
            "character, a skipped special token); booked at a "
            "stream's end")
        reg.counter_func(
            "llm_grammar_mask_seconds_total",
            lambda: eng.grammar_mask_seconds_total,
            "engine-thread seconds staging grammar logit masks "
            "(includes lazy automaton-state compiles; the steptrace "
            "grammar_compile/grammar_mask activities split the two)")
        reg.counter_func(
            "llm_spec_grammar_rejects_total",
            lambda: eng.spec_grammar_rejects,
            "drafted tokens rejected by the grammar during fused "
            "spec-round mask staging (the on-device acceptance "
            "cumprod truncates at each)")
        # multi-LoRA plane (serve/multi_lora.py, ISSUE 15): read the
        # adapter registries LIVE at scrape — the base engine's (when it
        # serves adapters) plus any distinct registry behind the
        # adapters= handles (the build_adapter_engines shim's shared
        # engine). Registered unconditionally; no registry → families
        # present, no samples.
        def _adapter_regs():
            seen = {}
            for e in (eng, *self.adapters.values()):
                r = getattr(e, "adapter_registry", None)
                if r is not None:
                    seen[id(r)] = r
            return list(seen.values())

        def _adapter_sum(key):
            def read():
                regs = _adapter_regs()
                if not regs:
                    return []
                return [({}, sum(r.stats()[key] for r in regs))]
            return read

        reg.gauge_func("llm_adapters_loaded", _adapter_sum("loaded"),
                       "LoRA adapters resident in the registry banks")
        reg.gauge_func("llm_adapter_bytes", _adapter_sum("bytes_loaded"),
                       "HBM bytes held by loaded adapter factor rows "
                       "(f32 payload at the padded bucket rank)")
        reg.counter_func("llm_adapter_swap_seconds_total",
                         _adapter_sum("swap_seconds_total"),
                         "cumulative seconds spent hot-loading adapter "
                         "checkpoints into the banks")
        reg.counter_func("llm_adapter_evictions_total",
                         _adapter_sum("evictions_total"),
                         "adapter rows evicted under the registry byte "
                         "budget (refcount-0 LRU only)")

        def _tenant_tokens():
            out: dict[str, int] = {}
            for r in _adapter_regs():
                for name, n in r.stats()["tenant_tokens"].items():
                    out[name] = out.get(name, 0) + n
            return [({"adapter": name}, n)
                    for name, n in sorted(out.items())]

        reg.counter_func("llm_tenant_tokens_total", _tenant_tokens,
                         "output tokens generated per adapter tenant "
                         "(finished requests; base-model traffic is "
                         "not labeled)")
        return reg

    def metrics_text(self) -> str:
        return self.registry.render()

    # --- HTTP plumbing -------------------------------------------------------

    def make_handler(self):
        server = self

        class Handler(JsonHandler):
            def _sse(self, events):
                self._responded = True
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.end_headers()
                try:
                    try:
                        for event in events:
                            payload = f"data: {json.dumps(event)}\n\n".encode()
                            self.wfile.write(payload)
                            self.wfile.flush()
                    except Exception as e:  # noqa: BLE001 — headers are out;
                        # surface the fault as an SSE error event, then DONE.
                        err = {"error": {"message": f"{type(e).__name__}: {e}",
                                         "type": "internal_error"}}
                        self.wfile.write(f"data: {json.dumps(err)}\n\n".encode())
                    self.wfile.write(b"data: [DONE]\n\n")
                    self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError):
                    pass  # client went away mid-stream

            def do_GET(self):
                if serve_obs_get(self, server.metrics_text,
                                 server.tracer):
                    return
                try:
                    if self.path == "/debug/kv":
                        # page-pool occupancy / sharing / fragmentation
                        # / block-table sizes (docs/paged-kv.md); the
                        # contiguous layout reports its reservation
                        return self._json(200, server.engine.debug_kv())
                    if self.path == "/debug/requests":
                        # recent-finished ring with per-request
                        # critical-path breakdowns (ISSUE 11; see
                        # docs/observability.md "Host timeline")
                        return self._json(
                            200, server.engine.debug_requests())
                    if self.path == "/debug/sessions":
                        # server-held conversation pins + fleet pull
                        # accounting (serve/sessions.py, ISSUE 17)
                        return self._json(
                            200, server.engine.debug_sessions())
                    if self.path == "/debug/hbm":
                        # HBM ownership tree + per-account high-water
                        # marks + reconciliation residual (obs/hbm.py,
                        # docs/observability.md "Memory plane")
                        return self._json(
                            200, get_ledger().debug_tree())
                    if self.path == "/v1/models":
                        return self._json(200, {
                            "object": "list",
                            "data": [{
                                "id": name,
                                "object": "model",
                                "owned_by": "llm-in-practise-tpu",
                            } for name in (server.model_name,
                                           *server.adapters)],
                        })
                    if self.path in ("/", "/chat"):
                        return self._text(
                            200, webui_html(server.model_name).encode(),
                            "text/html; charset=utf-8",
                        )
                except Exception as e:  # noqa: BLE001 — a GET fault must
                    # answer the client, not drop the connection
                    return self._json(500, {"error": {
                        "message": f"{type(e).__name__}: {e}",
                        "type": "internal_error"}})
                return self._json(404, {"error": {"message": "not found"}})

            def do_POST(self):
                if self.path not in ("/v1/chat/completions",
                                     "/v1/embeddings",
                                     "/internal/handoff/prefill",
                                     "/debug/profile"):
                    return self._json(404, {"error": {"message": "not found"}})
                body, err = self._read_json()
                if err:
                    return self._json(400, err)
                if serve_obs_post(self, body):
                    return None
                # cross-hop trace continuity: the gateway (or any
                # client) propagates a traceparent header; spans minted
                # here join that trace instead of starting a new one
                ctx = parse_traceparent(self.headers.get("traceparent"))
                # session-native serving (serve/sessions.py): the
                # conversation handle rides the header (gateway/client)
                # or the body field — the header wins on conflict, the
                # same precedence rule traceparent follows
                sid = self.headers.get("X-Session-ID")
                try:
                    if self.path == "/v1/embeddings":
                        return server.handle_embeddings(body, self._json)
                    if self.path == "/internal/handoff/prefill":
                        return server.handle_prefill(body, self._json,
                                                     trace=ctx)
                    return server.handle_chat(body, self._json, self._sse,
                                              trace=ctx, session_id=sid)
                except Exception as e:  # noqa: BLE001 — a handler fault must
                    # still answer the client, not drop the connection. If a
                    # response already went out (SSE underway), sending a
                    # second status line would corrupt the stream — _sse has
                    # its own in-band error path; just stop.
                    if self._responded:
                        return None
                    return self._json(500, {"error": {
                        "message": f"{type(e).__name__}: {e}",
                        "type": "internal_error",
                    }})

        return Handler

    def serve(self, host: str = "0.0.0.0", port: int = 8000, *, background: bool = False):
        """Start engine loop + HTTP server. Returns the bound port."""
        for eng in (self.engine, *self.adapters.values()):
            if eng._thread is None:
                eng.start()

        # The stdlib default listen backlog is 5: at a few hundred
        # concurrent connects the SYN queue overflows and clients see
        # ECONNRESET (measured: 101/512 requests lost at concurrency 256
        # before this). Size it for the benchmark ladder's worst burst.
        class _Server(ThreadingHTTPServer):
            request_queue_size = 1024
            daemon_threads = True

        self._httpd = _Server((host, port), self.make_handler())
        bound = self._httpd.server_address[1]
        if background:
            threading.Thread(target=self._httpd.serve_forever, daemon=True).start()
        else:
            self._httpd.serve_forever()
        return bound

    def shutdown(self):
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
        self.engine.stop()
        for eng in self.adapters.values():
            eng.stop()


def webui_html(model_name: str) -> str:
    """Minimal streaming chat page — the reference's Gradio web UIs
    (``Scripts/inference/05-…-webui-infr.py``, streaming ``06-…:52-75``)
    without the Gradio dependency: vanilla HTML + fetch over the SSE
    endpoint, incremental delta rendering, multi-turn history."""
    name_html = html.escape(model_name)
    name_js = json.dumps(model_name)  # JS string literal, quotes included
    return """<!doctype html>
<meta charset="utf-8"><title>chat — """ + name_html + """</title>
<style>
 body{font-family:system-ui,sans-serif;max-width:720px;margin:2rem auto;padding:0 1rem}
 #log{border:1px solid #ccc;border-radius:8px;padding:1rem;min-height:300px;
      white-space:pre-wrap}
 .u{color:#036;font-weight:600}.a{color:#222}
 form{display:flex;gap:.5rem;margin-top:1rem}
 input{flex:1;padding:.5rem;font-size:1rem}
 button{padding:.5rem 1rem}
</style>
<h2>""" + name_html + """</h2>
<div id=log></div>
<form id=f><input id=q autocomplete=off placeholder="message…">
<button>send</button></form>
<script>
const log=document.getElementById('log'),f=document.getElementById('f'),
      q=document.getElementById('q'),history=[];
f.onsubmit=async e=>{
  e.preventDefault();
  const text=q.value.trim(); if(!text)return; q.value='';
  history.push({role:'user',content:text});
  log.append(Object.assign(document.createElement('div'),
    {className:'u',textContent:'you: '+text}));
  const out=Object.assign(document.createElement('div'),
    {className:'a',textContent:'bot: '});
  log.append(out);
  const r=await fetch('/v1/chat/completions',{method:'POST',
    headers:{'Content-Type':'application/json'},
    body:JSON.stringify({model:""" + name_js + """,messages:history,
                         stream:true,max_tokens:256})});
  const reader=r.body.getReader(),dec=new TextDecoder();
  let buf='',answer='';
  for(;;){
    const {done,value}=await reader.read(); if(done)break;
    buf+=dec.decode(value,{stream:true});
    let i;
    while((i=buf.indexOf('\\n\\n'))>=0){
      const line=buf.slice(0,i).trim(); buf=buf.slice(i+2);
      if(!line.startsWith('data:'))continue;
      const data=line.slice(5).trim();
      if(data==='[DONE]')continue;
      const delta=JSON.parse(data).choices?.[0]?.delta?.content;
      if(delta){answer+=delta;out.textContent='bot: '+answer;}
    }
  }
  history.push({role:'assistant',content:answer});
};
</script>"""
