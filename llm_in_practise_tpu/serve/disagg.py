"""Disaggregated prefill/decode serving — role-split replicas with KV
handoff over the tiered pool.

The reference platform's llm-d stage (``LLM_on_Kubernetes/
Inference_Platfrom/08-LLM-Router``) splits serving into a **prefill pool**
and a **decode pool**: prefill is compute-bound, decode is bandwidth-bound
("Dissecting the Runtime Performance of … LLMs", arxiv 2311.03687), so
co-locating them trades TTFT against TPOT no matter how well one engine
fuses the two (PR 1 removed the per-step dispatch tax; the *cross-request*
interference — a 1,700 ms cold prefill stalling every decoder's block —
remains structural). Here:

- a **prefill replica** (``--role prefill``) runs chunked prefill only.
  On completion it publishes the full prompt KV as a pinned
  :class:`~.kv_pool.HostEntry` in the handoff namespace of the shared
  pool (``KVPoolServer`` ``hput``/``hclaim`` — pin-until-claimed, so LRU
  eviction can never race the claim; TTL-reclaimed if the decode side
  dies), then finishes the request with ``finish_reason="handoff"``.
- a **decode replica** (``--role decode``) claims the entry and admits
  the request through the engine's full-prefix-hit direct-insert path:
  the slot starts at ``index == len(prompt)`` with zero mid-prefill rows,
  so decode blocks never share a dispatch with somebody else's prefill
  chunk (``llm_mixed_blocks_total`` stays 0 by construction).
- the :class:`~.gateway.DisaggRouter` sequences the two calls and
  degrades gracefully: an empty pool or a lost handoff entry means the
  serving replica re-prefills locally (logged + counted) — correctness
  never depends on the handoff succeeding.

This module holds the handoff stores the roles speak through:
:class:`LocalHandoff` (in-process — tests, single-host multi-engine) and
:class:`RemoteHandoff` (the shared :class:`~.kv_pool.KVPoolServer`).
Both expose ``publish``/``claim`` with the same lost-entry semantics.

Observability (docs/observability.md): the gateway's two-phase dispatch
rides the request's trace id through ``kv_transfer_params`` — alongside
``handoff_id`` and ``model`` it carries ``trace`` (a traceparent-format
string), so the decode replica's ``handoff.claim`` span joins the same
trace as the prefill replica's ``handoff.publish`` span even when an
intermediary strips HTTP headers. The pool server's handoff counters
(pins/claims/TTL-reclaims/bytes) export at its ``--metrics-port``.
"""

from __future__ import annotations

import threading
import time
import uuid

from llm_in_practise_tpu.obs.logging import get_logger
from llm_in_practise_tpu.serve.kv_pool import (
    HandoffRejected,
    HostEntry,
    RemoteKVClient,
)

ROLES = ("prefill", "decode", "both")

# reserved namespace prefix for handoff entries on a shared pool server:
# they must never collide with the model's ordinary prefix-cache
# namespace (a handoff entry is pinned and claim-once; a prefix entry is
# LRU'd and shared)
HANDOFF_NS_PREFIX = "__handoff__/"


def new_handoff_id() -> str:
    return uuid.uuid4().hex


def validate_roles(role: str) -> str:
    if role not in ROLES:
        raise ValueError(f"role must be one of {ROLES}, got {role!r}")
    return role


#: default ngram proposal length for decode replicas (ISSUE 9 /
#: ROADMAP item 4): decode is bandwidth-bound, a decode replica never
#: prefills by design, and the fused spec round is greedy-lossless —
#: so speculation is the production default there, not an opt-in.
DECODE_DEFAULT_SPEC_K = 4


def default_speculative_k(role: str, requested: int | None) -> int | None:
    """Resolve the serving CLI's ``--speculative`` value for ``role``.

    ``--role decode`` replicas default speculation ON
    (:data:`DECODE_DEFAULT_SPEC_K`, the ngram proposer — no extra
    weights, lossless under greedy, one dispatch a round).
    An explicit ``--speculative 0`` opts out; any positive value is
    passed through; other roles keep speculation opt-in.
    """
    if requested == 0:
        return None
    if requested is None and role == "decode":
        return DECODE_DEFAULT_SPEC_K
    return requested


class LocalHandoff:
    """In-process handoff store: pin-until-claimed dict with TTL reclaim.

    Semantics match the pool server's handoff namespace exactly — tests
    and single-process multi-engine setups (chip sharing) use this so
    the role split is exercisable without a TCP pool."""

    def __init__(self, *, ttl_s: float = 120.0, clock=None):
        self.ttl_s = ttl_s
        self._clock = clock or time.monotonic
        self._entries: dict[str, tuple[float, HostEntry]] = {}  # guarded-by: _lock
        self._lock = threading.Lock()
        self.published = 0
        self.claimed = 0
        self.expired = 0

    def _sweep_locked(self, now: float) -> None:
        dead = [k for k, (exp, _) in self._entries.items() if exp <= now]
        for k in dead:
            del self._entries[k]
            self.expired += 1

    def publish(self, handoff_id: str, host: HostEntry) -> None:
        now = self._clock()
        with self._lock:
            self._sweep_locked(now)
            self._entries[handoff_id] = (now + self.ttl_s, host)
            self.published += 1

    def claim(self, handoff_id: str) -> HostEntry | None:
        now = self._clock()
        with self._lock:
            self._sweep_locked(now)
            found = self._entries.pop(handoff_id, None)
            if found is None:
                return None
            self.claimed += 1
            return found[1]

    def pending(self) -> int:
        with self._lock:
            return len(self._entries)


class RemoteHandoff:
    """Handoff store over a shared :class:`~.kv_pool.KVPoolServer`.

    ``namespace`` is the served model's identity (the same string the
    model's :class:`~.kv_pool.RemoteKVClient` uses) — the handoff keys
    get the reserved ``__handoff__/`` prefix on top, so prefix-cache
    traffic and handoff traffic of one model never collide, and two
    models' handoffs are isolated exactly like their KV."""

    def __init__(self, address, *, namespace: str = "",
                 timeout: float = 5.0):
        self._client = RemoteKVClient(
            tuple(address), timeout=timeout,
            namespace=HANDOFF_NS_PREFIX + namespace)
        self._log = get_logger("serve.disagg")
        # publishes run on the engine's publisher POOL and claims on
        # concurrent HTTP handler threads — bare `+= 1` across those
        # loses counts (read-modify-write is not GIL-atomic)
        self._lock = threading.Lock()
        self.published = 0        # guarded-by: _lock
        self.publish_errors = 0   # guarded-by: _lock
        self.claimed = 0          # guarded-by: _lock
        self.claim_errors = 0     # guarded-by: _lock

    @property
    def address(self):
        return self._client.address

    def publish(self, handoff_id: str, host: HostEntry) -> None:
        """Raises on failure (transport OR pool refusal): the caller is
        about to advertise this id to a decode replica, so a silent drop
        would turn into a guaranteed lost-claim later."""
        try:
            self._client.handoff_put(handoff_id, host)
        except (OSError, HandoffRejected):
            with self._lock:
                self.publish_errors += 1
            raise
        with self._lock:
            self.published += 1

    def claim(self, handoff_id: str) -> HostEntry | None:
        """``None`` = lost (expired / never published / already claimed /
        pool unreachable / reply undecodable) — the caller re-prefills
        locally. Transport AND decode faults are folded into "lost": a
        version-skewed pool returning a garbage manifest must degrade
        the request, not 5xx it."""
        import struct

        try:
            host = self._client.handoff_claim(handoff_id)
        except (OSError, ValueError, KeyError, struct.error) as e:
            with self._lock:
                self.claim_errors += 1
            self._log.warning("handoff claim %s failed (%s: %s) — "
                              "degrading to local prefill",
                              handoff_id, type(e).__name__, e)
            return None
        if host is not None:
            with self._lock:
                self.claimed += 1
        return host


def usable_for_engine(host: HostEntry, prompt_ids, engine) -> str | None:
    """Why a claimed handoff entry can NOT seed ``engine``'s slot for
    ``prompt_ids`` (``None`` = usable). The checks mirror the engine's
    ``_lookup_prefix`` usable() filter plus the full-length requirement
    of the direct-insert path — a mismatched entry (replica configured
    with a different cache layout / cache_len, or a tokenizer drift
    between replicas) degrades to local prefill instead of scattering
    garbage KV."""
    plen = len(prompt_ids)
    if host.length != plen:
        return (f"length mismatch: entry {host.length} vs prompt {plen} "
                "(tokenizer/crop drift between replicas?)")
    if getattr(host, "slot_axis", 0) != 0:
        return (f"cache layout mismatch: entry slot_axis {host.slot_axis} "
                "is the stacked layout an older replica wrote; this "
                "engine serves slot_axis 0")
    if getattr(engine, "paged", None) is None:
        # a contiguous consumer inserts the FULL (post-pow2-padding)
        # bucket width — bound that, or the scatter clamps and corrupts
        # the slot. A PAGED consumer only scatters the first `length`
        # positions, so any wire width is fine there.
        from llm_in_practise_tpu.serve.kv_pool import effective_bucket

        eff = effective_bucket(host)
        if eff > engine.cache_len:
            return (f"entry width {eff} (wire {host.bucket}, pow2-"
                    f"padded for the contiguous insert) exceeds engine "
                    f"cache_len {engine.cache_len}")
    return None
