"""Batched multi-LoRA serving — one base model, thousands of tenants
(ROADMAP item 5 / ISSUE 15).

``serve/adapters.py`` served each adapter as a whole merged-weight
engine: N adapters paid N full copies of the base model in HBM plus N
jit caches, and slots could never batch across tenants. This module is
the punica-style answer (gathered BGMV — arxiv 2310.18547's batched
``y += x @ A[idx] @ B[idx]`` idiom): the low-rank factors of every
loaded adapter live in shared, rank-bucketed HBM banks, a per-slot
``adapter_index`` array rides the dispatch plan, and twin "adapted"
engine programs (the ISSUE 12 masked-twin idiom) gather each slot's
A/B factors inside the jitted step and add the delta on the LoRA
target matmuls. Slots running DIFFERENT adapters — and adapter-none
slots, whose index selects the all-zeros row 0 — share one dispatch at
the pinned 1 dispatch/step on both KV layouts.

Three pieces:

- :func:`lora_context` / :func:`current_lora` — a thread-local stack
  carrying the gathered-BGMV dispatch pytree. The engine's adapter
  twin programs push it INSIDE the jitted function (the factors enter
  as traced jit arguments, never baked constants), and the facade's
  interceptor reads it per Dense call.
- :class:`LoRAServingModel` — the model facade
  (:class:`~llm_in_practise_tpu.parallel.collectives.TPQuantizedCollectives`
  idiom): ``apply`` delegates untouched when no context is set (base
  programs stay byte-identical executables) and runs under the
  gathered-BGMV method interceptor when one is.
- :class:`AdapterRegistry` — hot-load/evict lifecycle over the banks:
  rank-bucketed capacity with power-of-two growth (bounded retraces),
  refcounted rows with LRU evict-under-pressure against a byte budget
  (the kv-pool ``max_bytes`` convention), per-adapter namespace
  generations for prefix-cache isolation, and swap/eviction/tenant
  counters for /metrics.

``AdapterHandle`` at the bottom keeps the old engine-per-adapter
surface (``serve/api.py``'s ``adapters=`` dict) working over ONE
shared engine.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import re
import threading
import time
from contextlib import contextmanager

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from llm_in_practise_tpu.obs.hbm import get_ledger
from llm_in_practise_tpu.obs.logging import get_logger
from llm_in_practise_tpu.peft.lora import LoRAConfig, stack_lora_tree

_BLOCK_RE = re.compile(r"block_(\d+)/(.*)")

# ---------------------------------------------------------------------------
# thread-local lora context
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextmanager
def lora_context(lora):
    """Push a gathered-BGMV dispatch pytree for the current thread.

    The engine's adapter twin programs enter this INSIDE the jitted
    wrapper, so while the program traces, ``current_lora()`` returns
    TRACERS of the bank arrays — the compiled executable takes them as
    arguments and one program serves every adapter population."""
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append(lora)
    try:
        yield
    finally:
        stack.pop()


def current_lora():
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


def lora_wrap(fn):
    """Twin-program wrapper: same body, plus a KW-ONLY ``lora`` pytree
    argument pushed as the thread-local context inside the traced
    function. Keyword-only keeps every positional ``donate_argnums``
    index of the wrapped program valid, and jit's laziness means a twin
    that never runs never compiles (the masked-twin economics)."""

    def wrapped(*args, lora, **kwargs):
        with lora_context(lora):
            return fn(*args, **kwargs)

    return wrapped


# ---------------------------------------------------------------------------
# the gathered-BGMV interceptor + model facade
# ---------------------------------------------------------------------------


def _gathered_delta(lora, key, x):
    """Summed low-rank delta for Dense ``key`` over the batch:
    ``((x @ A[idx]) @ B[idx]) * scale[idx]`` per rank bucket, f32
    compute (the two rank-r einsums are tiny next to the base matmul).
    Returns None when no loaded bucket carries this target."""
    m = _BLOCK_RE.match(key)
    delta = None
    for rb, bank in lora["banks"].items():
        idx = lora["idx"][rb]
        fac = layer = None
        if m is not None:
            fac = bank["stacked"].get("blocks/block/" + m.group(2))
            layer = int(m.group(1))
        if fac is None:
            fac = bank["flat"].get(key)
            layer = None
        if fac is None:
            continue
        if layer is not None:
            ga = fac["a"][idx, layer]     # (B, d_in, rb)
            gb = fac["b"][idx, layer]     # (B, rb, d_out)
        else:
            ga = fac["a"][idx]
            gb = fac["b"][idx]
        t = jnp.einsum("b...d,bdr->b...r", x.astype(jnp.float32), ga)
        d = jnp.einsum("b...r,bro->b...o", t, gb)
        scale = bank["scale"][idx].reshape((-1,) + (1,) * (d.ndim - 1))
        d = d * scale
        delta = d if delta is None else delta + d
    return delta


def _lora_interceptor(next_fn, call_args, call_kwargs, context):
    """Flax method interceptor adding the gathered low-rank delta AFTER
    the unmodified base Dense call (the base math — including any
    packed-quantized or TP-collective interception stacked beneath —
    is untouched; adapter-none rows gather the all-zeros row 0, so
    their delta is exactly 0.0 and the output bit-identical)."""
    lora = current_lora()
    mod = context.module
    if (lora is None or not isinstance(mod, nn.Dense)
            or context.method_name != "__call__"):
        return next_fn(*call_args, **call_kwargs)
    y = next_fn(*call_args, **call_kwargs)
    key = "/".join(mod.path) + "/kernel"
    delta = _gathered_delta(lora, key, call_args[0])
    if delta is None:
        return y
    return y + delta.reshape(y.shape).astype(y.dtype)


class LoRAServingModel:
    """Model facade (the ``TPQuantizedCollectives`` idiom) routing every
    engine program through the gathered-BGMV interceptor WHEN a lora
    context is set — and delegating untouched when none is, so the base
    (non-twin) programs trace the exact pre-LoRA computation.

    Wraps any serving model object, including an already-wrapped
    ``TPQuantizedCollectives`` (the interceptors nest; the base matmul
    path beneath stays whatever it was). ``inner`` exposes the wrapped
    model for identity checks (the engine's quantized-collective
    isinstance probe must see through this facade)."""

    def __init__(self, model):
        self.inner = model

    @property
    def config(self):
        return self.inner.config

    def init_cache(self, *args, **kwargs):
        return self.inner.init_cache(*args, **kwargs)

    def apply(self, variables, *args, **kwargs):
        if current_lora() is None:
            return self.inner.apply(variables, *args, **kwargs)
        with nn.intercept_methods(_lora_interceptor):
            return self.inner.apply(variables, *args, **kwargs)

    def __getattr__(self, item):
        # dataclass-style passthrough for everything else the serving
        # stack duck-types off the model (paged_kv geometry, cost-model
        # config reads, draft compat checks, ...)
        return getattr(self.inner, item)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _AdapterRec:
    name: str
    rb: int                     # rank bucket
    row: int                    # bank row
    ns: int                     # prefix-namespace generation (monotone)
    n_bytes: int                # f32 payload bytes at padded rank
    refcount: int = 0
    last_used: float = 0.0
    source: str | None = None


class _RankBucket:
    """One rank bucket's stacked banks. Row 0 is RESERVED all-zeros —
    the "no adapter" row every idle/base slot's index selects, making
    the adapted programs' base rows bit-identical by construction."""

    def __init__(self, rb: int):
        self.rb = rb
        self.cap = 2                       # row 0 (zeros) + 1
        self.free: list[int] = [1]
        self.stacked: dict[str, dict] = {}   # key -> {"a","b"} jnp banks
        self.flat: dict[str, dict] = {}
        self.scale = jnp.zeros((self.cap,), jnp.float32)

    def banks(self) -> dict:
        return {"stacked": self.stacked, "flat": self.flat,
                "scale": self.scale}

    def grow(self) -> None:
        """Double capacity (power-of-two ladder → bounded retraces of
        the adapter twins, the prefill-bucket compile policy)."""
        new_cap = self.cap * 2
        pad = new_cap - self.cap

        def wide(bank):
            return {k: jnp.concatenate(
                [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)], axis=0)
                for k, v in bank.items()}

        self.stacked = {k: wide(v) for k, v in self.stacked.items()}
        self.flat = {k: wide(v) for k, v in self.flat.items()}
        self.scale = jnp.concatenate(
            [self.scale, jnp.zeros((pad,), jnp.float32)])
        self.free.extend(range(self.cap, new_cap))
        self.cap = new_cap

    def ensure_target(self, key: str, a_shape, b_shape,
                      stacked: bool) -> None:
        """Union-of-targets banks: an adapter bringing a target key the
        bucket hasn't seen allocates zero rows for every existing
        adapter (their delta through it stays exactly 0). One bounded
        retrace per new key — the pytree structure changed."""
        table = self.stacked if stacked else self.flat
        if key in table:
            return
        table[key] = {
            "a": jnp.zeros((self.cap,) + tuple(a_shape), jnp.float32),
            "b": jnp.zeros((self.cap,) + tuple(b_shape), jnp.float32),
        }

    def zero_row(self, row: int) -> None:
        for table in (self.stacked, self.flat):
            for key, fac in table.items():
                table[key] = {
                    "a": fac["a"].at[row].set(0.0),
                    "b": fac["b"].at[row].set(0.0),
                }
        self.scale = self.scale.at[row].set(0.0)


def load_adapter_tree(adapter_path: str):
    """Restore one ``adapter.msgpack`` + sidecar checkpoint
    (``ckpt.save_named`` layout, same path handling as
    ``serve.adapters.load_adapter``) WITHOUT merging: returns
    ``(lora_params, LoRAConfig)`` for bank stacking."""
    from llm_in_practise_tpu.ckpt import checkpoint as ckpt_lib

    if os.path.isdir(adapter_path):
        adapter_path = os.path.join(adapter_path, "adapter.msgpack")
    lora_params, meta = ckpt_lib.restore_checkpoint(adapter_path)
    if "lora_config" not in meta:
        raise ValueError(
            f"{adapter_path} has no lora_config metadata sidecar")
    return lora_params, LoRAConfig.from_dict(meta["lora_config"])


class AdapterRegistry:
    """Rank-bucketed stacked A/B factor banks + adapter lifecycle.

    Loading stacks an adapter's per-layer factors
    (:func:`~llm_in_practise_tpu.peft.lora.stack_lora_tree`) into one
    bank row per rank bucket — rank padded with zero columns to the
    bucket's power-of-two rank, which leaves the delta bit-unchanged.
    Requests ``acquire``/``release`` refcounts; eviction under the byte
    budget (``max_bytes``, the kv-pool convention — adapter payload
    bytes count against the same operator HBM ledger the tiered pool
    budgets) only ever takes refcount-0 rows, LRU first.

    Every (re-)register mints a fresh ``ns`` generation from a global
    monotone counter: the engine keys its prefix caches by
    ``token + (ns << 32)`` (length-preserving, injective), so tenants
    never hit each other's KV and a hot-swapped adapter name never hits
    its own stale KV. ``ns`` 0 is the base model's identity namespace.

    Thread-safe: HTTP threads register/acquire while the engine thread
    gathers dispatch args.
    """

    def __init__(self, base_params, *, max_bytes: int | None = None,
                 mesh=None, axis: str = "model"):
        blocks = [int(m.group(1)) for k in (base_params or {})
                  for m in (re.fullmatch(r"block_(\d+)", str(k)),) if m]
        self.n_layer = max(blocks) + 1 if blocks else 0
        self.max_bytes = max_bytes
        self.mesh = mesh
        self.axis = axis
        self._lock = threading.Lock()
        self._adapters: dict[str, _AdapterRec] = {}  # guarded-by: _lock
        self._buckets: dict[int, _RankBucket] = {}   # guarded-by: _lock
        self.bytes_loaded = 0                        # guarded-by: _lock
        # lifetime counters for /metrics (scrape threads read these as
        # monotone floats/ints; all writes under the lock)
        self.loads_total = 0                         # guarded-by: _lock
        self.evictions_total = 0                     # guarded-by: _lock
        self.swap_seconds_total = 0.0                # guarded-by: _lock
        self.tenant_tokens: dict[str, int] = {}      # guarded-by: _lock
        self._ns = itertools.count(1)
        self._log = get_logger("serve.multi_lora")

    # -- loading / eviction ------------------------------------------------

    def register(self, name: str, adapter_path: str) -> None:
        """Hot-load one adapter checkpoint under ``name``."""
        lora_params, cfg = load_adapter_tree(adapter_path)
        self.register_tree(name, lora_params, cfg, source=adapter_path)

    def register_tree(self, name: str, lora_params: dict,
                      cfg: LoRAConfig, source: str | None = None) -> None:
        """Stack a restored LoRA tree into the banks (tests and benches
        hand trees directly; :meth:`register` is the checkpoint path)."""
        t0 = time.monotonic()
        tree = (stack_lora_tree(lora_params, self.n_layer)
                if self.n_layer else dict(lora_params))
        rb = 1 << max(int(cfg.r) - 1, 0).bit_length()
        # f32 payload at the PADDED rank — what the bank row really costs
        n_bytes = 4 * sum(
            int(np.prod(ab["a"].shape)) // ab["a"].shape[-1] * rb
            + int(np.prod(ab["b"].shape)) // ab["b"].shape[-2] * rb
            for ab in tree.values())
        with self._lock:
            old = self._adapters.get(name)
            if old is not None:
                if old.refcount > 0:
                    raise RuntimeError(
                        f"adapter {name!r} is busy ({old.refcount} "
                        "in-flight requests); drain before hot-swapping")
                self._evict_locked(old)
            self._reserve_bytes_locked(name, n_bytes)
            bucket = self._buckets.get(rb)
            if bucket is None:
                bucket = self._buckets[rb] = _RankBucket(rb)
            row = self._take_row_locked(bucket)
            for key, ab in tree.items():
                # control-plane load path (register/hot-swap), not the
                # engine step: blocking on the checkpoint's arrays here
                # is the designed swap cost (llm_adapter_swap_seconds)
                a = np.asarray(ab["a"], np.float32)  # graftlint: disable=host-sync
                b = np.asarray(ab["b"], np.float32)  # graftlint: disable=host-sync
                r = a.shape[-1]
                if r > rb:                   # cannot happen (rb = ceil pow2)
                    raise ValueError(f"rank {r} exceeds bucket {rb}")
                a = np.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, rb - r)])
                b = np.pad(b, [(0, 0)] * (b.ndim - 2)
                           + [(0, rb - r), (0, 0)])
                stacked = key.startswith("blocks/block/")
                bucket.ensure_target(key, a.shape, b.shape, stacked)
                table = bucket.stacked if stacked else bucket.flat
                fac = table[key]
                table[key] = {
                    "a": self._place(fac["a"].at[row].set(a), key,
                                     part="a"),
                    "b": self._place(fac["b"].at[row].set(b), key,
                                     part="b"),
                }
            bucket.scale = bucket.scale.at[row].set(float(cfg.scaling))
            self._adapters[name] = _AdapterRec(
                name=name, rb=rb, row=row, ns=next(self._ns),
                n_bytes=n_bytes, last_used=time.monotonic(),
                source=source)
            self.bytes_loaded += n_bytes
            self.loads_total += 1
            self.swap_seconds_total += time.monotonic() - t0
            # HBM ledger: payload bytes under the rank bucket's account
            # (adapters/r<b>); the pow2 bank-capacity padding beyond
            # the payload shows up in the reconciliation residual, not
            # here — docs/observability.md "Memory plane"
            get_ledger().book(f"adapters/r{rb}", n_bytes)

    def _place(self, arr, key: str, *, part: str):
        """TP placement: factor banks shard with the BASE weight's rule
        (docs/serving-tp.md). Row-parallel targets shard the contraction
        dim — A's ``d_in`` — over the model axis; column-parallel
        targets shard the output dim — B's ``d_out``. Replicated
        whenever the mesh is absent or the dim doesn't divide (always
        correct; sharding is a memory/bandwidth choice)."""
        if self.mesh is None:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P

        from llm_in_practise_tpu.parallel.collectives import (
            ROW_PARALLEL_TARGETS,
        )

        tp = int(self.mesh.shape.get(self.axis, 1))
        row_parallel = any(t in key for t in ROW_PARALLEL_TARGETS)
        spec = [None] * arr.ndim
        if tp > 1:
            if part == "a" and row_parallel and arr.shape[-2] % tp == 0:
                spec[-2] = self.axis            # d_in
            elif (part == "b" and not row_parallel
                  and arr.shape[-1] % tp == 0):
                spec[-1] = self.axis            # d_out
        return jax.device_put(arr, NamedSharding(self.mesh, P(*spec)))

    def _take_row_locked(self, bucket: _RankBucket) -> int:
        if not bucket.free:
            bucket.grow()
        row = bucket.free.pop()
        # recycled rows hold the previous tenant's factors until the new
        # writes land — zero EVERY target so an adapter that doesn't
        # carry some bank key can't inherit stale deltas through it
        bucket.zero_row(row)
        return row

    def _reserve_bytes_locked(self, name: str, n_bytes: int) -> None:
        if self.max_bytes is None:
            return
        while self.bytes_loaded + n_bytes > self.max_bytes:
            victim = min(
                (r for r in self._adapters.values() if r.refcount == 0),
                key=lambda r: r.last_used, default=None)
            if victim is None:
                raise RuntimeError(
                    f"adapter byte budget exhausted loading {name!r}: "
                    f"{self.bytes_loaded + n_bytes} > {self.max_bytes} "
                    "and every loaded adapter has in-flight requests")
            self._log.info("evicting adapter %s under byte pressure "
                           "(%d bytes)", victim.name, victim.n_bytes)
            self._evict_locked(victim)
            self.evictions_total += 1
            get_ledger().note_reclaim(f"adapters/r{victim.rb}", "budget")

    def _evict_locked(self, rec: _AdapterRec) -> None:
        """Free ``rec``'s bank row (zeroed on reuse, not here — the
        engine thread may still hold last step's bank arrays, which are
        immutable snapshots) and drop its bytes from the ledger."""
        self._adapters.pop(rec.name, None)
        self._buckets[rec.rb].free.append(rec.row)
        self.bytes_loaded -= rec.n_bytes
        get_ledger().book(f"adapters/r{rec.rb}", -rec.n_bytes)

    def evict(self, name: str) -> bool:
        """Explicit unload; refuses while requests are in flight."""
        with self._lock:
            rec = self._adapters.get(name)
            if rec is None:
                return False
            if rec.refcount > 0:
                raise RuntimeError(
                    f"adapter {name!r} has {rec.refcount} in-flight "
                    "requests")
            self._evict_locked(rec)
            self.evictions_total += 1
            return True

    # -- request lifecycle -------------------------------------------------

    def acquire(self, name: str) -> None:
        with self._lock:
            rec = self._adapters.get(name)
            if rec is None:
                raise KeyError(name)
            rec.refcount += 1
            rec.last_used = time.monotonic()

    def release(self, name: str) -> None:
        with self._lock:
            rec = self._adapters.get(name)
            if rec is not None and rec.refcount > 0:
                rec.refcount -= 1

    def note_tokens(self, name: str, n: int) -> None:
        """Book ``n`` generated tokens to tenant ``name``
        (llm_tenant_tokens_total{adapter=…})."""
        if n <= 0:
            return
        with self._lock:
            self.tenant_tokens[name] = self.tenant_tokens.get(name, 0) + n

    def ns_of(self, name: str | None) -> int:
        """Prefix-namespace generation for ``name`` (0 = base)."""
        if name is None:
            return 0
        with self._lock:
            rec = self._adapters.get(name)
            return rec.ns if rec is not None else 0

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._adapters)

    def __contains__(self, name: str) -> bool:
        with self._lock:
            return name in self._adapters

    # -- dispatch ----------------------------------------------------------

    def dispatch_args(self, adapters: list[str | None]):
        """The gathered-BGMV jit-argument pytree for one dispatch whose
        batch rows run ``adapters`` (None = base → row 0), or None when
        every row is base — the caller then runs the base program and
        the twin never traces. Banks are IMMUTABLE snapshots (functional
        ``.at`` updates), so the engine thread may keep using a returned
        pytree across a concurrent register/evict."""
        with self._lock:
            recs = [self._adapters.get(a) if a is not None else None
                    for a in adapters]
            if all(r is None for r in recs):
                return None
            idx = {}
            banks = {}
            for rb, bucket in sorted(self._buckets.items()):
                rows = np.zeros((len(adapters),), np.int32)
                for i, rec in enumerate(recs):
                    if rec is not None and rec.rb == rb:
                        rows[i] = rec.row
                idx[rb] = jnp.asarray(rows)
                banks[rb] = bucket.banks()
            return {"idx": idx, "banks": banks}

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict:
        """Point-in-time snapshot for /metrics and /debug views."""
        with self._lock:
            return {
                "loaded": len(self._adapters),
                "bytes_loaded": self.bytes_loaded,
                "max_bytes": self.max_bytes,
                "loads_total": self.loads_total,
                "evictions_total": self.evictions_total,
                "swap_seconds_total": self.swap_seconds_total,
                "tenant_tokens": dict(self.tenant_tokens),
                "refcounts": {n: r.refcount
                              for n, r in self._adapters.items()},
                "buckets": {rb: {"cap": b.cap, "free": len(b.free)}
                            for rb, b in self._buckets.items()},
            }


# ---------------------------------------------------------------------------
# the engine-per-adapter compatibility surface
# ---------------------------------------------------------------------------


class AdapterHandle:
    """Engine-shaped view of ONE adapter on a SHARED engine — what
    ``serve/api.py``'s ``adapters=`` dict holds now that
    ``build_adapter_engines`` stopped building engines. ``submit``
    injects the adapter name; everything else proxies to the shared
    engine (stats, debug views, model/params reads, lifecycle)."""

    def __init__(self, engine, name: str):
        self._engine = engine
        self.adapter_name = name

    def submit(self, prompt_ids, params=None, **kw):
        kw.setdefault("adapter", self.adapter_name)
        return self._engine.submit(prompt_ids, params, **kw)

    def start(self):
        # the shared engine's loop may already run (engine.start is NOT
        # idempotent — two loops would race the slot tables)
        eng = self._engine
        if eng._thread is None or not eng._thread.is_alive():
            eng.start()

    def __getattr__(self, item):
        return getattr(self._engine, item)
