"""Paged KV cache — block-table page pool with refcounted sharing.

The slot engine's original KV layout gives every slot a contiguous
``cache_len``-row region of one ``(max_slots, cache_len, …)`` buffer per
layer, so concurrency is capped by WORST-CASE context reservation: a
16-token prompt generating 32 tokens pins the same HBM as an 8K-context
request. vLLM's PagedAttention (the reference platform's serving core)
breaks that bond: KV lives in fixed-size **pages** carved from one
preallocated pool, and each request maps logical positions to physical
pages through a **block table** — admission reserves the pages a request
actually needs, decode allocates one page at a time as the context
grows, and a shared prompt prefix is the SAME physical pages refcounted
across requests (copy-on-write: a would-be write to a shared page forks
it first).

TPU twist — XLA-static shapes, no custom kernel: the jitted engine
programs cannot take a different shape per step, and the in-tree model
families all consume a contiguous ``(slots, width, …)`` cache. So the
paged programs keep the pool as ONE flat token-major buffer per layer
(``(num_pages * page_size, heads, dim)``), take host-computed
**gather/scatter index arrays as ordinary inputs** (same shapes every
step → no retrace), and inside one dispatch:

1. gather each slot's pages into a transient contiguous view whose
   width is bucketed (power-of-two up to ``cache_len`` — one compile
   per bucket, same trick as prefill buckets);
2. run the UNCHANGED engine program body (``_decode_fn``,
   ``decode_scan``, ``batched_chunk``, the fused mixed step) against
   that view — the math is literally the contiguous code path, which is
   how golden-token parity with ``kv_layout="contiguous"`` is pinned;
3. scatter only the freshly written rows back to their pages; discarded
   writes (idle rows' dead windows, padding) are routed to a reserved
   **trash page** (physical page 0) by the host-built scatter indices,
   replacing the contiguous path's clamp-and-overwrite gymnastics.

The transient view is freed by XLA between dispatches; its width tracks
the longest LIVE context (not ``cache_len``), so the persistent KV
footprint is the pool — sized to expected live tokens, not
``max_slots × cache_len``. That is where the concurrency headroom comes
from (see docs/paged-kv.md for the admission math and the workspace
caveat; a fused paged-attention Pallas kernel that reads pages in place
is the follow-up that removes the gather entirely).

Two physical forms, chosen by the ROW's shape (:func:`stored_by_pages`):
the flat form above suits a row with a head axis, ``(heads, dim)``; a row
that is ONE vector (a latent cache: ``ckv`` of DeepSeek-V3, 576 wide) is
stored by pages, ``(num_pages, page_size, width rounded up to whole lane
tiles)``, gathered a page at a time from the block table itself, and
sliced back to its width once (docs/paged-kv.md, "A latent page row"). A
model whose heads are 1.5 lane tiles wide (192-wide keys) declares its row
as one vector of heads x dim, whole tiles, and is stored the same way
("Keys of 192").

Layers that do not grow (:func:`cache_kinds`): a layer whose cache rows do
not follow ``max_len`` (a sliding-window layer's ring of ``window`` rows)
has nothing for a page allocator to share out: every live slot needs the
same bounded state. Its buffers are held BY SLOT, ``(max_slots, rows,
...)``, beside the pools; no block table names them, and the engine's
programs hand the model the slot's rows as they are. A RECURRENT layer's
state is held the same way (docs/paged-kv.md, "A state that is replaced";
:func:`cache_kinds`'s ``recurrent``: buffers that are not rows at all, in
a dtype of their own, replaced at every position; the model masks a dead
row's update and zeroes a sequence's start). An entry of the template may
hold the stacked buffers of several layers of one kind.

Sharing/refcount protocol (one invariant the churn test pins): a
physical page's refcount equals the number of slot block tables mapping
it, plus one if the :class:`~.prefix_cache.PagedPrefixIndex` holds it.
Pages are freed when the count returns to zero — never while any reader
remains.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np

from llm_in_practise_tpu.obs.hbm import get_ledger

#: physical page 0 is never allocated: host-built scatter indices route
#: every discarded write (idle rows, padding beyond a row's valid
#: window) into it, and unmapped logical pages gather from it (those
#: positions sit beyond the row's cache index, so the causal mask keeps
#: them unattended).
TRASH_PAGE = 0


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` KV rows (0 tokens -> 0 pages)."""
    return -(-int(n_tokens) // int(page_size))


def cache_kinds(model, dtype, max_len: int = 1 << 20):
    """``(template, by_slot, recurrent)`` of ``model``'s cache at
    ``max_len`` (> 1) positions: the abstract per-layer buffers (nothing
    is allocated); per layer whether its state is BOUNDED: no buffer's row
    axis is ``max_len`` long; and per layer whether it is a STATE that is
    replaced and not rows that are appended: it has buffers and none of
    them changes shape with ``max_len`` at all (a ring's rows are
    ``min(max_len, window)``: they do). Such a layer is bounded whatever
    an axis of it happens to measure (a state of 16 is not a cache of 16
    positions). Properties of the cache template alone: no flag and no
    model name."""
    short, tpl = (jax.eval_shape(
        lambda n=n: model.init_cache(1, n, dtype=dtype))
        for n in (1, max_len))
    recurrent = [len(a) > 1 and all(a[key].shape == b[key].shape
                                    for key in a)
                 for a, b in zip(short, tpl)]
    by_slot = [still or all(buf.shape[1] != max_len
                            for key, buf in layer.items() if key != "index")
               for layer, still in zip(tpl, recurrent)]
    return tpl, by_slot, recurrent


def kv_row_bytes(model, dtype) -> int:
    """HBM bytes one KV-cache ROW (one token position, all layers that
    grow with the context) costs for ``model`` — the exchange rate the
    engine uses to express a draft model's contiguous cache in page-pool
    tokens, so a paged engine with a draft can't over-admit against bytes
    the draft already spent (ISSUE 9 satellite; docs/paged-kv.md)."""
    tpl, by_slot, _ = cache_kinds(model, dtype)
    total = 0
    for layer, bounded in zip(tpl, by_slot):
        if bounded:
            continue
        for key, buf in layer.items():
            if key == "index":
                continue
            total += (buf.size // buf.shape[1]) * buf.dtype.itemsize
    return total


#: lanes of the chip's tile: the minor dimension of a buffer the chip keeps
#: row-major is a whole number of them
LANE = 128


def stored_by_pages(tails) -> bool:
    """The storage rule, a property of the rows' shapes (``tails``: each
    pool buffer's per-token row shape). The chip tiles a buffer's two
    minor dimensions, and keeps a buffer by rows across programs only if
    the axis the view gathers along is neither of them and the minor one
    is whole lane tiles. A row with a head axis, ``(heads, dim)``, keeps
    the gathered axis out of the tile in the flat ``(rows, heads, dim)``
    form. A row that is one vector does not: flat, its token axis is
    tiled with a width the chip will not keep minor (576 is 4.5 lane
    tiles: the compiler stores ``(rows, 576)`` column-major and every
    program re-lays the whole pool out twice). Such a pool is stored by
    pages, ``(num_pages, page_size, width up to whole lanes)``: the page
    axis is gathered, a page is one contiguous block. True when every
    row is one vector."""
    tails = list(tails)
    return bool(tails) and all(len(t) == 1 for t in tails)


def lane_whole(width: int) -> int:
    return -(-int(width) // LANE) * LANE


# The traced accessors of a pool buffer stored by pages, ``buf``
# (num_pages, page_size, lanes); ``width`` is the row's own (the pad
# columns stay zero: every write pads with zeros).

def take_pages(buf, page_idx, width: int):
    """The view ``(S, n * page_size, width)`` of the pages ``page_idx``
    (S, n) names, each taken whole."""
    S, n = page_idx.shape
    # clip, not take's default fill: see InferenceEngine._paged_view
    pages = jnp.take(buf, page_idx.reshape(-1), axis=0, mode="clip")
    return pages.reshape(S, n * buf.shape[1], buf.shape[2])[..., :width]


def take_page_rows(buf, flat_idx, width: int):
    """Rows ``(*flat_idx.shape, width)`` by their flat pool-row index
    (``page * page_size + offset``, as the host builders give it)."""
    page, off = jnp.divmod(flat_idx, buf.shape[1])
    return buf[page, off, :width]


def set_page_rows(buf, flat_idx, rows):
    """``buf`` with ``rows`` (N, width) written at the flat pool-row
    indices ``flat_idx`` (N,)."""
    page, off = jnp.divmod(flat_idx, buf.shape[1])
    rows = jnp.pad(rows.astype(buf.dtype),
                   ((0, 0), (0, buf.shape[2] - rows.shape[-1])))
    return buf.at[page, off].set(rows)


def copy_page(buf, src, dst):
    page = jax.lax.dynamic_slice_in_dim(buf, src, 1, axis=0)
    return jax.lax.dynamic_update_slice_in_dim(buf, page, dst, axis=0)


class PagePoolExhausted(RuntimeError):
    """Allocation failed with no reclaimable pages left."""


class PagePool:
    """Host-side accountant of the physical page pool: free list,
    per-page refcounts, and the alloc/share/release protocol.

    Purely bookkeeping — the actual KV bytes live in
    :class:`PagedKV`'s device buffers; this class decides which pages a
    request may write. Engine-thread writes, scrape-thread reads: the
    mutating ops and the stats properties share ``_lock``.

    ``reclaim`` (optional callable ``(n_pages) -> int``) is asked to
    free at least ``n_pages`` when the free list runs dry — the engine
    wires the shared-prefix index's LRU eviction here, so cold shared
    prefixes are reclaimed before admission fails.
    """

    def __init__(self, num_pages: int, page_size: int, *, reclaim=None):
        if num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is the reserved trash "
                f"page), got {num_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_pages = num_pages
        self.page_size = page_size
        self.reclaim = reclaim
        self._lock = threading.Lock()
        # refcount per physical page; page 0 pinned forever as trash
        self._refs = np.zeros((num_pages,), np.int32)  # guarded-by: _lock
        self._refs[TRASH_PAGE] = 1
        self._free: list[int] = list(range(num_pages - 1, 0, -1))  # guarded-by: _lock
        self.allocs = 0          # guarded-by: _lock
        self.frees = 0           # guarded-by: _lock
        self.alloc_failures = 0  # guarded-by: _lock

    # -- capacity / stats -----------------------------------------------------

    @property
    def capacity(self) -> int:
        """Allocatable pages (the pool minus the trash page)."""
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.capacity - self.free_pages

    @property
    def shared_pages(self) -> int:
        """Pages mapped by more than one reader (refcount > 1)."""
        with self._lock:
            return int(np.sum(self._refs[1:] > 1))

    def refcount(self, page: int) -> int:
        with self._lock:
            return int(self._refs[page])

    def refcount_histogram(self) -> dict[int, int]:
        """{refcount: page count} over allocated pages (trash excluded)."""
        with self._lock:
            refs = self._refs[1:]
            live = refs[refs > 0]
            counts: dict[int, int] = {}
            for r in live:
                counts[int(r)] = counts.get(int(r), 0) + 1
            return counts

    def snapshot(self) -> dict:
        """Every occupancy/sharing/churn figure under ONE lock hold.

        The per-field properties above each take the lock separately —
        fine for a single gauge, but a multi-field report stitched from
        them can tear (a release between ``used_pages`` and
        ``shared_pages`` makes the sums disagree). ``/debug/kv`` and
        the ledger cross-check read through here so their page math is
        internally consistent by construction."""
        with self._lock:
            refs = self._refs[1:]
            live = refs[refs > 0]
            hist: dict[int, int] = {}
            for r in live:
                hist[int(r)] = hist.get(int(r), 0) + 1
            free = len(self._free)
            return {
                "capacity": self.num_pages - 1,
                "free_pages": free,
                "used_pages": self.num_pages - 1 - free,
                "shared_pages": int(np.sum(refs > 1)),
                "refcount_histogram": hist,
                "allocs": self.allocs,
                "frees": self.frees,
                "alloc_failures": self.alloc_failures,
            }

    # -- alloc / share / release ----------------------------------------------

    def try_alloc(self, n: int) -> list[int] | None:
        """``n`` fresh pages (refcount 1 each), or ``None`` when even the
        ``reclaim`` hook cannot free enough. Never raises — admission
        turns ``None`` into preemption/shed policy."""
        if n <= 0:
            return []
        with self._lock:
            short = n - len(self._free)
        if short > 0 and self.reclaim is not None:
            # outside the lock: reclaim re-enters through free()
            self.reclaim(short)
        with self._lock:
            if len(self._free) < n:
                self.alloc_failures += 1
                return None
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._refs[p] = 1
            self.allocs += n
            return pages

    def alloc(self, n: int) -> list[int]:
        """Like :meth:`try_alloc` but raises :class:`PagePoolExhausted`."""
        pages = self.try_alloc(n)
        if pages is None:
            raise PagePoolExhausted(
                f"page pool exhausted: need {n} pages, "
                f"{self.free_pages} free of {self.capacity}")
        return pages

    def share(self, pages) -> None:
        """One more reader for each page (prefix sharing / index pin)."""
        with self._lock:
            for p in pages:
                if p == TRASH_PAGE:
                    continue
                if self._refs[p] <= 0:
                    raise ValueError(f"share of unallocated page {p}")
                self._refs[p] += 1

    def release(self, pages) -> None:
        """One fewer reader; pages hitting refcount 0 return to the
        free list."""
        with self._lock:
            for p in pages:
                if p == TRASH_PAGE:
                    continue
                r = int(self._refs[p]) - 1
                if r < 0:
                    raise ValueError(f"release of free page {p}")
                self._refs[p] = r
                if r == 0:
                    self._free.append(p)
                    self.frees += 1

    def check_leaks(self, expected_held: int = 0) -> None:
        """Assert the pool accounting is consistent: the total of all
        outstanding refs (trash page excluded) equals ``expected_held``,
        and with zero holders every page is back on the free list.
        The churn test calls this after N admit/finish/shed cycles."""
        with self._lock:
            held = int(np.sum(self._refs[1:]))
            free = len(self._free)
        if held != expected_held:
            raise AssertionError(
                f"page refcount leak: {held} refs outstanding, "
                f"expected {expected_held}")
        if expected_held == 0 and free != self.capacity:
            raise AssertionError(
                f"page leak: {self.capacity - free} pages neither free "
                "nor referenced")


@dataclasses.dataclass
class PagedHit:
    """A paged-admission prefix hit.

    ``pages`` — physical pages already holding the prefix KV (share
    refs were taken by the index lookup; the engine maps them into the
    slot's block table). ``entry`` — a row-based entry instead (kv-pool
    tier or a claimed handoff), to be page-scattered at admission.
    Exactly one of the two is set. ``last_logits`` rides along for
    full-length entries (the direct-insert path samples from it)."""

    length: int
    pages: list[int] | None = None
    entry: object | None = None
    last_logits: object | None = None
    # True for a consume-once handoff claim (``Request.kv_entry``): a
    # dry-pool requeue must stash it BACK on the request — tier hits
    # are re-lookup-able, a dropped claim is a guaranteed local prefill
    external: bool = False


class PagedKV:
    """Device-side paged KV state for one engine: per-layer pools (flat
    by rows, or by pages: :func:`stored_by_pages`) + per-slot block
    tables + the host-side index-array builders the jitted paged
    programs consume.
    """

    def __init__(self, model, *, max_slots: int, cache_len: int,
                 page_size: int, pool_tokens: int, dtype,
                 mesh=None):
        self.page_size = int(page_size)
        self.cache_len = int(cache_len)
        self.max_slots = int(max_slots)
        # logical pages a single slot can ever map
        self.pages_per_slot = pages_for(cache_len, page_size)
        num_pages = pages_for(pool_tokens, page_size) + 1  # + trash page
        self.pool = PagePool(num_pages, page_size)
        # block tables: logical page -> physical page, 0 = unmapped
        self.block_tables = np.zeros(
            (max_slots, self.pages_per_slot), np.int32)
        # pages currently mapped per slot (bt[s, :n] are live)
        self.slot_pages_n = np.zeros((max_slots,), np.int32)
        # one dict of pool buffers per layer, index key dropped (the
        # per-dispatch view carries its own pinned index vector);
        # ``tails``: each buffer's per-token row shape as the model has it
        tpl = model.init_cache(1, self.page_size, dtype=dtype)
        self.n_layers = len(tpl)
        pool_rows = num_pages * self.page_size
        self.tails = [{key: tuple(buf.shape[2:])    # (1, P, *tail)
                       for key, buf in layer.items() if key != "index"}
                      for layer in tpl]
        # layers whose state is bounded are held by slot, not by page
        # (cache_kinds); their buffers' shapes at this cache length
        full, self.by_slot, self.recurrent = cache_kinds(
            model, dtype, self.cache_len)
        paged = [t for t, bounded in zip(self.tails, self.by_slot)
                 if not bounded]
        # the pool's physical form, "rows" | "pages": stored_by_pages
        self.form = "pages" if stored_by_pages(
            t for layer in paged for t in layer.values()) else "rows"
        # paged layers a decode program does NOT gather: the model asked
        # for the pool's pages as they are stored and walks them itself
        # (``reads_pages``; models/layers.py PAGES_KEY). Per layer; only a
        # pool stored by pages has pages to hand over.
        reads = self.form == "pages" and bool(getattr(
            getattr(model, "inner", model), "reads_pages", False))
        self.in_place = [reads and not bounded for bounded in self.by_slot]
        kv = []
        for layer, tails, whole, bounded in zip(tpl, self.tails, full,
                                                self.by_slot):
            bufs = {}
            for key, tail in tails.items():
                if bounded:
                    shape = (self.max_slots,) + tuple(whole[key].shape[1:])
                elif self.form == "pages":
                    shape = (num_pages, self.page_size, lane_whole(tail[0]))
                else:
                    shape = (pool_rows,) + tail
                bufs[key] = jnp.zeros(shape, layer[key].dtype)
            kv.append(bufs)
        if mesh is not None:
            kv = jax.device_put(kv, self._pool_shardings(kv, mesh))
        self.kv = kv
        # ledger account kv_pool.pages: the pools are the one real device
        # allocation that grows with tokens — page/row rates derive from
        # it so every page-count figure converts to bytes the same way
        # everywhere (/debug/kv, /debug/hbm, session pins). The layers
        # held by slot are a second rate, bytes a SLOT, whatever the
        # context: account kv.window_state for the rings,
        # kv.recurrent_state for the states that are replaced.
        self.pool_bytes = sum(
            int(buf.nbytes) for layer, bounded in zip(kv, self.by_slot)
            if not bounded for buf in layer.values())
        self.slot_state_bytes = sum(
            int(buf.nbytes) for layer, bounded in zip(kv, self.by_slot)
            if bounded for buf in layer.values())
        self.slot_bytes = self.slot_state_bytes // self.max_slots
        # the bytes of the layers held by slot that are a recurrent state
        # (no rows)
        self.recurrent_state_bytes = sum(
            int(buf.nbytes) for layer, still in zip(kv, self.recurrent)
            if still for buf in layer.values())
        # rows of the widest ring (0: no layer is a ring)
        self.ring_rows = max(
            (buf.shape[1] for layer, bounded, still in zip(
                kv, self.by_slot, self.recurrent)
             if bounded and not still for buf in layer.values()), default=0)
        self.row_bytes = self.pool_bytes // pool_rows if pool_rows else 0
        self.page_bytes = self.row_bytes * self.page_size
        self._ledger_open = True
        self._book_ledger(1)

    def _book_ledger(self, sign: int) -> None:
        """The pool, the rings held by slot, and the recurrent states held
        by slot: three accounts, booked at build and freed at close."""
        rings = self.slot_state_bytes - self.recurrent_state_bytes
        for account, size in (("kv_pool.pages", self.pool_bytes),
                              ("kv.window_state", rings),
                              ("kv.recurrent_state",
                               self.recurrent_state_bytes)):
            if size:
                get_ledger().book(account, sign * size)

    def close(self) -> None:
        """Release the pool's ledger claim (engine stop). Idempotent —
        a double stop must not double-free the account."""
        if self._ledger_open:
            self._ledger_open = False
            self._book_ledger(-1)

    def view_bytes(self, width: int, n_slots: int | None = None) -> int:
        """Device bytes of one transient gather view: ``n_slots`` rows
        of ``width`` tokens at the pool's per-row rate — what a paged
        dispatch materializes NEXT TO the pool (the coexistence bytes
        ROADMAP item 1 reclaims). Layers held by slot have no view: the
        programs read their rows where they live."""
        s = self.max_slots if n_slots is None else int(n_slots)
        return int(width) * s * self.row_bytes

    @staticmethod
    def _pool_shardings(kv, mesh):
        """KV heads (second-to-last dim of 'k'/'v' pools) shard over the
        mesh's ``model`` axis; everything else replicates — the paged
        mirror of the contiguous engine's ``_cache_shardings``."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        tp = mesh.shape.get("model", 1)
        out = []
        for layer in kv:
            specs = {}
            for key, buf in layer.items():
                if (key in ("k", "v") and tp > 1 and buf.ndim >= 2
                        and buf.shape[-2] % tp == 0):
                    spec = [None] * buf.ndim
                    spec[-2] = "model"
                    specs[key] = NamedSharding(mesh, P(*spec))
                else:
                    specs[key] = NamedSharding(mesh, P())
            out.append(specs)
        return out

    # -- capacity -------------------------------------------------------------

    def fits_ever(self, n_tokens: int) -> bool:
        """Whether a request needing ``n_tokens`` KV rows can EVER be
        admitted (pool capacity, ignoring current occupancy) — the
        api-layer 422 check."""
        return pages_for(n_tokens, self.page_size) <= self.pool.capacity

    def slot_tokens_capacity(self, slot: int) -> int:
        return int(self.slot_pages_n[slot]) * self.page_size

    # -- block-table mutation (engine thread only) ----------------------------

    def map_shared(self, slot: int, pages: list[int]) -> None:
        """Start ``slot``'s table with already-incref'd shared pages."""
        n = len(pages)
        self.block_tables[slot, :n] = pages
        self.slot_pages_n[slot] = n

    def extend(self, slot: int, need_tokens: int) -> bool:
        """Grow ``slot``'s table to cover ``need_tokens`` positions;
        False when the pool (after reclaim) cannot supply the pages —
        the engine then preempts or sheds."""
        target = min(pages_for(need_tokens, self.page_size),
                     self.pages_per_slot)
        cur = int(self.slot_pages_n[slot])
        if target <= cur:
            return True
        pages = self.pool.try_alloc(target - cur)
        if pages is None:
            return False
        self.block_tables[slot, cur:target] = pages
        self.slot_pages_n[slot] = target
        return True

    def release_slot(self, slot: int) -> list[int]:
        """Drop every page mapping of ``slot`` (refcounts decremented;
        exclusively-owned pages return to the free list). Returns the
        released physical pages (tests assert on them)."""
        n = int(self.slot_pages_n[slot])
        pages = [int(p) for p in self.block_tables[slot, :n]]
        self.pool.release(pages)
        self.block_tables[slot, :n] = TRASH_PAGE
        self.slot_pages_n[slot] = 0
        return pages

    def slot_pages(self, slot: int) -> list[int]:
        n = int(self.slot_pages_n[slot])
        return [int(p) for p in self.block_tables[slot, :n]]

    # -- host-side index builders --------------------------------------------

    def gather_idx(self, width: int) -> np.ndarray:
        """(max_slots, width) flat pool-row indices for the contiguous
        view gather: position ``t`` of slot ``s`` reads
        ``bt[s, t // P] * P + t % P`` (unmapped pages -> trash)."""
        P = self.page_size
        t = np.arange(width)
        lp = t // P
        return (self.block_tables[:, lp] * P
                + (t % P)[None, :]).astype(np.int32)

    def view_idx(self, width: int, slots=None) -> np.ndarray:
        """The index argument of a view ``width`` tokens wide over every
        slot (or the listed ``slots``), as this pool's form gathers it: a
        pool stored by pages takes the block table's first ``width /
        page_size`` columns (whole pages; unmapped ones are the trash
        page), a flat one :meth:`gather_idx`'s pool rows."""
        if self.form != "pages":
            idx = self.gather_idx(width)
            return idx if slots is None else idx[slots]
        bt = self.block_tables if slots is None else self.block_tables[slots]
        return bt[:, :pages_for(width, self.page_size)].astype(np.int32)

    def row_gather_idx(self, slot: int, width: int) -> np.ndarray:
        """(1, width) flat indices over one slot (handoff/offload rows)."""
        P = self.page_size
        t = np.arange(width)
        lp = np.minimum(t // P, self.pages_per_slot - 1)
        return (self.block_tables[slot, lp] * P
                + (t % P)).astype(np.int32)[None, :]

    def scatter_idx(self, starts: np.ndarray, valid: np.ndarray,
                    width: int) -> np.ndarray:
        """(max_slots, width) flat pool-row targets for the write-back
        of each row's window ``[starts[s], starts[s] + valid[s])``;
        positions at ``j >= valid[s]`` (and any unmapped page) are
        routed to the trash page."""
        P = self.page_size
        j = np.arange(width)
        pos = starts.astype(np.int64)[:, None] + j[None, :]
        lp = np.minimum(pos // P, self.pages_per_slot - 1)
        phys = np.take_along_axis(
            self.block_tables, lp.astype(np.int64), axis=1)
        keep = j[None, :] < valid[:, None]
        phys = np.where(keep, phys, TRASH_PAGE)
        return (phys * P + pos % P).astype(np.int32)

    def rows_scatter_idx(self, slots: list[int], lengths: list[int],
                         width: int) -> np.ndarray:
        """(B, width) flat targets for scattering B bucket-width row
        sets (one-shot prefill / direct insert): row b's positions
        ``[0, lengths[b])`` land in ``slots[b]``'s pages, padding goes
        to trash."""
        P = self.page_size
        j = np.arange(width)
        out = np.zeros((len(slots), width), np.int64)
        for b, (s, ln) in enumerate(zip(slots, lengths)):
            lp = np.minimum(j // P, self.pages_per_slot - 1)
            phys = self.block_tables[s, lp]
            phys = np.where(j < ln, phys, TRASH_PAGE)
            out[b] = phys * P + j % P
        return out.astype(np.int32)

    # -- snapshots ------------------------------------------------------------

    def debug_snapshot(self) -> dict:
        """The ``GET /debug/kv`` payload: pool occupancy, sharing,
        fragmentation, and per-slot block-table sizes.

        Pool state comes from ONE :meth:`PagePool.snapshot` (a report
        stitched from the per-field properties could tear between lock
        acquisitions), and every page figure is cross-linked to ledger
        account ``kv_pool.pages`` at the pool's own byte rate — so
        ``/debug/kv`` and ``/debug/hbm`` cannot disagree on what a page
        costs."""
        pool = self.pool.snapshot()
        # internal fragmentation: allocated-but-unfilled token slack of
        # the slot-mapped pages (tail of each slot's last page)
        mapped = int(np.sum(self.slot_pages_n))
        return {
            "layout": "paged",
            "page_size": self.page_size,
            "pages_total": pool["capacity"],
            "pages_free": pool["free_pages"],
            "pages_used": pool["used_pages"],
            "pages_shared": pool["shared_pages"],
            "pages_slot_mapped": mapped,
            "refcount_histogram": {
                str(k): v for k, v in
                sorted(pool["refcount_histogram"].items())},
            "alloc_failures": pool["alloc_failures"],
            "block_table_pages_per_slot": [
                int(n) for n in self.slot_pages_n],
            # each buffer's physical form and what one token's row of it
            # holds in the pool (first layer; a "pages" row is padded to
            # whole lane tiles)
            "buffers": {
                key: {"form": self.form,
                      "row_bytes": int(buf.nbytes) // (
                          self.pool.num_pages * self.page_size)}
                for key, buf in self.kv[self.by_slot.index(False)].items()
            } if not all(self.by_slot) else {},
            "ledger_account": "kv_pool.pages",
            "page_bytes": self.page_bytes,
            "pool_bytes": self.pool_bytes,
            "slot_mapped_bytes": mapped * self.page_bytes,
            # the second store: layers whose state is bounded (a window
            # layer's ring), held by slot whatever the context
            "slot_state": {
                "layers": sum(self.by_slot),
                "paged_layers": self.n_layers - sum(self.by_slot),
                # of the layers held by slot: a recurrent state (replaced,
                # not appended)
                "recurrent_layers": sum(self.recurrent),
                "recurrent_bytes": self.recurrent_state_bytes,
                "recurrent_ledger_account": "kv.recurrent_state",
                "ledger_account": "kv.window_state",
                "slot_bytes": self.slot_bytes,
                "bytes": self.slot_state_bytes,
                "buffers": {
                    key: {"shape": list(buf.shape)} for key, buf in
                    next((layer for layer, bounded in zip(
                        self.kv, self.by_slot) if bounded and layer),
                        {}).items()},
            },
        }
