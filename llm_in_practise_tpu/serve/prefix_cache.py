"""Prefix KV caching — the reference platform's L1 cache stage, in-engine.

The reference gets prompt-prefix reuse from vLLM's automatic prefix
caching (``07-L1-Cache/vllm-statefulset-apc.yaml`` —
``--enable-prefix-caching``) and from LMCache's remote KV pool
(``vllm-statefulset-lmcache.yaml:65-111``); warm-prefix TTFT drops from
800–1500 ms to 50–200 ms (``Inference_Platfrom/README.md:1336-1341``).

Here the same idea fits the slot engine's static-shape world: after a
prompt prefills, its per-layer KV rows (padded to the prefill bucket) are
kept in an LRU keyed by the token tuple. A new request reuses the longest
cached strict prefix — the engine then prefills only the suffix, with the
prefix rows pre-inserted and the cache index offset (positions and causal
masking follow from the index, so the math is identical to a cold
prefill). A full-prompt hit skips prefill entirely (the stored
last-position logits seed the first sampled token).

Eviction: LRU by total cached tokens. Entries are device arrays — the
budget is HBM, so default caps are modest; evictions flow into the
:mod:`.kv_pool` tiers when one is attached (the LMCache handoff).

:class:`PrefixLRU` is the shared store — the host pool and the remote
pool server in :mod:`.kv_pool` reuse the same budget/eviction/matching
logic with different value types.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from collections import OrderedDict

import jax


@dataclasses.dataclass
class PrefixEntry:
    length: int           # true token count of the cached prefix
    bucket: int           # padded length of the stored rows
    rows: list            # per-layer {key: (1, bucket, ...) device array}
    last_logits: object   # (1, vocab) logits at the final prefix position
    # The KV buffers' slot axis. Every engine writes 0 (the unrolled
    # layout it serves); the field stays on the kv_pool wire because a
    # shared pool may hold stacked rows (1) an older replica wrote,
    # whose shapes are transposed — readers refuse anything but 0.
    slot_axis: int = 0
    # Page-wise entries (kv_layout="paged" producers): rows span
    # ceil(length / page_size) * page_size positions — only live pages,
    # not a pow2 bucket. 0 = legacy bucket-width entry. Consumers of
    # either layout accept both; the field exists so wire accounting
    # (kv_pool) can count pages and so a reader knows the width law.
    page_size: int = 0


class PrefixLRU:
    """Token-budget LRU keyed by exact token tuples, with
    longest-strict-prefix lookup.

    Generic over the value type: ``length_of(value)`` must return the
    value's true token count. ``on_evict(key, value)`` fires (outside the
    lock) for every budget eviction — tier handoff hooks attach here.
    """

    def __init__(self, *, max_tokens: int, min_prefix: int,
                 length_of=None, on_evict=None):
        self.max_tokens = max_tokens
        self.min_prefix = min_prefix
        self.on_evict = on_evict
        self._length_of = length_of or (lambda v: v.length)
        # internal lock: the owner's worker thread mutates while /metrics
        # (or another engine thread) reads
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()  # guarded-by: _lock
        self._lock = threading.Lock()
        self._total_tokens = 0  # guarded-by: _lock
        self.hits = 0
        self.misses = 0

    @property
    def cached_tokens(self) -> int:
        with self._lock:
            return self._total_tokens

    @property
    def n_entries(self) -> int:
        # deliberately not __len__: an empty cache must stay truthy
        # (callers write ``prefix_cache or None`` to normalize False)
        with self._lock:
            return len(self._entries)

    def lookup(self, prompt_ids, usable=None):
        """Longest cached value that is a prefix of ``prompt_ids``.

        ``usable(value)`` (optional) filters candidates — the engine uses
        it to reject prefixes whose suffix prefill wouldn't fit the cache.
        """
        prompt = tuple(prompt_ids)
        with self._lock:
            best_key, best = None, None
            for key, value in self._entries.items():
                length = self._length_of(value)
                if length < self.min_prefix or length > len(prompt):
                    continue
                if best is not None and length <= self._length_of(best):
                    continue
                if prompt[:length] != key:
                    continue
                if usable is not None and not usable(value):
                    continue
                best_key, best = key, value
            if best is None:
                self.misses += 1
                return None
            self._entries.move_to_end(best_key)
            self.hits += 1
            return best

    def put(self, prompt_ids, value) -> None:
        length = self._length_of(value)
        if length < self.min_prefix:
            return
        key = tuple(prompt_ids[:length])
        evicted: list[tuple[tuple, object]] = []
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._total_tokens -= self._length_of(old)
            self._entries[key] = value
            self._total_tokens += length
            while self._total_tokens > self.max_tokens and len(self._entries) > 1:
                ekey, evalue = self._entries.popitem(last=False)
                self._total_tokens -= self._length_of(evalue)
                evicted.append((ekey, evalue))
        if self.on_evict is not None:
            for ekey, evalue in evicted:
                self.on_evict(ekey, evalue)

    def peek(self, key) -> object | None:
        """Exact-key read without touching LRU order (accounting hooks)."""
        with self._lock:
            return self._entries.get(tuple(key))

    def pop_lru(self):
        """Evict and return the least-recently-used (key, value), or None.

        Unlike :meth:`put`'s budget loop this will empty the store —
        callers enforcing an external budget (bytes) own the floor."""
        with self._lock:
            if not self._entries:
                return None
            key, value = self._entries.popitem(last=False)
            self._total_tokens -= self._length_of(value)
        if self.on_evict is not None:
            self.on_evict(key, value)
        return key, value

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._total_tokens = 0


class PrefixCache(PrefixLRU):
    """The engine's L1: device-array prefix entries + reuse accounting."""

    def __init__(self, *, max_tokens: int = 32768, min_prefix: int = 16,
                 on_evict=None):
        super().__init__(max_tokens=max_tokens, min_prefix=min_prefix,
                         on_evict=on_evict)
        self.full_hits = 0
        self.tokens_saved = 0

    def lookup(self, prompt_ids, usable=None) -> PrefixEntry | None:
        entry = super().lookup(prompt_ids, usable)
        if entry is not None:
            self.tokens_saved += entry.length
            if entry.length == len(prompt_ids):
                self.full_hits += 1
        return entry


@dataclasses.dataclass
class _PageEntry:
    eid: int              # this entry's chain id (children key on it)
    page: int             # physical page holding the KV rows
    parent_eid: int       # 0 = chain root


class PagedPrefixIndex:
    """Page-granular prefix sharing for ``kv_layout="paged"`` engines —
    the vLLM automatic-prefix-caching idiom at its native grain.

    Where :class:`PrefixCache` stores COPIED rows keyed by whole token
    tuples (hit = longest exact entry, all-or-nothing per entry), this
    index maps **hash-per-page chains to the physical pages
    themselves**: page ``i`` of a prompt is keyed by
    ``(parent_entry_id, tokens_of_page_i)``, where ``parent_entry_id``
    identifies the entry for pages ``0..i-1``. A lookup walks the chain
    and returns every consecutively matched FULL page — a new request
    sharing 3 of a cached prompt's 5 pages reuses exactly those 3
    physical pages (refcounted, zero device copies) and prefills only
    the tail. The exact-token chain keys make collisions impossible (a
    content-hash scheme would need a verify pass; vLLM compares block
    tokens the same way).

    Copy-on-write contract: only FULL pages are ever indexed, a hit is
    capped at ``(len(prompt) - 1) // page_size`` pages (the engine must
    recompute at least the final position to obtain next-token logits),
    and slots therefore never write inside a shared page — the engine's
    defensive fork (:meth:`InferenceEngine._paged_cow_fork`) covers any
    future path that would.

    Refcounts: the index holds ONE pool reference per indexed page
    (taken at :meth:`register`); every lookup hit takes one more per
    matched page on the caller's behalf. Eviction (LRU under a token
    budget, or on-demand through :class:`~.paged_kv.PagePool`'s
    ``reclaim`` hook when admission runs dry) drops the index's
    reference — pages still mapped by live slots survive until those
    slots release them. Evicting an entry cascades to its descendants:
    a child whose parent is gone can never match again, and letting it
    linger would pin its page forever.

    Counter names mirror :class:`PrefixCache` so the
    ``llm_prefix_cache_*`` metric plumbing reads either implementation
    unchanged; ``full_hits`` counts maximal hits (every matchable page
    of the prompt matched).
    """

    def __init__(self, pool, *, max_tokens: int = 32768,
                 min_prefix: int | None = None):
        self.pool = pool
        self.page_size = pool.page_size
        self.max_tokens = max_tokens
        self.min_prefix = (min_prefix if min_prefix is not None
                           else pool.page_size)
        self._lock = threading.Lock()
        # (parent_eid, page-token tuple) -> _PageEntry, LRU-ordered
        self._entries: "OrderedDict[tuple, _PageEntry]" = OrderedDict()  # guarded-by: _lock
        self._children: dict[int, list[tuple]] = {}  # guarded-by: _lock
        self._eid = itertools.count(1)
        self.hits = 0           # guarded-by: _lock
        self.misses = 0         # guarded-by: _lock
        self.full_hits = 0      # guarded-by: _lock
        self.tokens_saved = 0   # guarded-by: _lock

    @property
    def n_entries(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def cached_tokens(self) -> int:
        with self._lock:
            return len(self._entries) * self.page_size

    def _chain_keys(self, token_ids):
        """Yield each full page's ``(page_index, tokens)`` in order."""
        P = self.page_size
        for i in range(len(token_ids) // P):
            yield i, tuple(token_ids[i * P: (i + 1) * P])

    def lookup(self, prompt_ids) -> list[int]:
        """Physical pages holding the longest indexed full-page prefix
        of ``prompt_ids`` (possibly empty). One pool reference per
        returned page is taken FOR THE CALLER — map them into a block
        table or release them."""
        plen = len(prompt_ids)
        # at least the last position must be recomputed for its logits
        max_pages = max(0, (plen - 1) // self.page_size)
        pages: list[int] = []
        with self._lock:
            parent = 0
            for i, toks in self._chain_keys(prompt_ids):
                if i >= max_pages:
                    break
                entry = self._entries.get((parent, toks))
                if entry is None:
                    break
                self._entries.move_to_end((parent, toks))
                pages.append(entry.page)
                parent = entry.eid
            if len(pages) * self.page_size < self.min_prefix:
                # too-short hits aren't worth the bookkeeping — the
                # same floor PrefixCache applies (no refs taken yet:
                # share() runs below, only for returned pages)
                pages = []
            if not pages:
                self.misses += 1
                return []
            self.hits += 1
            if len(pages) == max_pages:
                self.full_hits += 1
            self.tokens_saved += len(pages) * self.page_size
        self.pool.share(pages)
        return pages

    def register(self, token_ids, pages: list[int]) -> int:
        """Index every full page of ``token_ids`` whose chain position
        is not yet present; ``pages[i]`` must be the physical page
        holding positions ``[i*P, (i+1)*P)``. Returns how many new
        entries were created (each pinned with one pool reference)."""
        if len(token_ids) < self.min_prefix:
            return 0
        new_pages: list[int] = []
        evict: list[int] = []
        with self._lock:
            parent = 0
            created = 0
            for i, toks in self._chain_keys(token_ids):
                if i >= len(pages):
                    break
                key = (parent, toks)
                entry = self._entries.get(key)
                if entry is not None:
                    # chain position already indexed (maybe by another
                    # slot's identical prefix) — reuse ITS entry; the
                    # registering slot keeps its private copy
                    self._entries.move_to_end(key)
                    parent = entry.eid
                    continue
                entry = _PageEntry(eid=next(self._eid),
                                   page=int(pages[i]),
                                   parent_eid=parent)
                self._entries[key] = entry
                self._children.setdefault(parent, []).append(key)
                new_pages.append(entry.page)
                parent = entry.eid
                created += 1
            while (len(self._entries) * self.page_size > self.max_tokens
                   and len(self._entries) > 1):
                evict.extend(self._evict_lru_locked())
        if new_pages:
            self.pool.share(new_pages)
        if evict:
            self.pool.release(evict)
        return created

    def _evict_locked(self, key) -> list[int]:
        """Remove ``key`` and every descendant; returns their pages
        (caller releases OUTSIDE the lock — PagePool has its own).
        Iterative worklist, NOT recursion: one long conversation indexes
        as one parent-child chain, so a cache_len=32K/page_size=16 chain
        root has ~2K descendants — deeper than Python's recursion
        limit."""
        root = self._entries.get(key)
        if root is None:
            return []
        siblings = self._children.get(root.parent_eid)
        if siblings is not None:
            try:
                siblings.remove(key)
            except ValueError:
                pass
        pages: list[int] = []
        work = [key]
        while work:
            entry = self._entries.pop(work.pop(), None)
            if entry is None:
                continue
            pages.append(entry.page)
            work.extend(self._children.pop(entry.eid, []))
        return pages

    def _evict_lru_locked(self) -> list[int]:
        if not self._entries:
            return []
        key = next(iter(self._entries))
        return self._evict_locked(key)

    def evict_pages(self, n: int) -> int:
        """Reclaim hook for :class:`~.paged_kv.PagePool`: drop LRU
        entries until ``n`` index references were released (the pages
        become allocatable once no slot maps them). Returns how many
        references were dropped."""
        dropped: list[int] = []
        with self._lock:
            while len(dropped) < n and self._entries:
                dropped.extend(self._evict_lru_locked())
        if dropped:
            from llm_in_practise_tpu.obs.hbm import get_ledger

            get_ledger().note_reclaim("kv_pool.pages", "prefix_evict")
            self.pool.release(dropped)
        return len(dropped)

    def clear(self) -> None:
        with self._lock:
            pages = [e.page for e in self._entries.values()]
            self._entries.clear()
            self._children.clear()
        if pages:
            self.pool.release(pages)


def slice_cache_rows(prefill_cache, bucket: int) -> list:
    """Keep only the first ``bucket`` rows of each layer's KV buffers
    (drop the per-layer index — the entry carries the true length)."""
    rows = []
    for layer in prefill_cache:
        rows.append({
            k: jax.lax.slice_in_dim(v, 0, bucket, axis=1)
            for k, v in layer.items() if k != "index"
        })
    return rows
