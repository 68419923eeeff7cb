"""Step statistics of models whose programs count what they did.

Two things the ordinary decode / chunk / fused mixed programs cannot tell
the host by their tokens alone, for two kinds of model:

- a ROUTED model outside the block step (``models/deepseek_v3.py``): how
  the step's tokens loaded the experts held here. The model counts that on
  the device; the counts leave the program through the transient cache
  VIEW: the model's ``step_stats(rows)`` gives zeroed entries per layer,
  the paged programs add them to the view they gather
  (``InferenceEngine._paged_view``), the routed layers fill them, and the
  programs return them beside their tokens (:meth:`StepStats.of_view`).
  They are no part of the page pool, and a model without ``step_stats``
  gets none of this: its programs lower exactly as before.
- a LATENT model (a cache row that is one latent, no ``k`` / ``v``): how
  many cache rows a decode step's attention really needed against how
  many the pow2 view made it read or, where the decode reads the latent
  pages in place (``models/deepseek_v3.py`` declares ``reads_pages``),
  against the rows one layer's reader copied, with ``global_pages_read``
  the pages all its layers copied, as below.
- a model with WINDOW layers held by slot beside its paged global layers
  (``models/mimo_v2.py``; ``PagedKV.by_slot``): the same two numbers for
  the global layers (one pair of counters, named ``latent_*`` or
  ``global_*`` after the model's cache), and only here the ring rows its
  window layers attended against the ring rows they READ (every slot's
  whole ring, idle slots too: ``models/afmoe.py``'s is 4,096 rows) and a
  chunk's (query, key) pairs under the band. Where the decode reads the
  global layers' pages in place (the model declares ``reads_pages``), the
  view's rows are the rows one layer's reader copied (live rows' lengths
  up to whole blocks of pages) and ``global_pages_read`` the pages all of
  them copied.

- a model with RECURRENT layers and a CROSS-DECODER (``models/phi4flash.py``;
  the model's ``census()`` gives how many layers read the shared view): the positions its chunk
  rows scanned, the decode-plane rows whose state moved against those it
  was held for, the rows that passed the self-decoder and the cross-decoder
  (a prompt's chunk passes the second at ONE position, the one it ends in:
  ``cross_decoder_prefill_rows``), and the true lengths the layers that
  read the one paged layer attended, times those readers, and, where the
  decode reads that layer's pages in place, the pages those readers copied
  (``shared_kv_pages_read``; ``global_view_tokens`` is then the rows one
  reader copied: live rows' lengths up to whole blocks). All booked
  by the host from what it dispatched; such a model counts nothing on the
  device and routes nothing (``load`` is None).
- a model whose RECURRENT layers hold a state of another name
  (``models/lfm2_moe.py``: a short convolution's two-row tail, ``conv``;
  routed besides, no ring, no cross-decoder): the decode-plane rows whose
  state moved against those it was held for, ``conv_state_rows_advanced``
  / ``conv_state_rows_held``, and the state's bytes, ``conv_state_bytes``.

**Every name follows from the cache template** (what
``paged_kv.cache_kinds`` reads), never from which model it is: the paged
layers' pair of counters is ``latent_*`` where a paged row is ONE buffer (a
latent, no ``k`` beside a ``v``) and ``global_*`` otherwise; the recurrent
counters carry the state's own name (``ssm`` where a recurrent layer holds a
buffer of that name, else its first buffer's); rings' counters exist where
a ring does; a cross-decoder's where the model's ``census()`` names its
``shared_readers``.

Every model here sends its prompts through the chunk program, one
chunk-wide trip a row: ``prefill_chunk_tokens`` (real prompt tokens)
against ``prefill_chunk_capacity`` (trips x the chunk's width) is what a
prompt shorter than a chunk pays.

:class:`RoutingLoad` is the one place expert load is booked; the
block-diffusion decoder (``serve/block_step.py``) books its passes into
one too, and ``/metrics`` reads whichever the engine has.
"""

from __future__ import annotations

import jax
import numpy as np

from llm_in_practise_tpu.models.layers import LOAD_KEY, ROUTE_KEY
from llm_in_practise_tpu.ops import swa_attention as swa


class RoutingLoad:
    """Lifetime expert-load counters (engine-thread writes, scrape-side
    reads of monotone numbers). ``layer_passes`` counts (layer, pass)
    pairs: one run of one routed layer over one batch of tokens, the
    unit that streams the touched experts' weights once."""

    def __init__(self, n_experts: int):
        self.n_experts = int(n_experts)     # experts held here
        self.layer_passes = 0
        self.assignments = 0
        self.experts_touched = 0
        self.max_load = 0
        self.mean_load = 0.0

    def book(self, layer_passes: int, assignments: int, touched: int,
             max_load: int) -> None:
        """``touched`` / ``max_load``: distinct experts that received a
        token and the busiest expert's load, each summed over the
        (layer, pass) pairs."""
        self.layer_passes += int(layer_passes)
        self.assignments += int(assignments)
        self.experts_touched += int(touched)
        self.max_load += int(max_load)
        if self.n_experts:
            self.mean_load += float(assignments) / self.n_experts

    def book_counts(self, counts: np.ndarray) -> None:
        """``counts`` (layer passes, experts): assignments per expert."""
        self.book(counts.shape[0], counts.sum(), (counts > 0).sum(),
                  counts.max(axis=1).sum())

    def counters(self) -> dict:
        return {"moe_layer_passes": self.layer_passes,
                "moe_assignments": self.assignments,
                "moe_experts_touched": self.experts_touched,
                "moe_max_expert_load": self.max_load,
                "moe_mean_expert_load": self.mean_load}


def stats_model(model):
    """The core model if it exports step statistics or keeps a latent
    cache, else None."""
    core = getattr(model, "inner", model)
    return core if hasattr(core, "step_stats") else None


class StepStats:
    """One engine's step statistics: the traced helpers its paged
    programs call, and the host-side booking of what they return."""

    def __init__(self, engine, core):
        self.eng, self.core = engine, core
        self.check_engine(engine)
        tpl = core.step_stats(1)
        self.routed = [i for i, d in enumerate(tpl) if d]
        self.load = (RoutingLoad(core.config.held[1]) if self.routed
                     else None)
        # rows of a window layer's ring (0: no layer is held by slot)
        self.ring_rows = engine.paged.ring_rows
        # one attended / view pair and one count of causal pairs, under
        # the family name that fits the model's cache: counter, step-record
        # key and ``/metrics`` family (``llm_<name>_total``) are one name.
        # A paged row that is ONE buffer is a latent; rows of keys beside
        # values are a global layer's
        paged = engine.paged
        latent = all(len(tails) == 1 for tails, bounded in zip(
            paged.tails, paged.by_slot) if not bounded)
        family = "latent" if latent else "global"
        self.attended_key = f"{family}_tokens_attended"
        self.view_key = f"{family}_view_tokens"
        self.pairs_key = ("prefill_qk_pairs" if latent
                          else "prefill_global_pairs")
        for key in (self.attended_key, self.view_key, self.pairs_key,
                    "prefill_keys_read", "prefill_chunk_tokens",
                    "prefill_chunk_capacity"):
            setattr(self, key, 0)
        if self.ring_rows:      # what only a window layer has
            self.window_rows_attended = 0
            self.window_ring_rows_read = 0
            self.prefill_band_pairs = 0
            self.prefill_band_keys_read = 0
        # recurrent layers: counted under the state's own name, read off
        # the template (None: the model has none)
        names = [key for layer, still in zip(paged.kv, paged.recurrent)
                 if still for key in layer]
        self.state = next((n for n in ("ssm", *names) if n in names), None)
        if self.state:
            self.advanced_key = f"{self.state}_state_rows_advanced"
            self.held_key = f"{self.state}_state_rows_held"
            setattr(self, self.advanced_key, 0)
            setattr(self, self.held_key, 0)
            setattr(self, f"{self.state}_state_bytes",
                    paged.recurrent_state_bytes)
        # a cross-decoder: how many layers read the ONE paged layer's view
        # (the model's census; 0: none)
        self.shared = (core.census()["shared_readers"]
                       if hasattr(core, "census") else 0)
        if self.shared:
            for key in ("ssm_scan_tokens", "self_decoder_rows",
                        "cross_decoder_rows", "cross_decoder_prefill_rows",
                        "shared_kv_rows_attended"):
                setattr(self, key, 0)
        # a model whose decode reads its paged layers' pages in place
        # (``PagedKV.in_place``): the pages a reader copies at once (the
        # kernel's block, at most a slot's pages), else 0; how many layers
        # read them (a cross-decoder's all read ONE layer's), and the
        # family name their copies are counted under
        self.page_block = swa.paged_block_pages(
            engine.paged.pages_per_slot) if any(engine.paged.in_place) else 0
        self.block_rows = self.page_block * engine.paged.page_size
        self.page_readers = self.shared or sum(engine.paged.in_place)
        self.pages_key = ("shared_kv_pages_read" if self.shared
                          else "global_pages_read")
        setattr(self, self.pages_key, 0)
        # reference comparisons (tests, the benchmark's check) set this
        # to a list: every booked program then appends {"kind", "uids":
        # {slot: request uid} at the dispatch, "route": per part (routed
        # layers, rows, k) experts each row's last position chose,
        # "last_logits": {slot: (vocab,)} of the prompts a chunk or mixed
        # program finished}. None: nothing kept.
        self.capture = None

    @staticmethod
    def check_engine(engine, who: str = "latent / routed model") -> None:
        """Build-time refusals: what a latent cache, a held share of
        routed experts, or (``who`` says which) layers held by slot
        cannot meet yet, each by name."""
        def no(what: str, why: str):
            raise ValueError(f"{who}: {what} is not supported — {why}")

        if engine.paged is None:
            no("kv_layout='contiguous'",
               "the step statistics ride the paged programs' view, and a "
               "layer held by slot exists beside a page pool only; use "
               "kv_layout='paged'")
        if engine.mesh is not None:
            no("a device mesh (tensor parallelism)",
               "the grouped expert kernel is not partitioned and a latent "
               "row has no head axis to shard; experts exchanged between "
               "chips are future work (ROADMAP M6)")
        if engine.speculative_k is not None or engine.draft_model is not None:
            no("speculative decoding",
               "the speculative round's programs return no routing "
               "statistics, no self-draft layer is built (ROADMAP M5), and "
               "a rejected draft would have to roll a recurrent state back")
        if engine.adapter_registry is not None:
            no("multi-LoRA", "the adapter twins know dense projections only")
        if engine.kv_pool is not None or engine.session_store is not None:
            no("tiered KV / the session store",
               "their entries and byte accounting assume k / v rows")
        if engine.role != "both" or engine.handoff is not None:
            no("disaggregated prefill/decode",
               "a handed-off entry is k / v rows")
        if any(engine.paged.by_slot) and engine.prefix_cache is not None:
            no("the prefix cache (shared pages, copy-on-write forks)",
               "a layer held by slot keeps only its last rows: a request "
               "that maps another's prefix pages would have no window "
               "state at the end of that prefix")

    # --- inside the jitted programs ------------------------------------------

    def view_entries(self, rows: int) -> list[dict]:
        return self.core.step_stats(rows)

    def of_view(self, view) -> list[dict]:
        """The routed layers' entries of a cache view a body returned."""
        return [{k: view[i][k] for k in (LOAD_KEY, ROUTE_KEY)}
                for i in self.routed]

    def zero_rows(self) -> list[dict]:
        """The row loop's accumulator: loads summed over its trips,
        routes by slot."""
        return self.of_view(self.core.step_stats(self.eng.max_slots))

    @staticmethod
    def add_row(acc, one, slot):
        """``acc`` after a trip whose one-row view returned ``one``."""
        return [{LOAD_KEY: a[LOAD_KEY] + o[LOAD_KEY],
                 ROUTE_KEY: jax.lax.dynamic_update_slice_in_dim(
                     a[ROUTE_KEY], o[ROUTE_KEY], slot, axis=0)}
                for a, o in zip(acc, one)]

    # --- on the host -----------------------------------------------------------

    def _count(self, **counts) -> None:
        """Add to the lifetime counters and to the step's record."""
        for key, n in counts.items():
            setattr(self, key, getattr(self, key) + n)
        self.eng.steptrace.note_extra(**counts)

    def note_decode_view(self, active, width: int) -> None:
        """A decode (or mixed step's decode half) of one token a row over
        ``active`` at view width ``width``: the rows its attention needed
        against the rows of the slot plane's view, or against the rows a
        decode that reads pages in place copied (and, with window
        layers, the ring rows those layers attended against the rows
        they read: every slot's whole ring)."""
        eng = self.eng
        lens = [int(eng.slot_len[s]) + 1 for s in active]
        counts = {self.attended_key: sum(lens),
                  self.view_key: eng.max_slots * int(width)}
        if self.page_block:
            # pages read where they lie: each live row's length up to the
            # whole blocks its readers copy, nothing for an idle row
            pages = sum(-(-length // self.block_rows) for length in lens
                        ) * self.page_block
            counts[self.view_key] = pages * eng.paged.page_size
            counts[self.pages_key] = pages * self.page_readers
        if self.ring_rows:
            counts["window_rows_attended"] = sum(
                min(length, self.ring_rows) for length in lens)
            counts["window_ring_rows_read"] = eng.max_slots * self.ring_rows
        if self.state:
            # the state is held for every row of the plane; only the live
            # rows' moves
            counts[self.advanced_key] = len(lens)
            counts[self.held_key] = eng.max_slots
        if self.shared:
            # every row of the plane passes both decoders
            counts.update(
                self_decoder_rows=eng.max_slots,
                cross_decoder_rows=eng.max_slots,
                shared_kv_rows_attended=sum(lens) * self.shared)
        self._count(**counts)

    def note_chunk_rows(self, entries, finishing: int = 0) -> None:
        """A chunk or mixed dispatch advances ``entries`` ((slot, state,
        chunk) triples): the (query, key) pairs its causal attention
        covers (query ``i`` of a chunk that starts at ``done`` sees
        ``done + i + 1`` keys) and the cache rows it reads. With window
        layers: the causal pairs are the global layers', and a window
        layer's query sees ``min(done + i + 1, ring rows)``. A row is
        one trip of the chunk's width, however short its chunk."""
        entries = list(entries)
        width = self.eng.chunked_prefill or max(len(c) for _, _, c in entries)
        counts = {
            "prefill_chunk_tokens": sum(len(c) for _, _, c in entries),
            "prefill_chunk_capacity": len(entries) * int(width),
            self.pairs_key: sum(
                len(c) * st["done"] + len(c) * (len(c) + 1) // 2
                for _, st, c in entries),
            "prefill_keys_read": sum(st["done"] + len(c)
                                     for _, st, c in entries)}
        if self.ring_rows:
            w = self.ring_rows
            band = band_keys = 0
            for _, st, c in entries:
                # queries whose whole band exists, then the ramp before
                ramp = min(max(w - 1 - st["done"], 0), len(c))
                first = st["done"] + 1
                band += (ramp * (2 * first + ramp - 1) // 2
                         + (len(c) - ramp) * w)
                band_keys += len(c) + min(st["done"], w - 1)
            counts.update(prefill_band_pairs=band,
                          prefill_band_keys_read=band_keys)
        if self.shared:
            # a chunk's positions pass the self-decoder; the cross-decoder
            # sees one position of each prompt that ENDS here
            tokens = counts["prefill_chunk_tokens"]
            counts.update(ssm_scan_tokens=tokens, self_decoder_rows=tokens,
                          cross_decoder_rows=int(finishing),
                          cross_decoder_prefill_rows=int(finishing))
        self._count(**counts)

    def pend(self, kind: str, stats, last=None, finishing=()):
        """A program's statistics output (device arrays) as issued, for
        :meth:`book` once the program is read; ``last`` / ``finishing``:
        a chunk or mixed program's last-position logits and the (slot,
        request) pairs it finished, read only under ``capture``. The
        requests named are those that take part in the program, as the
        slots stand at ISSUE: a row at its deterministic end, or one whose
        stream ended while a later program still ran it, is left out."""
        if not stats:
            return None     # a program without the output (a masked twin)
        kept = None
        if self.capture is not None:
            eng = self.eng
            # a prompt admitted through the chunk program holds its slot
            # only once it is activated: ``finishing`` names it
            kept = (last, [slot for slot, _ in finishing],
                    {**{s: r.uid for s, r in enumerate(eng.slot_req)
                        if r is not None and eng.slot_closing[s] is None
                        and s not in eng._zombies},
                     **{slot: req.uid for slot, req in finishing}})
        return kind, stats, kept

    @staticmethod
    def counted(pended):
        """What :meth:`book` wants fetched of ``pended`` (the caller's
        one fetch takes it along with the program's tokens)."""
        return None if pended is None else pended[1]

    def book(self, pended, parts) -> None:
        """``pended``'s program has been read, and ``parts`` is what it
        counted, on the host: book it, in the locked step that emits
        the program's tokens."""
        if pended is None:
            return
        kind, _, kept = pended
        # parts: one per trunk the program ran (a mixed program's chunk
        # rows, then its decode half), each a list of the routed layers'
        # entries
        if self.load is not None:
            loads = np.sum([layer[LOAD_KEY] for part in parts
                            for layer in part], axis=0)
            self.load.book(*(int(v) for v in loads))
            self.eng.steptrace.note_extra(
                moe_layer_passes=int(loads[0]),
                moe_assignments_held=int(loads[1]),
                moe_experts_touched=int(loads[2]),
                moe_max_expert_load=int(loads[3]))
        if kept is not None and self.capture is not None:
            last, slots, uids = kept
            self.capture.append({
                "kind": kind, "uids": uids,
                "route": [np.stack([layer[ROUTE_KEY] for layer in part])
                          for part in parts] if self.routed else [],
                # reference comparisons only
                "last_logits": {
                    s: np.asarray(last[s])  # graftlint: disable=host-sync
                    for s in slots}})
