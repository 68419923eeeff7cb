"""Block-diffusion decoding in the engine: a pass reveals part of a block,
it does not commit one token.

A model with ``block_length`` B > 1 (models/sdar_moe.py) generates a block
of B positions at a time. A slot's decode input is that block: some
positions revealed, the others ``<|MASK|>``. One **denoise pass** runs the
block against the slot's stored K/V under the block-causal mask, takes a
candidate token and its confidence at every masked position, and reveals
some of them (``low_confidence_static``: the ``B / T`` most confident at
each of ``T`` steps; ``low_confidence_dynamic``: every one above the
threshold, or the ``B / T`` most confident if fewer clear it). When the
input block holds no mask, the pass is a **commit pass**: its K/V are
stored, its tokens stream to the client, the slot advances by B and opens
an all-mask block. So passes != tokens: a block costs 2 to ``T + 1``
passes, tokens reach the client B at a time, and TTFT is "first block
committed".

:class:`BlockDecoder` is what ``InferenceEngine`` becomes for such a
model: the engine keeps admission, buckets, the page pool, one-shot and
chunked prefill (over the prompt's WHOLE blocks; the ``P mod B``
remaining prompt tokens open the first block as already-revealed
positions), the finish funnel and the loop that issues and retires
programs; this class owns the per-slot block state and the one jitted
program of a dispatch (``_paged_block_fn``). Rows are at independent
phases: in one dispatch some rows commit and the others reveal.

**A pass is issued before the pass before it is read** (the engine's
"issue and retire", ``InferenceEngine._fly`` / ``_retire``; a
``_Flight`` of kind ``block``):

- **The block plane stays on the device.** The program returns every
  row's block after the pass, ``(tokens, revealed)``, and the next pass
  takes them as they are. Where the HOST knows a row's block better (one
  opened at activation with the prompt's remainder revealed; the
  all-mask block that follows a commit) its values ride in as a ``fix``:
  a flag a slot and the ``(slots, B)`` planes, always passed (columns of
  the pass's ``plan``, the ONE array the host sends a pass) and applied
  by a ``where`` at the program's head, so the program has one form. An
  idle row keeps what the device holds (its quota is 0: it comes back
  unchanged and writes to the trash page).
- **The host keeps the schedule, not the values.** Under the static rule
  a pass reveals exactly ``min(quota, masked)`` positions a row, so a
  per-slot COUNT of revealed positions says which rows commit in which
  pass; lengths, block numbers, budgets and the ``length`` / ``cache``
  ends follow from it. That half of a pass runs at ISSUE
  (:meth:`BlockDecoder.issue`), with no value of the pass before.
- **RETIRE** (:meth:`BlockDecoder.retire`) reads pass n after pass n + 1
  was issued: ONE ``device_get`` (tokens, revealed flags, expert ids;
  the logits too for a reference comparison), then the half that needs
  values: the reveal log, a committed block's stream up to EOS, the
  routing load, the counters. A row whose stream ends in EOS there has
  already run in pass n + 1: a denoise pass over the next block whose
  K/V went to the trash page. That pass is discarded and counted, and
  the slot and its pages are released when it is read (a zombie, as in
  ``InferenceEngine._retire``).
- **What drains**: a step in which any ready row decodes under
  ``low_confidence_dynamic`` (how many positions clear the threshold is
  a device value, so the next pass's commit set is not the host's to
  know: ``block_dynamic``), an admission's one-shot prefill
  (``oneshot_prefill``) and a chunk beside a pass (``two_dispatch``).
  The same two halves then run with nothing in between: the program is
  read before the next is planned, as the serial step did. No option
  chooses: the rows' own ``remasking`` does.

The K/V of a denoise pass are used inside the pass and not kept: the
host routes their write-back to the trash page (``valid`` = 0), so only a
commit pass's window reaches the slot's pages.

The engine keeps each block's revealed flags ITSELF and never infers
them from ``token == mask id``: a prompt that happens to hold that id is
served like any other.

What a block-diffusion engine refuses, at build or at submit, is listed
in :meth:`BlockDecoder.check_engine` and :meth:`BlockDecoder.check_submit`.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from llm_in_practise_tpu.infer.sampling import (
    sample_token_batched,
    sampler_tier_name,
)
from llm_in_practise_tpu.models.sdar_moe import REMASKING
from llm_in_practise_tpu.serve.step_stats import RoutingLoad


def unwrap(model):
    """The model behind the serving facades (each holds it as ``inner``
    or ``model``)."""
    while True:
        inner = next((m for m in (getattr(model, a, None)
                                  for a in ("inner", "model"))
                      if hasattr(m, "apply")), None)
        if inner is None:
            return model
        model = inner


def block_length_of(model) -> int:
    return int(getattr(unwrap(model), "block_length", 1))


def reveal_quota(block: int, steps: int, step: int) -> int:
    """Positions the static rule reveals at denoise step ``step`` (0-based)
    of ``steps``: ``block // steps``, the remainder spread over the first
    steps (the family's ``get_num_transfer_tokens``)."""
    return block // steps + (1 if step < block % steps else 0)


def reveal(cand, conf, tokens, revealed, quota, threshold, dynamic):
    """The reveal rule on the device, all rows at once. ``cand`` / ``conf``
    (S, B): candidate token and its confidence at every position;
    ``tokens`` / ``revealed`` (S, B): the input block; ``quota`` (S,)
    int, ``threshold`` (S,) float, ``dynamic`` (S,) bool per row. Returns
    the block after the pass ``(tokens, revealed)``. A row whose block
    held no mask comes back unchanged."""
    masked = ~revealed
    conf_m = jnp.where(masked, conf, -jnp.inf)
    # rank of each position among its row's masked ones, most confident
    # first (ties: the earlier position)
    rank = jnp.argsort(jnp.argsort(-conf_m, axis=1, stable=True), axis=1)
    top = masked & (rank < quota[:, None])
    high = masked & (conf > threshold[:, None])
    use_high = dynamic & (jnp.sum(high, axis=1) >= quota)
    now = jnp.where(use_high[:, None], high, top)
    return jnp.where(now, cand, tokens), revealed | now


class BlockDecoder:
    """Per-slot block state + the block step of one ``InferenceEngine``."""

    def __init__(self, engine):
        self.eng = engine
        core = unwrap(engine.model)
        self.B = int(core.block_length)
        self.mask_id = int(core.mask_token_id)
        cfg = core.config
        self.default_steps = int(getattr(cfg, "denoising_steps", self.B))
        self.default_rule = getattr(cfg, "remasking", REMASKING[0])
        self.default_threshold = float(
            getattr(cfg, "confidence_threshold", 0.9))
        self.n_experts = int(getattr(cfg, "n_experts", 0))  # 0: no routing
        S, B = engine.max_slots, self.B
        # the block plane: every row's block after the last ISSUED pass,
        # device arrays (a pass's outputs are the next pass's inputs)
        self._plane = (jnp.zeros((S, B), jnp.int32), jnp.zeros((S, B), bool))
        # rows whose block the host knows better, handed to the next pass
        # (:meth:`_plan`)
        self._fix = self._no_fix()
        # the schedule, as of the last ISSUED pass: revealed positions a
        # row (under the dynamic rule a device value: written when the
        # pass is read, and the engine drains), denoise passes its block
        # has had, blocks it has committed
        self.n_rev = np.zeros((S,), np.int32)
        self.passes_in_block = np.zeros((S,), np.int32)
        self.block_no = np.zeros((S,), np.int32)
        # the revealed flags as of the last pass READ: what a pass newly
        # revealed is told against them
        self.rev = np.zeros((S, B), bool)
        # leading positions of the slot's FIRST block that are prompt
        # remainder: revealed from the start and never emitted
        self.keep = np.zeros((S,), np.int32)
        self.steps = np.full((S,), self.default_steps, np.int32)
        self.dynamic = np.zeros((S,), bool)
        self.threshold = np.full((S,), self.default_threshold, np.float32)
        # lifetime counters (engine-thread writes, scrape-side reads of
        # monotone numbers: the spec_* counter convention)
        # (booked when a pass is read)
        self.passes = 0             # dispatches of the block program
        self.row_passes = 0         # rows that really advanced in them
        # ... rows a pass ran past their stream's EOS, dropped unread
        self.row_passes_discarded = 0
        self.blocks_committed = 0
        self.tokens_committed = 0   # tokens streamed out of commits
        self.tokens_revealed = 0
        self.routing = RoutingLoad(self.n_experts)
        # reference comparisons (tests, the benchmark's check) set this
        # to a list: a pass issued from then on keeps its logits (the one
        # program returns them always, as a device array nothing else
        # reads), its reading FETCHES them with the rest, and each
        # advancing row appends {uid, block, pass, commit, logits
        # (B, vocab), experts (layers, B, k) | None}. None: nothing kept.
        self.capture = None
        # device copies of the per-row sampling and schedule arrays: they
        # change at activation only, not every pass
        self._row_params = None
        _c = lambda fn: engine.dispatch_meter.wrap(  # noqa: E731
            engine.compile_meter.wrap(fn))
        self._pg_block = _c(jax.jit(
            self._paged_block_fn, donate_argnums=(1,)))

    # --- what a block-diffusion engine refuses -------------------------------

    @staticmethod
    def check_engine(engine) -> None:
        """Build-time refusals: every engine feature whose meaning rests
        on "one pass = one committed token, causal attention" and that
        has no block form yet."""
        B = block_length_of(engine.model)

        def no(what: str, why: str):
            raise ValueError(
                f"block-diffusion model (block_length={B}): {what} is not "
                f"supported — {why}")

        if engine.paged is None:
            no("kv_layout='contiguous'",
               "the block step is written against the page pool; use "
               "kv_layout='paged'")
        if engine.speculative_k is not None or engine.draft_model is not None:
            no("speculative decoding",
               "a draft verifies next-token guesses; a block pass reveals "
               "positions of a block")
        if engine.prefix_cache is not None or engine.kv_pool is not None:
            no("prefix caching / tiered KV",
               "pages are shared per 16 causal positions; block-causal "
               "K/V of a partial block depend on the block's other tokens")
        if engine.session_store is not None:
            no("the session store", "it pins prefix pages (see above)")
        if engine.adapter_registry is not None:
            no("multi-LoRA", "the block program has no adapter twin")
        if engine.role != "both" or engine.handoff is not None:
            no("disaggregated prefill/decode",
               "a handed-off entry ends in last-position logits, which a "
               "block-diffusion decode never samples from")
        if engine.tp > 1:
            no("tensor parallelism",
               "the grouped expert kernel is not partitioned; experts "
               "sharded over chips are future work")
        pool_tokens = engine.paged.pool.capacity * engine.paged.page_size
        if pool_tokens < engine.max_slots * engine.cache_len:
            no(f"a page pool of {pool_tokens} tokens, below max_slots x "
               "cache_len", "a dry pool preempts by recompute, which "
               "resumes from ONE pending token, not a half-revealed block")
        if engine.cache_len % B:
            no(f"cache_len={engine.cache_len}",
               f"it must be a multiple of the block length {B}")
        if engine.chunked_prefill is not None and engine.chunked_prefill % B:
            no(f"chunked_prefill={engine.chunked_prefill}",
               f"a chunk must be a multiple of the block length {B}")
        for b in engine.buckets:
            if b % B:
                no(f"prefill bucket {b}",
                   f"buckets must be multiples of the block length {B}")

    def check_submit(self, params, *, kv_entry, handoff_id, adapter,
                     session_id) -> None:
        """Submit-time refusals (raised on the caller's thread, before
        anything is queued)."""
        def no(what: str):
            raise ValueError(
                f"block-diffusion model (block_length={self.B}): {what}")

        if params.constraint is not None:
            no("grammar-constrained decoding is not supported (the mask "
               "encodes one automaton state per next token; a block "
               "reveals positions out of order)")
        if kv_entry is not None or handoff_id is not None:
            no("handed-off KV / prefill-only requests are not supported")
        if adapter is not None:
            no("LoRA adapters are not supported")
        if session_id is not None:
            no("sessions are not supported")
        steps, rule, _ = self._schedule(params)
        if not 1 <= steps <= self.B:
            no(f"denoising_steps must be in [1, {self.B}], got {steps}")
        if rule not in REMASKING:
            no(f"remasking must be one of {REMASKING}, got {rule!r}")

    def _schedule(self, params):
        steps = (self.default_steps if params.denoising_steps is None
                 else int(params.denoising_steps))
        rule = params.remasking or self.default_rule
        thr = (self.default_threshold if params.confidence_threshold is None
               else float(params.confidence_threshold))
        return steps, rule, thr

    # --- the jitted program ---------------------------------------------------

    def _paged_block_fn(self, params, pool, plan, tokens, revealed, rng,
                        temperature, top_k, top_p, greedy, threshold,
                        dynamic):
        """One pass over the slot plane, ONE dispatch: take the host's
        word for the rows it ``fix``es (``tokens`` / ``revealed`` are the
        pass before's outputs), gather every slot's pages into a view
        pinned at its committed length (always a multiple of B), forward
        the (slots, B) blocks (unrevealed positions fed as the mask id),
        sample a candidate and its confidence at every position, apply
        the reveal rule, and write the B-wide window back — to the slot's
        pages for rows that commit, to the trash page for all others (the
        host built ``sidx`` so). ``plan`` is what the host decided for
        this pass, a row a slot and one transfer (:meth:`_plan_row`);
        the engine's key goes in and comes back split, so the host
        dispatches nothing else. Returns ``(tokens, revealed, experts,
        logits, pool, rng)``: the blocks after the pass, the experts
        every position of the plane chose in every layer, ``(layers,
        slots * B, k)``, and the float32 logits ``(slots, B, vocab)``,
        which stay on the device unless a reference comparison fetches
        them (``capture``): there is ONE program, so what is compared is
        what serves."""
        eng = self.eng
        S, B = tokens.shape
        rng, sub = jax.random.split(rng)
        gidx, index_vec, sidx, quota, fix, fix_tokens, fix_revealed = \
            jnp.split(plan, self._plan_cuts(plan.shape[1]), axis=1)
        index_vec, quota, fix = index_vec[:, 0], quota[:, 0], fix != 0
        tokens = jnp.where(fix, fix_tokens, tokens)
        revealed = jnp.where(fix, fix_revealed != 0, revealed)
        view = eng._paged_view(pool, gidx, index_vec)
        ids = jnp.where(revealed, tokens, self.mask_id)
        (logits, view), aux = eng.model.apply(
            {"params": params}, ids, deterministic=True, cache=view,
            mutable=["routing"])
        logits = logits.astype(jnp.float32)
        flat = logits.reshape(S * B, -1)
        rep = lambda a: jnp.repeat(a, B)  # noqa: E731
        # idle rows (quota 0) go in as greedy: what a finished request left
        # in their flags must not choose the sampler's body, and when every
        # LIVE row is greedy the pass skips the full-vocabulary sort
        cand = sample_token_batched(
            sub, flat, temperature=rep(temperature), top_k=rep(top_k),
            top_p=rep(top_p), greedy=rep(greedy | (quota == 0)),
        ).astype(jnp.int32).reshape(S, B)
        # confidence: the candidate's probability under softmax(logits)
        picked = jnp.take_along_axis(logits, cand[..., None], axis=-1)[..., 0]
        conf = jnp.exp(picked - jax.nn.logsumexp(logits, axis=-1))
        new_tok, new_rev = reveal(cand, conf, tokens, revealed, quota,
                                  threshold, dynamic)
        routing = aux.get("routing", {})
        chosen = [routing[name]["moe"]["experts"][0]
                  for name in sorted(routing, key=lambda n: int(
                      n.rsplit("_", 1)[1]))]
        experts = (jnp.stack(chosen) if chosen
                   else jnp.zeros((0, S * B, 1), jnp.int32))
        pool = eng._paged_writeback(pool, view, sidx, index_vec)
        return new_tok, new_rev, experts, logits, pool, rng

    def _plan_cuts(self, width: int) -> list[int]:
        """Where a plan row ``width`` wide splits into its seven parts."""
        B = self.B
        return np.cumsum([width - 3 - 3 * B, 1, B, 1, 1, B]).tolist()

    def _plan(self, gidx, index_vec, sidx, quota) -> np.ndarray:
        """The host's decisions for one pass as ONE int32 array, a row a
        slot: the view's gather indices, the pinned cache index, the
        write-back targets, the reveal quota (0: an idle row), and the
        rows whose block the host opened since the last pass (``fix``
        and its planes, handed over once)."""
        fix, tokens, revealed = self._fix
        self._fix = self._no_fix()
        return np.concatenate(
            [gidx, index_vec[:, None], sidx, quota[:, None], fix[:, None],
             tokens, revealed], axis=1, dtype=np.int32)

    # --- slot life cycle ------------------------------------------------------

    def split_prompt(self, prompt_ids: list[int]):
        """(whole blocks to prefill, remainder that opens the first
        generated block)."""
        whole = len(prompt_ids) // self.B * self.B
        return prompt_ids[:whole], prompt_ids[whole:]

    def activate(self, slot: int, req, plen: int) -> None:
        """The prompt's whole blocks are in the slot's pages: open the
        first generated block. Nothing is sampled and nothing emitted;
        the request's first token arrives with its first commit."""
        eng = self.eng
        steps, rule, thr = self._schedule(req.params)
        eng.slot_req[slot] = req
        eng.slot_ready[slot] = True
        eng.slot_len[slot] = plen
        eng.slot_budget[slot] = req.params.max_tokens
        eng._slot_sampling(slot, req.params)
        eng.slot_hist[slot] = None
        eng.slot_constraint[slot] = None
        self.steps[slot] = steps
        self.dynamic[slot] = rule == REMASKING[1]
        self.threshold[slot] = thr
        self._row_params = None
        self.block_no[slot] = 0
        # no pass has run on the row: the block as opened is the last read
        self.rev[slot] = self._open_block(slot, req.block_open)

    def _no_fix(self) -> tuple:
        S, B = self.eng.max_slots, self.B
        return (np.zeros((S,), bool), np.zeros((S, B), np.int32),
                np.zeros((S, B), bool))

    def _open_block(self, slot: int, opening=()) -> np.ndarray:
        """A fresh block: ``opening`` tokens revealed at its head (the
        prompt's remainder, never emitted), the rest masked. The device
        hears of it through the next pass's ``fix``. Returns the block's
        revealed flags."""
        r = len(opening)
        fix, tok, rev = self._fix
        fix[slot] = True
        tok[slot] = 0
        tok[slot, :r] = opening
        rev[slot] = np.arange(self.B) < r
        self.keep[slot] = r
        self.n_rev[slot] = r
        self.passes_in_block[slot] = 0
        return rev[slot]

    # --- the step: issue, then retire -----------------------------------------

    def issue(self, active: list[int], f) -> None:
        """ISSUE one block pass over the ready rows ``active`` into the
        flight ``f`` (the caller holds the engine's step lock, has
        advanced the prefills and reserved the rows' pages): indices,
        the dispatch, and the half of the pass that needs none of its
        values (:meth:`_advance_row`). Nothing here reads the pass
        before."""
        eng, B = self.eng, self.B
        st = eng.steptrace
        with st.scope("index_build"):
            W = eng._paged_width(
                max(int(eng.slot_len[s]) for s in active) + B)
            eng._pulse_view(W)
            idxv = eng._paged_index_vec(W, B)
            valid = np.zeros((eng.max_slots,), np.int32)
            quota = np.zeros((eng.max_slots,), np.int32)    # 0: idle row
            for s in active:
                if self.n_rev[s] == B:
                    valid[s] = B                            # a commit pass
                quota[s] = reveal_quota(B, int(self.steps[s]),
                                        int(self.passes_in_block[s]))
            plan = self._plan(eng._paged_view_idx(W), idxv,
                              eng.paged.scatter_idx(idxv, valid, B), quota)
            if self._row_params is None:
                self._row_params = tuple(jnp.asarray(a) for a in (
                    eng._temperature, eng._top_k, eng._top_p, eng._greedy,
                    self.threshold, self.dynamic))
            st.note_sampler_tier(sampler_tier_name(
                eng._greedy | (quota == 0), eng._top_k, eng._top_p))
        with st.scope("dispatch_wait"):
            eng._window_open("decode", f)
            (f.toks, f.rev, f.experts, logits, eng.paged.kv,
             eng.rng) = self._pg_block(
                eng.params, eng.paged.kv, jnp.asarray(plan), *self._plane,
                eng.rng, *self._row_params)
            self._plane = (f.toks, f.rev)
            if self.capture is not None:    # reference comparisons only
                f.logits = logits
            f.rows = [self._advance_row(s, int(quota[s])) for s in active]
            st.window_issued()

    def _advance_row(self, slot: int, quota: int) -> tuple:
        """The half of a row's pass that needs no VALUE, done when the
        pass is issued. A commit pass: the slot grows by B, the budget
        pays for the tokens the block will stream (fewer only if an EOS
        cuts it: the reading finds that), the row closes where the budget
        or the cache ends, else it opens an all-mask block. A denoise
        pass: the static rule reveals ``min(quota, masked)`` positions.
        Returns the flight's row: ``(slot, request, tokens the commit
        streams, why the stream ends with them or None, block, pass,
        is it a commit, the block's leading prompt tokens)``."""
        eng, B = self.eng, self.B
        keep = int(self.keep[slot])
        row = (slot, eng.slot_req[slot])
        at = (int(self.block_no[slot]), int(self.passes_in_block[slot]))
        if self.n_rev[slot] < B:
            if not self.dynamic[slot]:
                self.n_rev[slot] += min(quota, B - int(self.n_rev[slot]))
            self.passes_in_block[slot] += 1
            return (*row, 0, None, *at, False, keep)
        eng.slot_len[slot] += B
        taken = min(B - keep, int(eng.slot_budget[slot]))
        eng.slot_budget[slot] -= taken
        why = ("length" if eng.slot_budget[slot] <= 0
               else "cache" if int(eng.slot_len[slot]) + B > eng.cache_len
               else None)
        # a closing row is in no later pass; it ends when this one is read
        eng.slot_closing[slot] = why
        if why is None:
            self.block_no[slot] += 1
            self._open_block(slot)
        return (*row, taken, why, *at, True, keep)

    def retire(self, f) -> None:
        """Read the pass ``f``: ONE fetch forces its results, then the
        half that needs the values runs, a row at a time and in the
        order the serial step had: the capture, a commit's stream (up to
        EOS) or a denoise pass's reveal log, the routing load, the
        counters."""
        eng, B = self.eng, self.B
        st = eng.steptrace
        with st.scope("dispatch_wait"):
            # the pass's result: the one fetch the step blocks on
            with st.fetch():
                tok, rev, experts, logits = jax.device_get(  # graftlint: disable=host-sync
                    (f.toks, f.rev, f.experts, f.logits))
            dt, _ = eng._window_close(
                "decode", [row[1] for row in f.rows], f.holders)
            eng.dispatch_meter.note_phase(
                "block", tokens=B * len(f.rows), duration_s=dt, mfu=None,
                hbm_bw_util=None)
        with st.scope("sample_commit"):
            self._book_routing(experts)
            capture = self.capture if logits is not None else None
            newly = rev & ~self.rev     # what the pass revealed, a row
            rows = commits = n_rev = n_out = 0
            for slot, req, taken, why, block, pas, commit, keep in f.rows:
                if slot in eng._zombies:
                    # its stream ended in EOS when the pass before this
                    # one was read: what this one revealed goes nowhere,
                    # and the slot and its pages are free from here on
                    st.note_discarded(int(newly[slot].sum()))
                    self.row_passes_discarded += 1
                    eng._release_pages(slot, req)
                    eng._clear_slot(slot)
                    continue
                rows += 1
                req.block_passes += 1
                if capture is not None:
                    capture.append({
                        "uid": req.uid, "block": block, "pass": pas,
                        "commit": commit, "logits": logits[slot].copy(),
                        "experts": (experts[:, slot * B:(slot + 1) * B].copy()
                                    if experts.shape[0] else None)})
                if commit:
                    commits += 1
                    self.rev[slot] = False      # the block it opened
                    n_out += self._stream(slot, req, tok[slot, keep:],
                                          taken, why)
                    continue
                hits = np.flatnonzero(newly[slot])
                req.reveal_log.extend(
                    (block, pas, int(j), int(tok[slot, j])) for j in hits)
                n_rev += len(hits)
                self.rev[slot] = rev[slot]
                if self.dynamic[slot]:
                    self.n_rev[slot] = int(rev[slot].sum())
            self.passes += 1
            self.row_passes += rows
            self.blocks_committed += commits
            self.tokens_revealed += n_rev
            self.tokens_committed += n_out
            st.note_block_pass(rows, commits, n_rev, n_out)

    def _stream(self, slot: int, req, tokens, taken: int,
                why: str | None) -> int:
        """The slot's finished block has its K/V stored: stream the
        ``taken`` tokens its commit paid for (not the prompt's
        remainder), up to an EOS, and end the stream there or for the
        deterministic ``why``. Returns the tokens streamed."""
        eng = self.eng
        for sent in range(taken):
            tok = int(tokens[sent])
            if eng.eos_id is not None and tok == eng.eos_id:
                eng._finish_slot(slot, "stop")
                return sent
            if req.first_token_time is None:
                req.first_token_time = time.monotonic()
            req.tokens.put(tok)
            req.n_generated += 1
        if why is not None:
            eng._finish_slot(slot, why)
        return taken

    def _book_routing(self, experts: np.ndarray) -> None:
        """Expert load of one pass, over the WHOLE plane the device
        computed (idle rows route too, and stream their experts'
        weights): assignments, distinct experts that received a token,
        and the busiest expert's load, summed over layers."""
        layers, n_exp = experts.shape[0], self.n_experts
        if not layers or not n_exp:
            return
        self.routing.book_counts(np.bincount(
            (experts.reshape(layers, -1)
             + np.arange(layers)[:, None] * n_exp).ravel(),
            minlength=layers * n_exp).reshape(layers, n_exp))

    def counters(self) -> dict:
        return {
            "block_passes": self.passes,
            "block_row_passes": self.row_passes,
            "block_row_passes_discarded": self.row_passes_discarded,
            "blocks_committed": self.blocks_committed,
            "block_tokens_committed": self.tokens_committed,
            "block_tokens_revealed": self.tokens_revealed,
            **{k: v for k, v in self.routing.counters().items()
               if k != "moe_layer_passes"},
        }
