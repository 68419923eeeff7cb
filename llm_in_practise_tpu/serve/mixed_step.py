"""Fused mixed-batch engine step: prefill chunk + multi-step decode, ONE dispatch.

The r5 long-context bench (8B int8, 6,144-token prompts) failed both
SLAs the moment prefill and decode overlapped: the engine ran the
batched prefill chunk and the decode as SEPARATE device dispatches
(docs/perf.md Finding 5) and hard-disabled multi-step decode whenever a prompt was mid-prefill,
degrading every active decoder to one token per TWO dispatches. Runtime
dissections of LLM serving identify exactly this prefill/decode
interference as the dominant mixed-load latency tax (arXiv:2311.03687),
and the TPU/GPU serving gap is mostly dispatch/scheduling overhead, not
FLOPs (arXiv:2605.25645).

This module is the fix: one jitted program that, against the engine
cache directly and in a single dispatch,

(a) advances every mid-prefill row one chunk — the pinned-index scatter
    idiom of ``engine._chunk_batch_fn`` (host-tracked ``starts`` pin
    each row's cache index for the forward; ``starts + lens`` pins it
    after, so only prefilling rows advance), then
(b) runs an ``n``-step ``lax.scan`` decode block over ALL rows — ready
    decoders produce ``n`` real tokens; mid-prefill and idle rows
    decode garbage that the overwrite-before-attend invariant already
    covers (every garbage row is rewritten by the chunk that owns its
    range, or by real decode in order, before any query can attend it).

Correctness bounds the scheduler must respect (enforced by
``InferenceEngine._mixed_feasible``; violation falls back to the
sequential two-dispatch path with a logged reason):

- ``n <= chunk``: the scan writes ``n`` garbage rows above each
  mid-prefill row's watermark; the next chunk's padded write (width
  ``chunk``) must cover them.
- prefill rows: ``done + chunk + n <= cache_len`` — both the chunk
  scatter and the garbage scan rows must land inside the cache (a
  clamped scatter would shift backward over attended prompt KV).
- decode rows, CONTIGUOUS layout only: ``slot_len + chunk <=
  cache_len`` — the dead chunk write window must fit (same bound as
  the batched chunk path); the scan's real writes fit a fortiori since
  ``n <= chunk``. The paged layout asks ``slot_len + n <= cache_len``
  instead (see below).
- free rows: dead either way; the caller clamps their pinned index to
  ``cache_len - chunk`` so even the dead window stays in bounds.

Token-exactness: part (a) is bit-identical to ``_chunk_batch_fn`` (same
pinning arithmetic) and part (b) to ``_decode_multi_fn`` (same scan
body, same per-step key split), so greedy outputs equal the sequential
path's exactly — pinned by ``tests/test_mixed_step.py``.

The PAGED layout (``engine._paged_mixed_fn``) keeps the one dispatch
and the two shared bodies, but its part (a) runs
:func:`batched_chunk_hidden` over the rows that are mid-prefill only,
one row a trip of a loop with a traced trip count
(``engine._paged_chunk_fn``), and its part (b) is the paged decode
body over the slot plane. The prefill half's device work follows the
number of chunking rows, not ``max_slots``, and no decode row
receives a chunk write: idle and mid-prefill rows' decode garbage
goes to the trash page through the host-built scatter indices. The
functions built by :func:`make_mixed_step` serve the contiguous
layout alone. The paged programs also take and return the device's
last-token plane (PR 40): part (b) decodes from it, part (a) puts the
first tokens it samples into it, so the engine can issue the step
after a fused one (and the fused step after a decode) before it has
read either's tokens (``engine._fly``).

What a prefill body returns (PR 32). Every body stops the forward at
the final norm and takes each row's state at its last real position
BEFORE the output head (``models/layers.py``), so the head runs on one
position a row, never on the chunk's width. The contiguous bodies
(:func:`batched_chunk`, the two ``make_*mixed_step`` functions) return
``(B, vocab)`` last-position logits as before, and the host samples a
finished prompt's first token from them with one jitted call. The paged
programs end a prompt in its first token themselves: the row loop
carries ``(max_slots, hidden)`` states by slot, ONE head pass over that
plane and the decode programs' sampler run after the loop, under a
``cond`` on "some row's prompt ends here", and the programs return
``(first tokens (max_slots,), last-position logits (max_slots, vocab),
[decode tokens (max_slots, n),] pool)``. The host reads a finishing
row's token from the step's one fetch; the logits stay an output for
the three things that read them on the host (a grammar's start-state
mask, a stored prefix entry, a handoff).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llm_in_practise_tpu.infer.sampling import sample_token_batched
from llm_in_practise_tpu.models.layers import (
    head_logits,
    last_position_hidden,
)


def pin_index(cache, index_vec):
    """Replace every layer's ``index`` with the host-provided vector —
    the shared pin/advance idiom of the batched chunk, draft, and fused
    mixed-step paths (one place to fix if the cache key convention
    changes)."""
    return [
        {k: (index_vec.astype(jnp.int32) if k == "index" else v)
         for k, v in layer.items()}
        for layer in cache
    ]


def decode_scan(model, params, cache, tokens, rng, temperature, top_k,
                top_p, greedy, *, n, gmask=None):
    """``n`` single-token decodes under one ``lax.scan`` — the SHARED
    body of the sequential multi-step program
    (``engine._decode_multi_fn``) and the fused mixed step, so the two
    dispatch modes can never drift apart in sampling or key-split
    order. Returns ``((B, n) tokens, cache)``.

    ``gmask`` (optional, (B, vocab) additive): the grammar logit mask
    of constrained decoding (serve/constrain.py) — 0 for allowed
    tokens, ``NEG_INF`` otherwise, zero rows for unconstrained slots.
    The SAME mask applies at every scan step, which is only correct for
    ``n == 1`` (the grammar state advances per token); the engine's
    planner caps constrained blocks at 1, and the unmasked programs
    (``gmask=None``) stay compiled-identical to pre-constraint builds.
    """
    if gmask is not None and n != 1:
        raise ValueError(
            f"grammar-masked decode blocks must be n=1, got n={n} "
            "(the per-slot mask is staged for one automaton state)")

    def body(carry, key):
        tok, c = carry
        lg, c = model.apply(
            {"params": params}, tok[:, None], deterministic=True,
            cache=c,
        )
        logits = lg[:, -1, :].astype(jnp.float32)
        if gmask is not None:
            logits = logits + gmask
        nxt = sample_token_batched(
            key, logits,
            temperature=temperature, top_k=top_k, top_p=top_p,
            greedy=greedy,
        ).astype(jnp.int32)
        return (nxt, c), nxt

    keys = jax.random.split(rng, n)
    (_, cache), toks = jax.lax.scan(body, (tokens, cache), keys)
    return toks.T, cache                                     # (B, n)


def batched_chunk_hidden(model, params, cache, chunk_ids, starts, lens):
    """Advance every row one pinned-index prefill chunk against the
    whole cache, up to the output head: ``((B, hidden) final-norm state
    of each row's last real position, cache)`` with the cache index
    pinned to ``starts + lens``. The paged row loop carries these and
    runs ONE head pass over the slot plane after its last trip
    (``engine._paged_chunk_fn``)."""
    last, cache = last_position_hidden(
        model, params, chunk_ids, lens, pin_index(cache, starts))
    return last, pin_index(cache, starts + lens)


def batched_chunk(model, params, cache, chunk_ids, starts, lens):
    """:func:`batched_chunk_hidden` through the output head — the
    SHARED body of ``engine._chunk_batch_fn`` and the contiguous fused
    mixed step (see that method's docstring for the invariants).
    Returns ``((B, vocab) last-real-position logits, cache)``; the head
    runs on that one position a row, not on the chunk's width."""
    last, cache = batched_chunk_hidden(
        model, params, cache, chunk_ids, starts, lens)
    return head_logits(model, params, last), cache


def spec_verify_block(model, params, cache, tokens, base, mask, *, m,
                      gmasks=None):
    """Fused speculative round: verify the K drafted tokens AND run the
    remainder of the planned decode block, in ONE jitted dispatch
    (ROADMAP item 4 — "verify k proposed tokens inside the n-step
    decode dispatch").

    The pre-fusion spec path cost a contiguous engine TWO dispatches
    per round (the wide verify + a host-driven index ``_rewind``) and
    capped every round at ``n_acc + 1`` tokens however large
    ``decode_steps`` was. This body folds the whole round into one
    program:

    1. one wide forward over the K+1 proposed positions (index pinned
       to the host-tracked ``base`` — the same pin idiom as
       :func:`batched_chunk`, so idle/mid-prefill rows stop
       accumulating index drift);
    2. ON-DEVICE acceptance: ``n_acc`` = longest prefix of the drafts
       matching the forward's own greedy outputs (a cumprod over the
       matches — the host loop, vectorized);
    3. the index fixup the separate rewind dispatch used to do:
       ``base + (n_acc + 1) * mask`` (mask 0 rows — idle, mid-prefill
       — are restored to ``base`` exactly);
    4. ``m`` extra greedy scan steps from each row's bonus token
       ``out[s, n_acc]`` — the tail of the planned n-step block, so a
       spec round spans the same dispatch plan as a plain multi-step
       block (``m = block - 1``, see :func:`plan_spec_extension`).
       Each step overwrites the next rejected draft position before any
       query can attend it (overwrite-before-attend, as everywhere).

    ``tokens``: (B, K+1) — ``[last_token, draft_1..K]`` per row (zeros
    for undrafted/idle rows). ``base``: (B,) pinned pre-dispatch cache
    index. ``mask``: (B,) 1 for really-advancing rows. Returns
    ``(out (B, K+1), n_acc (B,), extra (B, m), cache)`` with the final
    index at ``base + (n_acc + 1 + m) * mask``.

    Greedy-lossless: every emitted token — accepted, bonus, or
    extension — is an argmax of this program's own forward, identical
    to what the sequential greedy path emits.

    ``gmasks`` (optional, (B, K+1, vocab) additive): grammar logit
    masks for constrained decoding — position ``j``'s row is the mask
    of the automaton state after the first ``j`` drafts (the host
    advances the grammar tentatively over the drafted tokens,
    serve/engine._try_speculative). A grammar-forbidden draft cannot be
    the masked argmax at its position, so the acceptance cumprod
    truncates there exactly like an argmax mismatch, and the bonus
    token at ``n_acc`` is masked by the right state's row. The caller
    runs constrained rounds at ``m == 0`` (the extension's scan steps
    have no host-stageable mask).
    """
    base = base.astype(jnp.int32)
    mask = mask.astype(jnp.int32)
    logits, cache = model.apply(
        {"params": params}, tokens, deterministic=True,
        cache=pin_index(cache, base),
    )
    logits = logits.astype(jnp.float32)
    if gmasks is not None:
        logits = logits + gmasks
    out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # longest accepted prefix: position j is accepted iff every draft
    # up to and including j matched the model's own output
    match = (out[:, :-1] == tokens[:, 1:]).astype(jnp.int32)   # (B, K)
    n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)        # (B,)
    if m == 0:
        cache = pin_index(cache, base + (n_acc + 1) * mask)
        extra = jnp.zeros((tokens.shape[0], 0), jnp.int32)
        return out, n_acc, extra, cache
    # bonus token = the model's continuation at the first mismatch (or
    # past the last draft) — the extension decodes onward from it
    bonus = jnp.take_along_axis(out, n_acc[:, None], axis=1)[:, 0]
    cache = pin_index(cache, base + (n_acc + 1) * mask)

    def body(carry, _):
        tok, c = carry
        lg, c = model.apply(
            {"params": params}, tok[:, None], deterministic=True,
            cache=c,
        )
        nxt = jnp.argmax(
            lg[:, -1, :].astype(jnp.float32), axis=-1).astype(jnp.int32)
        return (nxt, c), nxt

    (_, cache), extra = jax.lax.scan(body, (bonus, cache), None, length=m)
    # the scan advanced EVERY row's index by m; pin the real per-row
    # positions (masked rows return to base, same contract as the
    # fused mixed step's ``advance``)
    cache = pin_index(cache, base + (n_acc + 1 + m) * mask)
    return out, n_acc, jnp.swapaxes(extra, 0, 1), cache       # (B, m)


def plan_spec_extension(*, block: int, k: int, headroom: int) -> int:
    """Extra greedy steps ``m`` after the K-token verify, so one fused
    spec dispatch spans the same ``n``-step plan as a plain block
    (``block`` from :func:`plan_decode_block`): ``m = block - 1``,
    shrunk to ``headroom`` (= min over live rows of
    ``cache_len - (k + 1) - position`` — every write of the widened
    dispatch must land inside the cache) and, when shrunk by headroom,
    quantized DOWN to a power of two. Compile-set bound (each distinct
    ``m`` is its own compiled program): ``m`` takes values in
    ``{decode_steps - 1}`` ∪ ``{2^j - 1}`` (a capped block from
    :func:`plan_decode_block` is a power of two, so ``block - 1``
    lands one below) ∪ ``{2^j}`` (headroom quantization) ∪ ``{0}`` —
    ~2·log2(decode_steps) variants, all reachable by a warmup that
    drives queueing/prefill caps, same order as the plain block
    family.
    """
    m = block - 1
    if m <= 0 or headroom <= 0:
        return 0
    if headroom < m:
        m = headroom
        if m > 1:
            m = 1 << (m.bit_length() - 1)
    return m


def make_mixed_step(model):
    """Build the fused mixed-step function for ``model`` (jit with
    ``donate_argnums=(1,)`` and ``static_argnames=("n",)``).

    Signature of the returned function::

        chunk_last, toks, cache = fn(
            params, cache, chunk_ids, starts, lens, advance,
            tokens, rng, temperature, top_k, top_p, greedy, n=n)

    - ``chunk_ids`` (max_slots, chunk): real chunk tokens for
      mid-prefill rows, zeros elsewhere.
    - ``starts``/``lens`` (max_slots,): host-pinned cache index per row
      and real chunk length (0 for non-prefill rows).
    - ``advance`` (max_slots,): how far the decode block REALLY moves
      each row — ``n`` for ready decode rows, 0 elsewhere. The scan
      bumps every row's device index by ``n``; the final pin
      ``starts + lens + advance`` undoes that for mid-prefill and idle
      rows, so a prompt whose last chunk completes inside this dispatch
      activates at exactly ``plen`` (the next, unpinned decode dispatch
      must not leave an ``n``-row garbage gap below its write index).
    - ``tokens`` (max_slots,): last sampled token per ready decode row
      (garbage elsewhere).
    - ``chunk_last`` (max_slots, vocab): last-real-position logits of
      the chunk forward (meaningful only for prefill rows).
    - ``toks`` (max_slots, n): the decode block's sampled tokens
      (meaningful only for ready rows).

    Compiled variants: one per distinct ``n`` — the engine quantizes
    block lengths to powers of two, bounding this at
    log2(decode_steps)+1, all reachable by warmup.
    """

    def mixed_step_fn(params, cache, chunk_ids, starts, lens, advance,
                      tokens, rng, temperature, top_k, top_p, greedy,
                      *, n):
        # (a) one prefill chunk for every mid-prefill row, engine cache
        # directly — the same body _chunk_batch_fn compiles
        chunk_last, cache = batched_chunk(
            model, params, cache, chunk_ids, starts, lens)
        # (b) n-step decode block over all rows — the same body
        # _decode_multi_fn compiles
        toks, cache = decode_scan(
            model, params, cache, tokens, rng, temperature, top_k,
            top_p, greedy, n=n)
        # the scan advanced EVERY row's index by n; only ready decode
        # rows really moved — pin the rest back (see ``advance`` above)
        cache = pin_index(cache, starts + lens + advance)
        return chunk_last, toks, cache                       # (B, n)

    return mixed_step_fn


def make_masked_mixed_step(model):
    """Grammar-masked twin of :func:`make_mixed_step`: identical body
    plus a trailing ``gmask`` (max_slots, vocab) additive logit mask
    applied to the decode half (serve/constrain.py). A SEPARATE
    compiled program, not a flag on the unmasked one — unconstrained
    steps keep the exact pre-constraint program (golden parity by
    construction) and never pay the mask's host→device transfer. The
    planner caps constrained blocks at ``n == 1`` (the mask encodes one
    automaton state per slot)."""

    def masked_mixed_step_fn(params, cache, chunk_ids, starts, lens,
                             advance, tokens, rng, temperature, top_k,
                             top_p, greedy, gmask, *, n):
        chunk_last, cache = batched_chunk(
            model, params, cache, chunk_ids, starts, lens)
        toks, cache = decode_scan(
            model, params, cache, tokens, rng, temperature, top_k,
            top_p, greedy, n=n, gmask=gmask)
        cache = pin_index(cache, starts + lens + advance)
        return chunk_last, toks, cache                       # (B, n)

    return masked_mixed_step_fn


def plan_decode_block(*, decode_steps: int, queue_depth: int,
                      soonest_finish: int | None,
                      chunk: int | None,
                      prefill_headroom: int | None) -> int:
    """Token-budget planner for the decode block length ``n``
    (Sarathi-style stall-free batching, host side).

    Pure function so the policy is unit-testable without an engine:

    - start from the configured ``decode_steps``;
    - under queueing (``queue_depth > 0``) cap at the soonest
      *deterministic* completion among active rows (token budget or
      cache room), so a freed slot refills at the very next step;
    - while any row is mid-prefill, cap at ``chunk`` (the scan's
      garbage rows must be covered by the next chunk's write) and at
      ``prefill_headroom`` (= min over prefill rows of
      ``cache_len - chunk - done``: the garbage window must land inside
      the cache);
    - a CAPPED length is quantized DOWN to a power of two — every
      distinct ``n`` is its own compiled program, and an uncapped
      1..decode_steps range lets a first-seen length land a
      multi-second compile inside a latency-SLA request (measured r4:
      a 703 ms-mean-TPOT outlier in an otherwise 70 ms ladder). The
      configured ``decode_steps`` itself always runs at full value (a
      non-pow2 ``--decode-steps 6`` means 6, not 4) — it is one known,
      warmup-reachable variant.
    """
    n = decode_steps
    capped = False
    if (n > 1 and queue_depth > 0 and soonest_finish is not None
            and soonest_finish < n):
        n = max(1, soonest_finish)
        capped = True
    if chunk is not None and chunk < n:
        n = max(1, chunk)
        capped = True
    if prefill_headroom is not None and prefill_headroom < n:
        n = max(1, prefill_headroom)
        capped = True
    if capped and n > 1:
        n = 1 << (n.bit_length() - 1)
    return n
