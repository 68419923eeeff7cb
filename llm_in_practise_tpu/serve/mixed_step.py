"""Fused mixed-batch engine step: prefill chunk + decode, ONE dispatch.

Run as SEPARATE device dispatches, the batched prefill chunk and the
decode make every active decoder wait two dispatches a token whenever a
prompt is mid-prefill (docs/perf.md Finding 5). Runtime dissections of
LLM serving identify exactly this prefill/decode interference as the
dominant mixed-load latency tax (arXiv:2311.03687), and the TPU/GPU
serving gap is mostly dispatch/scheduling overhead, not FLOPs
(arXiv:2605.25645).

This module is the fix: one jitted program that, against the engine
cache directly and in a single dispatch,

(a) advances every mid-prefill row one chunk — the pinned-index scatter
    idiom of ``engine._chunk_batch_fn`` (host-tracked ``starts`` pin
    each row's cache index for the forward; ``starts + lens`` pins it
    after, so only prefilling rows advance), then
(b) decodes one token for ALL rows (:func:`decode_scan`) — ready
    decoders produce a real token; mid-prefill and idle rows decode
    garbage that the overwrite-before-attend invariant already covers
    (every garbage row is rewritten by the chunk that owns its range,
    or by real decode in order, before any query can attend it).

Correctness bounds the scheduler must respect (enforced by
``InferenceEngine._mixed_feasible``; violation falls back to the
sequential two-dispatch path with a logged reason):

- prefill rows: ``done + chunk + 1 <= cache_len`` — both the chunk
  scatter and the decode's garbage row above each mid-prefill row's
  watermark must land inside the cache (a clamped scatter would shift
  backward over attended prompt KV); the next chunk's padded write
  (width ``chunk``) covers the garbage row.
- decode rows, CONTIGUOUS layout only: ``slot_len + chunk <=
  cache_len`` — the dead chunk write window must fit (same bound as
  the batched chunk path); the decode's real write fits a fortiori.
  The paged layout asks ``slot_len + 1 <= cache_len`` instead (see
  below).
- free rows: dead either way; the caller clamps their pinned index to
  ``cache_len - chunk`` so even the dead window stays in bounds.

Token-exactness: part (a) is bit-identical to ``_chunk_batch_fn`` (same
pinning arithmetic) and part (b) samples as ``_decode_fn`` does, so
greedy outputs equal the sequential path's exactly — pinned by
``tests/test_mixed_step.py``.

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
[decode tokens (max_slots, 1),] pool)``. The host reads a finishing
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
                top_p, greedy, *, gmask=None):
    """One single-token decode of every row under a one-trip
    ``lax.scan`` — the decode half of every fused mixed step, both
    layouts. Returns ``((B, 1) tokens, cache)``.

    ``gmask`` (optional, (B, vocab) additive): the grammar logit mask
    of constrained decoding (serve/constrain.py) — 0 for allowed
    tokens, ``NEG_INF`` otherwise, zero rows for unconstrained slots,
    staged by the host from each slot's automaton state. The unmasked
    programs (``gmask=None``) stay compiled-identical to
    pre-constraint builds.
    """

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

    keys = jax.random.split(rng, 1)
    (_, cache), toks = jax.lax.scan(body, (tokens, cache), keys)
    return toks.T, cache                                     # (B, 1)


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


def spec_verify_block(model, params, cache, tokens, base, mask, *,
                      gmasks=None):
    """Fused speculative round: verify the K drafted tokens and accept
    on device, in ONE jitted dispatch.

    1. one wide forward over the K+1 proposed positions (index pinned
       to the host-tracked ``base`` — the same pin idiom as
       :func:`batched_chunk`, so idle/mid-prefill rows stop
       accumulating index drift);
    2. ON-DEVICE acceptance: ``n_acc`` = longest prefix of the drafts
       matching the forward's own greedy outputs (a cumprod over the
       matches — the host loop, vectorized);
    3. the index fixup: ``base + (n_acc + 1) * mask`` (mask 0 rows —
       idle, mid-prefill — are restored to ``base`` exactly). The
       rejected draft positions above it are overwritten by the next
       real write before any query can attend them
       (overwrite-before-attend, as everywhere).

    ``tokens``: (B, K+1) — ``[last_token, draft_1..K]`` per row (zeros
    for undrafted/idle rows). ``base``: (B,) pinned pre-dispatch cache
    index. ``mask``: (B,) 1 for really-advancing rows. Returns
    ``(out (B, K+1), n_acc (B,), cache)`` with the final index at
    ``base + (n_acc + 1) * mask``.

    Greedy-lossless: every emitted token — accepted or bonus — is an
    argmax of this program's own forward, identical to what the
    sequential greedy path emits.

    ``gmasks`` (optional, (B, K+1, vocab) additive): grammar logit
    masks for constrained decoding — position ``j``'s row is the mask
    of the automaton state after the first ``j`` drafts (the host
    advances the grammar tentatively over the drafted tokens,
    serve/engine._try_speculative). A grammar-forbidden draft cannot be
    the masked argmax at its position, so the acceptance cumprod
    truncates there exactly like an argmax mismatch, and the bonus
    token at ``n_acc`` is masked by the right state's row.
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
    cache = pin_index(cache, base + (n_acc + 1) * mask)
    return out, n_acc, cache


def make_mixed_step(model):
    """Build the fused mixed-step function for ``model`` (jit with
    ``donate_argnums=(1,)``).

    Signature of the returned function::

        chunk_last, toks, cache = fn(
            params, cache, chunk_ids, starts, lens, advance,
            tokens, rng, temperature, top_k, top_p, greedy)

    - ``chunk_ids`` (max_slots, chunk): real chunk tokens for
      mid-prefill rows, zeros elsewhere.
    - ``starts``/``lens`` (max_slots,): host-pinned cache index per row
      and real chunk length (0 for non-prefill rows).
    - ``advance`` (max_slots,): how far the decode REALLY moves each
      row — 1 for ready decode rows, 0 elsewhere. The decode bumps
      every row's device index by one; the final pin
      ``starts + lens + advance`` undoes that for mid-prefill and idle
      rows, so a prompt whose last chunk completes inside this dispatch
      activates at exactly ``plen`` (the next, unpinned decode dispatch
      must not leave a garbage row below its write index).
    - ``tokens`` (max_slots,): last sampled token per ready decode row
      (garbage elsewhere).
    - ``chunk_last`` (max_slots, vocab): last-real-position logits of
      the chunk forward (meaningful only for prefill rows).
    - ``toks`` (max_slots, 1): the decode's sampled tokens (meaningful
      only for ready rows).
    """

    def mixed_step_fn(params, cache, chunk_ids, starts, lens, advance,
                      tokens, rng, temperature, top_k, top_p, greedy):
        # (a) one prefill chunk for every mid-prefill row, engine cache
        # directly — the same body _chunk_batch_fn compiles
        chunk_last, cache = batched_chunk(
            model, params, cache, chunk_ids, starts, lens)
        # (b) one decode over all rows
        toks, cache = decode_scan(
            model, params, cache, tokens, rng, temperature, top_k,
            top_p, greedy)
        # the decode advanced EVERY row's index; only ready decode rows
        # really moved — pin the rest back (see ``advance`` above)
        cache = pin_index(cache, starts + lens + advance)
        return chunk_last, toks, cache                       # (B, 1)

    return mixed_step_fn


def make_masked_mixed_step(model):
    """Grammar-masked twin of :func:`make_mixed_step`: identical body
    plus a trailing ``gmask`` (max_slots, vocab) additive logit mask
    applied to the decode half (serve/constrain.py). A SEPARATE
    compiled program, not a flag on the unmasked one — unconstrained
    steps keep the exact pre-constraint program (golden parity by
    construction) and never pay the mask's host→device transfer."""

    def masked_mixed_step_fn(params, cache, chunk_ids, starts, lens,
                             advance, tokens, rng, temperature, top_k,
                             top_p, greedy, gmask):
        chunk_last, cache = batched_chunk(
            model, params, cache, chunk_ids, starts, lens)
        toks, cache = decode_scan(
            model, params, cache, tokens, rng, temperature, top_k,
            top_p, greedy, gmask=gmask)
        cache = pin_index(cache, starts + lens + advance)
        return chunk_last, toks, cache                       # (B, 1)

    return masked_mixed_step_fn
