"""Token sampling: greedy / temperature / top-k / top-p.

Parity with the reference's decode styles: greedy argmax
(``llm-demo/minigpt/generate.py:14-28``), temperature + multinomial
(``minigpt2/test_model.py:35-57``), top-k/top-p HF ``generate`` kwargs
(``Scripts/inference/04-*.py``). All jittable (static shapes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def sample_token(
    rng: jax.Array,
    logits: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    greedy: bool = False,
) -> jax.Array:
    """Sample next token ids from (..., vocab) logits."""
    if greedy or temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k is not None and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p is not None and top_p < 1.0:  # 0.0 = keep only the top token
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens until cumulative prob exceeds top_p (always keep top-1)
        cutoff_mask = cum - probs > top_p
        cutoff_logit = jnp.min(
            jnp.where(cutoff_mask, jnp.inf, sorted_logits), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff_logit, NEG_INF, logits)
    return jax.random.categorical(rng, logits, axis=-1)


SAMPLER_TIERS = ("argmax", "plain", "filtered")


def sampler_tier(greedy, top_k, top_p):
    """Index into :data:`SAMPLER_TIERS` of the cheapest body of
    :func:`sample_token_batched` that gives these rows their tokens: 0
    when every row is greedy, 1 when no sampled row filters, else 2. The
    one place that decides: the sampler switches on it inside the
    compiled program, and the engine books the same function of the same
    flags (numpy arrays, no device fetch: :func:`sampler_tier_name`) into
    its step records."""
    filters = ~greedy & ((top_k > 0) | (top_p < 1.0))
    return (~greedy).any().astype("int32") + filters.any().astype("int32")


def sampler_tier_name(greedy, top_k, top_p) -> str:
    """:func:`sampler_tier` by name, for host-side (numpy) flags."""
    return SAMPLER_TIERS[int(sampler_tier(greedy, top_k, top_p))]


def sample_token_batched(
    rng: jax.Array,
    logits: jax.Array,
    *,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    greedy: jax.Array,
) -> jax.Array:
    """Per-row sampling params — the continuous-batching sampler.

    Every slot in the serving engine carries its own request's sampling
    settings, so all params are ``(B,)`` vectors: ``temperature`` floats,
    ``top_k`` ints (0 disables), ``top_p`` floats (>=1.0 disables),
    ``greedy`` bools. logits: ``(B, vocab)``. Jittable, static shapes.

    The rows' own flags pick the body (:func:`sampler_tier`); every body
    returns, bit for bit, what ``filtered`` returns for the same input,
    so a row's token does not depend on which one its neighbours forced:
    a greedy row's is ``argmax(logits)``, and a row without filters
    passes ``scaled`` through both of ``filtered``'s masks untouched into
    the same ``categorical`` draw (the noise depends on the key and the
    plane's shape alone). A caller that wants rows ignored (idle slots)
    passes them as greedy.
    """
    return jax.lax.switch(
        sampler_tier(greedy, top_k, top_p), (_argmax, _plain, _filtered),
        rng, logits, temperature, top_k, top_p, greedy)


def _argmax(rng, logits, temperature, top_k, top_p, greedy):
    return jnp.argmax(logits, axis=-1)


def _plain(rng, logits, temperature, top_k, top_p, greedy):
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled)


def _filtered(rng, logits, temperature, top_k, top_p, greedy):
    """Temperature, row-wise top-k and top-p over one full-vocabulary
    sort, then the draw: the reference the cheaper bodies must equal."""
    n_vocab = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]

    # One O(V log V) sort serves both filters (the top-k masking below keeps
    # descending order, so no re-sort for top-p).
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]

    # Row-wise top-k: kth-largest threshold per row (k=0 -> keep all).
    k_idx = jnp.clip(top_k - 1, 0, n_vocab - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    k_on = top_k[:, None] > 0
    scaled = jnp.where(k_on & (scaled < kth), NEG_INF, scaled)
    sorted_desc = jnp.where(
        k_on & (jnp.arange(n_vocab)[None, :] > k_idx[:, None]), NEG_INF, sorted_desc
    )

    # Row-wise top-p over the filtered logits; top_p=0 is most restrictive
    # (keeps exactly the top-1), >=1 disables.
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_mask = cum - probs > top_p[:, None]
    cutoff_logit = jnp.min(
        jnp.where(cutoff_mask, jnp.inf, sorted_desc), axis=-1, keepdims=True
    )
    use_p = (top_p < 1.0)[:, None]
    scaled = jnp.where(use_p & (scaled < cutoff_logit), NEG_INF, scaled)

    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled)
