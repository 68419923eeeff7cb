"""Ulysses-style all-to-all sequence parallelism over the ``seq`` mesh axis.

The second canonical long-context scheme next to ring attention
(:mod:`.ring_attention`), after DeepSpeed-Ulysses: activations stay
sequence-sharded through the whole network, and only around attention do
two ``all_to_all`` collectives re-partition — sequence-sharded
``(B, L/s, H, D)`` becomes head-sharded ``(B, L, H/s, D)``, every device
runs *ordinary dense/flash attention* over the full sequence for its head
group, and the second all-to-all restores sequence sharding.

Trade against the ring (why ship both — the reference ships neither,
SURVEY §5.7):

- **Ulysses**: 2 all-to-alls per attention, each moving the full
  activation block once; the attention itself is completely local, so any
  kernel (Pallas flash included) drops in unchanged. Requires
  ``n_kv_heads % seq == 0`` — the degree is capped by KV head count
  (GQA models cap hard).
- **Ring**: ppermute per step with compute overlap and no head-count
  constraint, but the attention inner loop must be ring-aware (online
  softmax across rotations).

Numerics: exactly dense attention — the collectives only permute data;
tests assert equality with the gathered-sequence reference on the
8-device CPU mesh, gradients included.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from llm_in_practise_tpu.core import mesh as mesh_lib
from llm_in_practise_tpu.ops.attention import dense_attention


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = mesh_lib.AXIS_SEQ,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """All-to-all attention; call inside ``shard_map`` over ``axis_name``.

    q/k/v: local shards ``(batch, local_len, heads, head_dim)``; the global
    sequence is the concatenation of shards in axis order. Heads must be
    divisible by the axis size. Returns the local output shard.
    """
    sp = jax.lax.psum(1, axis_name)
    if q.shape[2] % sp or k.shape[2] % sp:
        raise ValueError(
            f"ulysses needs heads divisible by the seq axis: "
            f"q heads {q.shape[2]}, kv heads {k.shape[2]}, axis {sp}"
        )

    def seq_to_heads(x):
        # (B, L/s, H, D) -> (B, L, H/s, D): split the head axis across the
        # devices, concatenate the sequence axis from them
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # GQA: broadcast the local KV head group AFTER the all-to-all, so the
    # collective only ever moves the compact kv heads
    groups = qh.shape[2] // kh.shape[2]
    if groups > 1:
        kh = jnp.repeat(kh, groups, axis=2)
        vh = jnp.repeat(vh, groups, axis=2)
    # full sequence, local head group: any attention body works unchanged
    out = dense_attention(qh, kh, vh, causal=causal, scale=scale)
    return heads_to_seq(out.astype(q.dtype))


def make_ulysses_attention(
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: float | None = None,
    batch_axes: Sequence[str] = mesh_lib.BATCH_AXES,
):
    """Wrap :func:`ulysses_attention` in shard_map over a concrete mesh.

    Returned fn takes *global* q/k/v ``(B, L, H, D)`` (batch over
    ``batch_axes``, sequence over ``seq``) and returns the output with the
    same sharding. Composable with jit. Note: unlike the ring wrapper,
    heads are NOT additionally sharded over ``model`` here — Ulysses
    already spends the head axis on the ``seq`` mesh dimension.
    """
    spec = P(tuple(batch_axes), mesh_lib.AXIS_SEQ, None, None)
    return _shard_map(
        functools.partial(ulysses_attention, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )


@functools.lru_cache(maxsize=32)
def _cached_ulysses_fn(mesh: Mesh, causal: bool, scale: float | None):
    return make_ulysses_attention(mesh, causal=causal, scale=scale)


def context_ulysses_attention(q, k, v, *, causal: bool = True, scale=None):
    """Ulysses attention over the ambient SP mesh (``attn_impl='ulysses'``
    under :class:`..ring_attention.sp_context` — same contract as ring)."""
    from llm_in_practise_tpu.ops.ring_attention import active_sp_mesh

    mesh = active_sp_mesh()
    if mesh is None:
        raise RuntimeError(
            "attn_impl='ulysses' needs an active sp_context(mesh) with seq>1"
        )
    return _cached_ulysses_fn(mesh, causal, scale)(q, k, v)
