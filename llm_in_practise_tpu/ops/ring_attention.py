"""Ring attention: sequence-parallel causal attention over the ``seq`` mesh axis.

The reference has **no** training-time sequence/context parallelism (SURVEY
§5.7 — max training seq is ``block_size=256``,
``DeepSeekLike_spare_MoE_wikitext2.py:426``; long context exists only through
vLLM's paged KV at inference). For TPU-scale capability parity this module
ships it as a first-class mesh axis: Q/K/V are sharded over ``seq``; each
device computes attention for its query block while the K/V shards rotate
around the ring via ``jax.lax.ppermute`` — the collective rides ICI and
overlaps with the per-block flash computation. Memory per device is
O(L/n · L/n) for logits and O(L/n) for the accumulators, so sequence length
scales linearly with the ring size.

Numerics: online (streaming) softmax in float32 — identical math to the
FlashAttention-2 forward in :mod:`llm_in_practise_tpu.ops.flash_attention`,
accumulated across ring steps instead of kernel grid steps. Causality is
enforced with absolute positions (query block ``i`` attends to KV block ``j``
fully when ``j < i``, triangularly when ``j == i``, not at all when ``j > i``),
so the result is bit-comparable to dense causal attention on the gathered
sequence (tests assert this on an 8-device CPU mesh).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from llm_in_practise_tpu.core import mesh as mesh_lib
from llm_in_practise_tpu.ops.attention import NEG_INF


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """GQA: repeat KV heads to match query heads."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = mesh_lib.AXIS_SEQ,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Sequence-sharded attention; call inside ``shard_map`` over ``axis_name``.

    q/k/v: local shards ``(batch, local_len, heads, head_dim)`` — the global
    sequence is the concatenation of shards in ring order. Returns the local
    output shard, same shape/dtype as ``q``.
    """
    batch, q_len, n_head, head_dim = q.shape
    kv_len = k.shape[1]
    n_rep = n_head // k.shape[2]
    scale = scale if scale is not None else head_dim ** -0.5

    ring_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    q_pos = my_idx * q_len + jnp.arange(q_len)  # absolute query positions

    # Each step every device forwards its current KV shard to the next ring
    # neighbour, so after t rotations device i holds the shard that started
    # on device (i - t) mod n.
    perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]

    def step(t, carry):
        o, m, l, k_blk, v_blk = carry
        kv_idx = (my_idx - t) % ring_size
        kv_pos = kv_idx * kv_len + jnp.arange(kv_len)

        kf = _repeat_kv(k_blk, n_rep)
        vf = _repeat_kv(v_blk, n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kf,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            allowed = kv_pos[None, :] <= q_pos[:, None]  # (q_len, kv_len)
            s = jnp.where(allowed[None, None], s, NEG_INF)
            keep = allowed[None, None].astype(jnp.float32)
        else:
            keep = None

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))  # (B, H, Lq)
        # NEG_INF is finite, so exp(s - m_new) is 1.0 on fully-masked rows —
        # multiply by `keep` to zero those contributions exactly.
        p = jnp.exp(s - m_new[..., None])
        if keep is not None:
            p = p * keep
        corr = jnp.exp(m - m_new)  # (B, H, Lq)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vf.astype(jnp.float32))
        o_new = o * corr.transpose(0, 2, 1)[..., None] + pv

        k_next, v_next = jax.lax.ppermute((k_blk, v_blk), axis_name, perm)
        return o_new, m_new, l_new, k_next, v_next

    o0 = jnp.zeros((batch, q_len, n_head, head_dim), jnp.float32)
    m0 = jnp.full((batch, n_head, q_len), NEG_INF, jnp.float32)
    l0 = jnp.zeros((batch, n_head, q_len), jnp.float32)
    o, _, l, _, _ = jax.lax.fori_loop(
        0, ring_size, step, (o0, m0, l0, k, v)
    )
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: float | None = None,
    batch_axes: Sequence[str] = mesh_lib.BATCH_AXES,
    head_axis: str | None = mesh_lib.AXIS_TENSOR,
):
    """Wrap :func:`ring_attention` in shard_map over a concrete mesh.

    Returned fn takes *global* q/k/v ``(B, L, H, D)`` (sharded: batch over
    ``batch_axes``, sequence over ``seq``, heads over ``head_axis``) and
    returns the attention output with the same sharding. Composable with
    jit — shard_map nests inside a jitted train step.
    """
    spec = P(tuple(batch_axes), mesh_lib.AXIS_SEQ, head_axis, None)
    fn = _shard_map(
        functools.partial(ring_attention, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn


# --- Mesh context: lets models opt into SP via ``attn_impl="ring"`` ----------
#
# Models dispatch attention through a config string (mirroring how the
# reference picks attention by model file); the mesh is ambient state set by
# the training/serving entry point, not threaded through every module.

_ACTIVE_MESH: list[Mesh] = []


class sp_context:
    """``with sp_context(mesh):`` — route ``attn_impl='ring'`` over ``mesh``."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        _ACTIVE_MESH.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _ACTIVE_MESH.pop()
        return False


def active_sp_mesh() -> Mesh | None:
    if _ACTIVE_MESH and _ACTIVE_MESH[-1].shape.get(mesh_lib.AXIS_SEQ, 1) > 1:
        return _ACTIVE_MESH[-1]
    return None


@functools.lru_cache(maxsize=32)
def _cached_ring_fn(mesh: Mesh, causal: bool, scale: float | None):
    return make_ring_attention(mesh, causal=causal, scale=scale)


def context_ring_attention(q, k, v, *, causal: bool = True, scale=None):
    """Ring attention over the ambient SP mesh; caller checked it is set."""
    mesh = active_sp_mesh()
    if mesh is None:
        raise RuntimeError(
            "attn_impl='ring' needs an active sp_context(mesh) with seq>1"
        )
    return _cached_ring_fn(mesh, causal, scale)(q, k, v)
