"""Pipeline parallelism: GPipe-style microbatching over the mesh.

The reference has PP only at inference, via vLLM's Ray executor
(``Deployment/Ray/serve_deploy_examples/qwen3_app_pipeline_parallel.yaml:
22-30`` — ``pipeline_parallel_size: 2`` across nodes); training PP is
absent. Here PP is a first-class *training* schedule, TPU-shaped: no Ray,
no per-stage processes — one SPMD program under ``shard_map`` where

- each device along the ``model`` mesh axis holds one **stage**: an equal
  slice of the transformer blocks, stacked ``(layers_per_stage, ...)`` and
  sharded on the leading axis (stem/head replicated — their FLOPs are
  negligible and SPMD keeps one program),
- microbatches flow through the ring with ``jax.lax.ppermute`` over ICI:
  at step ``t`` stage 0 injects microbatch ``t`` while stage ``s``
  processes microbatch ``t − s``; after ``n_micro + n_stages − 1`` steps
  every microbatch has crossed every stage (the GPipe fill/drain
  schedule),
- the loop is a ``lax.scan``, so reverse-mode AD differentiates straight
  through the schedule — the backward pipeline (reverse ppermutes) falls
  out of autodiff instead of hand-written send/recv,
- the math is *identical* to the unpipelined model (GPipe is exact, unlike
  async PP schemes) — tested by equality against ``model.apply``.

Entry points: :func:`split_gpt_params` / :func:`make_pipeline_loss_fn` for
the GPT family, and :func:`pipeline_strategy` returning the mesh spec.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from llm_in_practise_tpu.core import mesh as mesh_lib
from llm_in_practise_tpu.models import layers
from llm_in_practise_tpu.ops.rope import sinusoidal_embeddings

AXIS = "model"  # stages live on the tensor/model axis of the 5-axis mesh


def pipeline_mesh(n_stages: int, data: int = -1, devices=None) -> Mesh:
    return mesh_lib.build_mesh(
        mesh_lib.MeshSpec(data=data, model=n_stages), devices=devices
    )


def split_gpt_params(params, n_layer: int):
    """GPT param tree → (stem_and_head dict, stacked blocks (n_layer, ...)).

    ``stem`` keeps everything that is not a block (tok_embed, pos_embed,
    ln_f, lm_head) — replicated; the stacked blocks shard over ``model``.
    """
    stem = {k: v for k, v in params.items() if not k.startswith("block_")}
    blocks = [params[f"block_{i}"] for i in range(n_layer)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return stem, stacked


def merge_gpt_params(stem, stacked, n_layer: int):
    """Inverse of :func:`split_gpt_params` (for checkpoint interop)."""
    params = dict(stem)
    for i in range(n_layer):
        params[f"block_{i}"] = jax.tree_util.tree_map(
            lambda x: x[i], stacked
        )
    return params


def _gpt_fns(cfg):
    """(embed_fn, block_fn, head_fn) over raw param dicts for a GPTConfig."""
    block = layers.TransformerBlock(
        cfg.embed_dim, cfg.n_head, cfg.mlp_ratio, cfg.dropout,
        norm_first=cfg.norm_first, activation=cfg.activation,
        use_rope=cfg.pos_embedding == "rope",
        rope_theta=cfg.rope_theta, max_seq_len=cfg.seq_len,
        attn_impl=cfg.attn_impl,
    )
    compute_dtype = jnp.dtype(cfg.compute_dtype)

    def embed_fn(stem, tokens):
        x = stem["tok_embed"]["embedding"][tokens]
        l = tokens.shape[-1]
        if cfg.pos_embedding == "learned":
            x = x + stem["pos_embed"][:l]
        elif cfg.pos_embedding == "sinusoidal":
            x = x + sinusoidal_embeddings(cfg.seq_len, cfg.embed_dim)[:l]
        return x.astype(compute_dtype)

    def block_fn(block_params, h):
        out, _ = block.apply({"params": block_params}, h, deterministic=True)
        return out

    def head_fn(stem, h):
        h = _layer_norm(stem["ln_f"], h.astype(jnp.float32))
        if cfg.tie_weights:
            return h @ stem["tok_embed"]["embedding"].T
        return h @ stem["lm_head"]["kernel"] + stem["lm_head"]["bias"]

    return embed_fn, block_fn, head_fn


def _layer_norm(p, x, eps: float = 1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def make_pipeline_loss_fn(cfg, mesh: Mesh, n_micro: int):
    """Jittable ``loss(stem, stacked_blocks, x, y) -> mean CE`` running the
    GPipe schedule over ``mesh``'s ``model`` axis.

    x, y: (B, L) int32 with ``B % n_micro == 0``; blocks stacked
    ``(n_layer, ...)`` with ``n_layer %% n_stages == 0``.
    """
    n_stages = mesh.shape[AXIS]
    if cfg.n_layer % n_stages:
        raise ValueError(
            f"n_layer {cfg.n_layer} not divisible by {n_stages} stages"
        )
    if cfg.dropout > 0:
        # the schedule runs blocks deterministically (no rng plumbing yet);
        # training with a dropout config would silently diverge from the
        # unpipelined path — refuse instead
        raise ValueError(
            "pipeline loss runs deterministically; set dropout=0.0 in the "
            "model config (rng threading through the schedule is not wired)"
        )
    embed_fn, block_fn, head_fn = _gpt_fns(cfg)

    def stage_body(stem, local_blocks, tokens, targets):
        """Runs on one device: local_blocks (layers_per_stage, ...)."""
        sid = jax.lax.axis_index(AXIS)
        last = n_stages - 1
        mb, l = tokens.shape[1], tokens.shape[2]
        act0 = jnp.zeros((mb, l, cfg.embed_dim),
                         jnp.dtype(cfg.compute_dtype))

        def run_blocks(h):
            def scan_fn(h, bp):
                return block_fn(bp, h), None
            h, _ = jax.lax.scan(scan_fn, h, local_blocks)
            return h

        def step(carry, t):
            act, total, count = carry
            # stage 0 injects microbatch t (clamped when draining)
            inject = embed_fn(stem, tokens[jnp.clip(t, 0, n_micro - 1)])
            act = jnp.where(sid == 0, inject, act)
            act = run_blocks(act)
            # last stage scores the microbatch that has finished all stages
            out_mb = t - last
            logits = head_fn(stem, act)
            tgt = targets[jnp.clip(out_mb, 0, n_micro - 1)]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ll = jnp.take_along_axis(logp, tgt[..., None], -1)[..., 0]
            use = (sid == last) & (out_mb >= 0) & (out_mb < n_micro)
            total = total + jnp.where(use, -ll.sum(), 0.0)
            count = count + jnp.where(use, jnp.asarray(tgt.size, jnp.float32),
                                      0.0)
            # rotate: stage s -> s+1 (ring; last->0 carries drained acts)
            act = jax.lax.ppermute(
                act, AXIS, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (act, total, count), None

        steps = n_micro + n_stages - 1
        (act, total, count), _ = jax.lax.scan(
            step, (act0, 0.0, 0.0), jnp.arange(steps)
        )
        # loss accumulated on the last stage; share it
        total = jax.lax.psum(total, AXIS)
        count = jax.lax.psum(count, AXIS)
        return total / jnp.maximum(count, 1.0)

    mapped = shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(P(), P(AXIS), P(), P()),
        out_specs=P(),
        check_vma=False,
    )

    def loss_fn(stem, stacked_blocks, x, y):
        b, l = x.shape
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
        tokens = x.reshape(n_micro, b // n_micro, l)
        targets = y.reshape(n_micro, b // n_micro, l)
        return mapped(stem, stacked_blocks, tokens, targets)

    return loss_fn


def reference_loss(model, params, x, y):
    """Unpipelined CE with the same reduction — the equality target."""
    from llm_in_practise_tpu.train.losses import cross_entropy

    logits = model.apply({"params": params}, x, deterministic=True)
    return cross_entropy(logits, y)[0]
