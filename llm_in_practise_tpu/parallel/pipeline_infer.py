"""Pipeline-parallel inference: GPipe-scheduled generate with a
stage-sharded KV cache.

The reference serves with pipeline parallelism through vLLM's Ray
executor (``Deployment/Ray/serve_deploy_examples/
qwen3_app_pipeline_parallel.yaml:22-30`` — ``pipeline_parallel_size: 2``
spanning nodes that can't each fit the model). The TPU-native shape of
the same capability reuses the training pipeline's design
(:mod:`.pipeline`): stages live on the ``model`` mesh axis, transformer
blocks are stacked and sharded on their leading (layer) axis, and
microbatches rotate stage→stage with ``jax.lax.ppermute`` over ICI — one
SPMD program, no per-stage processes, no RPC.

What inference adds over the training schedule is **state**: each stage
owns the KV cache rows of its local layers, stacked
``(layers_per_stage, batch, cache_len, heads, head_dim)`` and sharded on
the layer axis — the cache for the whole model never exists on one chip,
which is the point of PP serving (HBM capacity scales with stages). A
forward processes each microbatch through all stages, reading/writing
only the local cache slice; decode is the same schedule at ``l=1``.

GPipe inference is exact (tested against the unpipelined
:func:`~llm_in_practise_tpu.infer.generate.generate`), with the usual
fill/drain bubble: per token, ``n_micro + n_stages − 1`` stage-times of
latency for ``n_micro`` microbatches of throughput — the reason TP over
ICI is preferred *within* a slice and PP is the cross-slice/HBM-capacity
tool, matching the reference's use of PP strictly across nodes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from llm_in_practise_tpu.parallel.pipeline import AXIS, _gpt_fns


def init_pipeline_cache(cfg, batch: int, cache_len: int, dtype=jnp.float32):
    """Stacked KV cache ``{"k","v"}: (n_layer, batch, cache_len, H, D)``.

    The leading layer axis shards over ``model`` under the forward's
    ``shard_map`` — each stage materializes only its own layers' rows.
    The write index is a single replicated scalar: all sequences advance
    in lockstep (uniform prompt length, one token per decode step).
    """
    head_dim = cfg.embed_dim // cfg.n_head
    shape = (cfg.n_layer, batch, cache_len, cfg.n_head, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def make_pipeline_forward(cfg, mesh: Mesh, n_micro: int):
    """Jittable ``forward(stem, stacked_blocks, cache, tokens, index) ->
    (last_logits (B, vocab), cache)`` over ``mesh``'s ``model`` axis.

    ``tokens``: (B, l) int32 with ``B % n_micro == 0``; ``index`` is the
    scalar cache write position (0 for prefill, prompt_len + t for decode
    step t). Works for any ``l`` — prefill and decode share the code and
    compile once per shape.
    """
    n_stages = mesh.shape[AXIS]
    if cfg.n_layer % n_stages:
        raise ValueError(
            f"n_layer {cfg.n_layer} not divisible by {n_stages} stages")
    _, _, head_fn = _gpt_fns(cfg)  # training embed assumes position 0

    from llm_in_practise_tpu.models import layers as L
    from llm_in_practise_tpu.ops.rope import sinusoidal_embeddings

    compute_dtype = jnp.dtype(cfg.compute_dtype)

    def embed_at(stem, tokens, index):
        """Token + position embedding at absolute cache position ``index``
        (mirrors ``models.gpt.GPT.__call__``'s cached-positions path)."""
        x = stem["tok_embed"]["embedding"][tokens]
        l = tokens.shape[-1]
        positions = index + jnp.arange(l)
        if cfg.pos_embedding == "learned":
            x = x + stem["pos_embed"][positions]
        elif cfg.pos_embedding == "sinusoidal":
            x = x + sinusoidal_embeddings(cfg.seq_len, cfg.embed_dim)[positions]
        return x.astype(compute_dtype)

    block = L.TransformerBlock(
        cfg.embed_dim, cfg.n_head, cfg.mlp_ratio, 0.0,
        norm_first=cfg.norm_first, activation=cfg.activation,
        use_rope=cfg.pos_embedding == "rope",
        rope_theta=cfg.rope_theta, max_seq_len=cfg.seq_len,
        attn_impl=cfg.attn_impl,
    )

    def stage_body(stem, local_blocks, local_k, local_v, tokens, index):
        """One device: local_blocks/local_k/local_v lead with the stage's
        layers; tokens (n_micro, mb, l) replicated."""
        sid = jax.lax.axis_index(AXIS)
        last = n_stages - 1
        mb, l = tokens.shape[1], tokens.shape[2]
        vocab = stem["tok_embed"]["embedding"].shape[0]
        act0 = jnp.zeros((mb, l, cfg.embed_dim), jnp.dtype(cfg.compute_dtype))
        out0 = jnp.zeros((n_micro, mb, vocab), jnp.float32)

        def run_blocks(h, k_mb, v_mb):
            """Scan the stage's layers; k_mb/v_mb: (Lps, mb, cl, H, D)."""
            def scan_fn(h, xs):
                bp, k_layer, v_layer = xs
                cache = {"k": k_layer, "v": v_layer, "index": index}
                h, cache = block.apply({"params": bp}, h,
                                       deterministic=True, cache=cache)
                return h, (cache["k"], cache["v"])
            h, (k_out, v_out) = jax.lax.scan(
                scan_fn, h, (local_blocks, k_mb, v_mb))
            return h, k_out, v_out

        def step(carry, t):
            act, k_all, v_all, out = carry
            mbid = t - sid                       # this stage's microbatch
            valid = (mbid >= 0) & (mbid < n_micro)
            row = jnp.clip(mbid, 0, n_micro - 1) * mb
            # stage 0 injects microbatch t
            inject = embed_at(stem, tokens[jnp.clip(t, 0, n_micro - 1)], index)
            act = jnp.where(sid == 0, inject, act)
            k_mb = jax.lax.dynamic_slice_in_dim(k_all, row, mb, axis=1)
            v_mb = jax.lax.dynamic_slice_in_dim(v_all, row, mb, axis=1)
            act, k_new, v_new = run_blocks(act, k_mb, v_mb)
            # commit the cache slice only when this step carried real work
            k_upd = jax.lax.dynamic_update_slice_in_dim(k_all, k_new, row, 1)
            v_upd = jax.lax.dynamic_update_slice_in_dim(v_all, v_new, row, 1)
            k_all = jnp.where(valid, k_upd, k_all)
            v_all = jnp.where(valid, v_upd, v_all)
            # last stage emits final-position logits for its microbatch
            logits = head_fn(stem, act[:, -1:, :])[:, 0, :].astype(jnp.float32)
            use = (sid == last) & valid
            out_upd = jax.lax.dynamic_update_slice_in_dim(
                out, logits[None], jnp.clip(mbid, 0, n_micro - 1), 0)
            out = jnp.where(use, out_upd, out)
            act = jax.lax.ppermute(
                act, AXIS, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (act, k_all, v_all, out), None

        steps = n_micro + n_stages - 1
        (act, k_all, v_all, out), _ = jax.lax.scan(
            step, (act0, local_k, local_v, out0), jnp.arange(steps))
        # logits live on the last stage only; psum replicates them
        out = jax.lax.psum(out, AXIS)
        return out, k_all, v_all

    mapped = shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(P(), P(AXIS), P(AXIS), P(AXIS), P(), P()),
        out_specs=(P(), P(AXIS), P(AXIS)),
        check_vma=False,
    )

    def forward(stem, stacked_blocks, cache, tokens, index):
        b, l = tokens.shape
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
        grouped = tokens.reshape(n_micro, b // n_micro, l)
        out, k, v = mapped(stem, stacked_blocks, cache["k"], cache["v"],
                           grouped, jnp.asarray(index, jnp.int32))
        return out.reshape(b, -1), {"k": k, "v": v}

    return forward


def pipeline_generate(cfg, mesh: Mesh, stem, stacked_blocks, prompts,
                      max_new_tokens: int, *, n_micro: int | None = None,
                      cache_len: int | None = None, greedy: bool = True,
                      temperature: float = 1.0, rng=None):
    """Generate ``max_new_tokens`` for a batch of uniform-length prompts
    over the stage mesh. Returns (B, max_new_tokens) int32.

    The serving engine buckets prompts to uniform lengths already; this
    is the PP counterpart of
    :func:`~llm_in_practise_tpu.infer.generate.generate`.
    """
    from llm_in_practise_tpu.infer.sampling import sample_token

    prompts = jnp.asarray(prompts, jnp.int32)
    b, plen = prompts.shape
    n_micro = n_micro or mesh.shape[AXIS]
    cache_len = cache_len or min(cfg.seq_len, plen + max_new_tokens)
    if plen + max_new_tokens > cache_len:
        raise ValueError(
            f"prompt {plen} + {max_new_tokens} new > cache_len {cache_len}")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    forward = make_pipeline_forward(cfg, mesh, n_micro)
    cache = init_pipeline_cache(cfg, b, cache_len,
                                jnp.dtype(cfg.compute_dtype))

    def pick(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.vmap(
            lambda lg, k: sample_token(k, lg, temperature=temperature)
        )(logits, jax.random.split(key, logits.shape[0])).astype(jnp.int32)

    @jax.jit
    def run(stem, stacked_blocks, cache, prompts, rng):
        logits, cache = forward(stem, stacked_blocks, cache, prompts, 0)
        rng, key = jax.random.split(rng)
        tok = pick(logits, key)

        def step(carry, t):
            cache, tok, rng = carry
            # decode step t consumes the t-th sampled token, writing its
            # KV at absolute position plen + t
            logits, cache = forward(stem, stacked_blocks, cache,
                                    tok[:, None], plen + t)
            rng, key = jax.random.split(rng)
            nxt = pick(logits, key)
            return (cache, nxt, rng), nxt

        (cache, _, _), rest = jax.lax.scan(
            step, (cache, tok, rng), jnp.arange(max_new_tokens - 1))
        return jnp.concatenate([tok[None], rest], axis=0).T

    with mesh:
        return run(stem, stacked_blocks, cache, prompts, rng)
