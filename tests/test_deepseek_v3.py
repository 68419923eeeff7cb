"""DeepSeek-V3 (models/deepseek_v3.py) against its plain reference
(benchmark/reference/deepseek_v3.py) at tiny sizes on the CPU: the forward,
the latent cache through both layouts, the two attention forms, YaRN, the
router, one chip's share of the routed experts, and the serving engine."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v3 as ref
from llm_in_practise_tpu.models import deepseek_v3 as dsv3
from llm_in_practise_tpu.ops import grouped_experts as ge
from llm_in_practise_tpu.ops import mla_attention as mla
from llm_in_practise_tpu.ops import rope

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def tiny():
    cfg = dsv3.deepseek_v3_config(compute_dtype="float32", experts_held=8,
                                  expert_offset=8)
    return cfg, dsv3.DeepSeekV3(cfg), dsv3.random_params(cfg, 3, jnp.float32)


def _full(model, params, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply({"params": params}, jnp.asarray(ids)))


def test_full_forward_logits_match_reference(tiny):
    """40 positions pass YaRN's 32 original positions; the held share
    (experts 8-15 of 32) is left out of both alike."""
    cfg, model, params = tiny
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0,
                                        cfg.vocab_size))
    want, _ = ref.Reference(ref.geometry(cfg)).logits(
        params, ids[0].tolist(), last=40)
    got = _full(model, params, ids)[0]
    assert np.abs(got - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    assert np.std(want) > 0.01


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_then_decode_equals_full_forward(tiny, chunk):
    """The contiguous latent cache: chunks through the blocked naive
    form, single tokens through the absorbed form, logits at EVERY
    position equal to the cache-free forward's."""
    cfg, model, params = tiny
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 28), 0,
                             cfg.vocab_size)
    full = _full(model, params, ids)
    cache = model.init_cache(2, 64, dtype=jnp.float32)
    assert cache[0]["ckv"].shape == (2, 64, cfg.latent_dim)
    assert set(cache[0]) == {"ckv", "index"}
    outs = []
    with jax.default_matmul_precision("highest"):
        for a in range(0, 16, chunk):
            lg, cache = model.apply({"params": params}, ids[:, a:a + chunk],
                                    cache=cache)
            outs.append(lg)
        for t in range(16, 28):
            lg, cache = model.apply({"params": params}, ids[:, t:t + 1],
                                    cache=cache)
            outs.append(lg)
    got = np.asarray(jnp.concatenate(outs, axis=1))
    assert np.abs(got - full).max() < 1e-5


def test_absorbed_form_equals_naive_form():
    """The same weights and latent rows through decode_attention
    (absorbed), prefill_attention naive and prefill_attention absorbed."""
    b, lq, h, dn, dr, dv, rank, w = 2, 8, 4, 8, 4, 8, 16, 32
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    qn = jax.random.normal(k[0], (b, lq, h, dn))
    qr = jax.random.normal(k[1], (b, lq, h, dr))
    lat = jax.random.normal(k[2], (b, w, rank + dr))
    wkvb = 0.3 * jax.random.normal(k[3], (rank, h, dn + dv))
    start = jnp.array([5, 17])
    kw = dict(rank=rank, scale=0.3, key_block=16, block_q=4, block_k=8)
    naive = mla.prefill_attention(qn, qr, lat, start, wkvb, **kw)
    absorbed = mla.prefill_attention(qn, qr, lat, start, wkvb,
                                     absorbed=True, **kw)
    one = mla.decode_attention(qn[:, -1:], qr[:, -1:], lat, start + lq - 1,
                               wkvb, rank=rank, scale=0.3)
    assert np.abs(np.asarray(naive - absorbed)).max() < 1e-5
    assert np.abs(np.asarray(one - naive[:, -1:])).max() < 1e-5


def test_yarn_tables_and_scale_match_the_published_formulas():
    """DeepSeek-V3's own numbers: 64 rope dims, theta 10,000, factor 40
    over 4,096, beta 32 / 1, mscale = mscale_all_dim = 1."""
    f = np.asarray(rope.yarn_inv_freq(64, 10000.0, factor=40.0,
                                      original_max_len=4096))
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction dims: floor / ceil of d ln(L / (2 pi beta)) / (2 ln theta)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    assert np.allclose(f[:low + 1], base[:low + 1], rtol=1e-6)  # kept
    assert np.allclose(f[high:], base[high:] / 40, rtol=1e-6)   # / factor
    mid = (low + high) // 2
    ramp = (mid - low) / (high - low)
    assert np.isclose(f[mid], base[mid] / 40 * ramp + base[mid] * (1 - ramp),
                      rtol=1e-6)
    geom = {"qk_rope_head_dim": 64, "rope_theta": 10000.0,
            "yarn": (40.0, 4096, 32.0, 1.0, 1.0, 1.0),
            "qk_nope_head_dim": 128}
    assert np.allclose(f, ref.rope_frequencies(geom), rtol=1e-6)
    cos, sin = rope.precompute_yarn_cos_sin(
        64, 16384, 10000.0, factor=40.0, original_max_len=4096, mscale=1.0,
        mscale_all_dim=1.0)
    for pos in (100, 10000):        # below and above 4,096
        # float32 angles of ~1e4 radians: 1e-3 of a turn
        assert np.allclose(cos[pos], np.cos(pos * f.astype(np.float64)),
                           atol=2e-3)
        assert np.allclose(sin[pos], np.sin(pos * f.astype(np.float64)),
                           atol=2e-3)
    m = 0.1 * 1.0 * math.log(40) + 1
    assert np.isclose(rope.yarn_attention_scale(192, 40.0, 1.0),
                      192 ** -0.5 * m * m)
    assert np.isclose(ref.softmax_scale(geom), 192 ** -0.5 * m * m)
    assert rope.yarn_attention_scale(192, 40.0, 0.0) == 192 ** -0.5


def _route(scores, bias, **kw):
    """``route`` on hand-made sigmoid SCORES: logits through an identity
    router."""
    s = np.asarray(scores, np.float64)
    logits = np.log(s / (1 - s))
    e = s.shape[-1]
    ids, w = ge.route(jnp.asarray(logits, jnp.float32), jnp.eye(e), 2,
                      scoring="sigmoid",
                      bias=jnp.asarray(bias, jnp.float32), **kw)
    return np.asarray(ids), np.asarray(w)


def test_router_bias_changes_the_set_and_not_the_weights():
    s = [[0.9, 0.8, 0.7, 0.6, 0.1, 0.1, 0.1, 0.1]]
    ids, w = _route(s, np.zeros(8))
    assert sorted(ids[0]) == [0, 1]
    assert np.allclose(sorted(w[0]), [0.8 / 1.7, 0.9 / 1.7], atol=1e-6)
    ids, w = _route(s, [0, 0, 0.15, 0, 0, 0, 0, 0])   # 0.7 + 0.15 > 0.8
    assert sorted(ids[0]) == [0, 2]
    # weights from s, never from s + b
    assert np.allclose(sorted(w[0]), [0.7 / 1.6, 0.9 / 1.6], atol=1e-6)


def test_router_group_cut_excludes_a_globally_top_expert():
    # 4 groups of 2, keep 1 group: group 0 sums 0.9 + 0.1, group 1
    # 0.6 + 0.55: the global best (0.9) is cut with its group
    s = [[0.9, 0.1, 0.6, 0.55, 0.2, 0.2, 0.3, 0.3]]
    ids, w = _route(s, np.zeros(8), n_group=4, topk_group=1)
    assert sorted(ids[0]) == [2, 3]
    assert np.allclose(sorted(w[0]), [0.55 / 1.15, 0.6 / 1.15], atol=1e-6)
    # the reference's choose agrees
    geom = {"top_k": 2, "n_group": 4, "topk_group": 1, "norm_topk": True,
            "routed_scaling_factor": 1.0}
    dense, _ = ref.choose(np.asarray(s), np.asarray(s), geom, None, 0)
    assert sorted(np.flatnonzero(dense[0])) == [2, 3]


def test_router_scale_and_unnormalised_weights():
    s = [[0.9, 0.8, 0.7, 0.6, 0.1, 0.1, 0.1, 0.1]]
    _, w = _route(s, np.zeros(8), scale=2.5)
    assert np.isclose(w.sum(), 2.5, atol=1e-6)
    _, w = _route(s, np.zeros(8), scale=2.5, norm_topk=False)
    assert np.allclose(sorted(w[0]), [2.0, 2.25], atol=1e-6)


def _previous_route(x, w_router, top_k, *, norm_topk=True):
    """``route`` as the tree before this file's PR had it."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), weights


def test_softmax_routing_and_the_unheld_layer_are_unchanged():
    """What SDAR calls: the softmax router traces to the jaxpr it had,
    and its results and the expert layer's are bit for bit those of the
    same call with the new keywords at their defaults."""
    k = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(k[0], (12, 32))
    w_router = 0.3 * jax.random.normal(k[1], (32, 8))
    now = jax.make_jaxpr(lambda a, b: ge.route(a, b, 2))(x, w_router)
    then = jax.make_jaxpr(lambda a, b: _previous_route(a, b, 2))(x, w_router)
    assert str(now) == str(then)
    ids, w = ge.route(x, w_router, 2)
    ids0, w0 = _previous_route(x, w_router, 2)
    assert np.array_equal(ids, ids0) and np.array_equal(w, w0)
    wg, wu = (0.1 * jax.random.normal(k[i], (8, 32, 16)) for i in (2, 3))
    wd = 0.1 * jax.random.normal(k[4], (8, 16, 32))
    y = ge.grouped_expert_ffn(x, ids, w, wg, wu, wd)
    # against the plain sum over each token's experts
    want = sum(w[:, j, None] * jnp.einsum(
        "nw,nwh->nh",
        jax.nn.silu(jnp.einsum("nh,nhw->nw", x, wg[ids[:, j]]))
        * jnp.einsum("nh,nhw->nw", x, wu[ids[:, j]]), wd[ids[:, j]])
        for j in range(2))
    assert np.abs(np.asarray(y - want)).max() < 1e-5
    # holding all of them is the same sum
    held = ge.grouped_expert_ffn(x, ids, w, wg, wu, wd, held=(0, 8),
                                 n_experts=8)
    assert np.abs(np.asarray(held - y)).max() < 1e-5


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Guide section 4: 32 experts over 4 chips. Each share routes over
    all 32 and computes its own 8; the four routed parts plus the shared
    expert counted ONCE equal the uncut reference's layer output."""
    cfg = dsv3.deepseek_v3_config(compute_dtype="float32")
    layer = dsv3.RoutedExperts(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.hidden_size))
    params = layer.init(jax.random.PRNGKey(6), x)["params"]
    params = jax.tree.map(lambda a: 4.0 * a, params)    # sharper routing
    flat = x[0]
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(flat, params["shared"])
        total = shared
        seen = 0
        for rank in range(4):
            share = dict(params, **{
                k: params[k][8 * rank:8 * (rank + 1)]
                for k in ("w_gate", "w_up", "w_down")})
            y, ids, counts = dsv3.RoutedExperts(cfg.replace(
                experts_held=8, expert_offset=8 * rank)).apply(
                {"params": share}, x)
            total = total + (y[0] - shared)
            seen += int(counts.sum())
        assert seen == 24 * cfg.n_experts_per_tok   # every assignment once
        geom = dict(ref.geometry(cfg), held=(0, 32))
        s, biased = ref.router_scores(flat, params)
        dense, _ = ref.choose(np.asarray(s), np.asarray(biased), geom,
                              None, 0)
        want = ref.held_experts(flat, jnp.asarray(dense), params) + shared
    assert np.abs(np.asarray(total - want)).max() < 1e-4
    assert float(jnp.abs(want - shared).max()) > 1e-3   # the experts count


def test_held_layer_is_dropless_when_every_assignment_is_local():
    """The rows buffer holds four expected loads; a routing that sends
    everything to the held experts takes the full-size branch."""
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    n, e_all, held = 160, 64, 4
    x = jax.random.normal(k[0], (n, 16))
    wg, wu = (0.3 * jax.random.normal(k[i], (held, 16, 8)) for i in (1, 2))
    wd = 0.3 * jax.random.normal(k[3], (held, 8, 16))
    ids = jnp.tile(jnp.arange(8, 12, dtype=jnp.int32)[None, :2], (n, 1))
    w = jnp.full((n, 2), 0.5)
    y = ge.grouped_expert_ffn(x, ids, w, wg, wu, wd, held=(8, held),
                              n_experts=e_all)
    want = sum(0.5 * (jax.nn.silu(x @ wg[j]) * (x @ wu[j])) @ wd[j]
               for j in range(2))
    assert np.abs(np.asarray(y - want)).max() < 1e-5
    assert np.array_equal(ge.held_counts(ids, (8, held)), [n, n, 0, 0])


def test_from_hf_config_on_the_catalog_row():
    row = next(json.loads(line) for line in open(CATALOG)
               if '"name": "DeepSeek-V3"' in line)
    cfg = dsv3.DeepSeekV3Config.from_hf_config(row["config"])
    assert (cfg.n_layer, cfg.first_k_dense_replace, cfg.held) == (
        61, 3, (0, 256))
    assert cfg.n_nextn_predict_layers == 1      # read; no module built
    assert cfg.yarn == (40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert cfg.latent_dim == 576
    count = lambda tree: sum(int(np.prod(a.shape))  # noqa: E731
                             for a in jax.tree.leaves(tree))
    x = jax.ShapeDtypeStruct((1, 8, cfg.hidden_size), jnp.float32)
    attn = jax.eval_shape(lambda: dsv3.MLAttention(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape),
        dsv3.rope_tables(cfg.replace(max_seq_len=16))))
    # the five projection matrices; the two norm scales (1,536 + 512)
    # come on top
    assert count(attn) - 1536 - 512 == 187_105_280
    moe = jax.eval_shape(lambda: dsv3.RoutedExperts(cfg.replace(
        experts_held=1)).init(jax.random.PRNGKey(0), jnp.zeros(x.shape)))
    experts = {k: v for k, v in moe["params"].items()
               if k in ("w_gate", "w_up", "w_down")}
    assert count(experts) == 44_040_192
    assert count(moe["params"]["shared"]) == 44_040_192


@pytest.mark.parametrize("change, match", [
    ({"rope_scaling": {"type": "linear", "factor": 2.0}}, "rope_scaling"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
])
def test_from_hf_config_refuses_what_it_does_not_implement(change, match):
    row = next(json.loads(line) for line in open(CATALOG)
               if '"name": "DeepSeek-V3"' in line)
    with pytest.raises(ValueError, match=match):
        dsv3.DeepSeekV3Config.from_hf_config(dict(row["config"], **change))
