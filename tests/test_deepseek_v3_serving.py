"""DeepSeek-V3 through the serving engine (paged latent pages, chunked
prefill, the fused mixed step), its step statistics, what the engine
refuses for it, and the benchmark's check against a wrong variant; tiny
sizes on the CPU."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v3 as ref
from benchmark.runners import serve_latent_cell as cell
from llm_in_practise_tpu.models import deepseek_v3 as dsv3
from llm_in_practise_tpu.serve import paged_kv
from llm_in_practise_tpu.serve.engine import InferenceEngine, SamplingParams
from llm_in_practise_tpu.serve.paged_kv import (
    PagedKV,
    kv_row_bytes,
    lane_whole,
)

# the two physical forms of a latent pool (paged_kv.stored_by_pages): what
# the rule gives a one-vector row, and the flat form the tests steer a
# pool into by replacing the rule (the program has no option for it)
FORMS = ["pages", "rows"]


def _steer(mp, form):
    if form == "rows":
        mp.setattr(paged_kv, "stored_by_pages", lambda tails: False)

GREEDY = SamplingParams(temperature=0.0, greedy=True, max_tokens=10)


def _engine(cfg, params, **kw):
    opts = dict(max_slots=4, cache_len=128, kv_layout="paged",
                chunked_prefill=16, cache_dtype=jnp.float32)
    opts.update(kw)
    return InferenceEngine(dsv3.DeepSeekV3(cfg), params, **opts)


@pytest.fixture(scope="module", params=FORMS)
def served(request):
    """One engine a pool form; a 40-token prompt decodes while a 70-token
    one chunks beside it (fused mixed steps)."""
    cfg = dsv3.deepseek_v3_config(compute_dtype="float32", experts_held=8)
    params = dsv3.random_params(cfg, 3, jnp.float32)
    with pytest.MonkeyPatch.context() as mp:
        _steer(mp, request.param)
        eng = _engine(cfg, params)
    assert eng.paged.form == request.param
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, cfg.vocab_size, n).tolist() for n in (40, 70)]
    eng.step_stats.capture = []
    first = eng.submit(prompts[0], GREEDY)
    head = first.next_item()
    second = eng.submit(prompts[1], GREEDY)
    tokens = [[head] + first.result(), second.result()]
    with eng._lock:     # the last step books its statistics at its end
        captured, eng.step_stats.capture = eng.step_stats.capture, None
        records = eng.steptrace.records(limit=200)
    yield types.SimpleNamespace(cfg=cfg, params=params, eng=eng,
                                prompts=prompts, tokens=tokens,
                                captured=captured, records=records,
                                form=request.param)
    eng.stop()


def _own_generate(cfg, params, prompt, n):
    """The model's own cached greedy generate, and its logits at the
    prompt's last position."""
    model = dsv3.DeepSeekV3(cfg)
    cache = model.init_cache(1, 128, dtype=jnp.float32)
    lg, cache = model.apply({"params": params}, jnp.asarray([prompt]),
                            cache=cache)
    last = np.asarray(lg[0, -1])
    out = [int(jnp.argmax(lg[0, -1]))]
    for _ in range(n - 1):
        lg, cache = model.apply({"params": params}, jnp.asarray([[out[-1]]]),
                                cache=cache)
        out.append(int(jnp.argmax(lg[0, -1])))
    return out, last


def test_engine_tokens_equal_the_models_own_cached_generate(served):
    assert served.eng.mixed_blocks >= 3     # one row chunked, one decoded
    for prompt, tokens in zip(served.prompts, served.tokens):
        want, _ = _own_generate(served.cfg, served.params, prompt, 10)
        assert tokens == want


def test_paged_prefill_logits_equal_the_contiguous_forward(served):
    """The last chunk of each prompt through the page pool: its
    last-position logits, as the program returned them."""
    ended = [c for c in served.captured if c["last_logits"]]
    assert [c["kind"] for c in ended] == ["chunk", "mixed"]
    for c, prompt in zip(ended, served.prompts):
        _, want = _own_generate(served.cfg, served.params, prompt, 1)
        (got,) = c["last_logits"].values()
        assert np.abs(got - want).max() < 1e-5


def test_step_records_and_counters(served):
    st = served.eng.step_stats
    recs = served.records
    for key, total in (("latent_tokens_attended", st.latent_tokens_attended),
                       ("latent_view_tokens", st.latent_view_tokens),
                       ("moe_assignments_held", st.load.assignments),
                       ("moe_experts_touched", st.load.experts_touched),
                       ("moe_max_expert_load", st.load.max_load),
                       ("moe_layer_passes", st.load.layer_passes),
                       ("prefill_qk_pairs", st.prefill_qk_pairs)):
        assert sum(r.get(key, 0) for r in recs) == total > 0
    # 40 + 70 prompt tokens in chunks of 16: the causal pairs, by hand
    assert st.prefill_qk_pairs == 40 * 41 // 2 + 70 * 71 // 2
    assert st.prefill_keys_read == (16 + 32 + 40) + (16 + 32 + 48 + 64 + 70)
    # 2 routed layers; a decode step routes the whole 4-slot plane
    dec = [r for r in recs if r.get("moe_layer_passes") == 2
           and not r.get("chunk_rows")]
    assert dec and all(r["moe_experts_touched"] <= 2 * 8 for r in dec)
    assert served.eng.routing_load is st.load
    assert 0 < st.latent_tokens_attended < st.latent_view_tokens
    # which form ran: a pool stored by pages gathers whole pages for a
    # chunk row's one-row view (its decode reads the pages in place and
    # gathers none), a flat one none
    gathered = served.eng.view_pages_gathered
    assert sum(r.get("view_pages", 0) for r in recs) == gathered
    if served.form == "pages":
        chunks = sum(r.get("prefill_chunk_capacity", 0) // 16 for r in recs)
        assert gathered >= chunks > 0
    else:
        assert gathered == 0 and not any("view_pages" in r for r in recs)


def _jitted(fn):
    """The ``jax.jit`` under the engine's meters."""
    while not hasattr(fn, "_cache_size"):
        fn = fn.__wrapped__
    return fn


def test_decode_reads_the_latent_pages_where_they_lie(served):
    """The class declares ``reads_pages``: a pool stored by pages gives a
    decode step no view of any layer. The step books the rows ONE layer's
    reader copied (each live row's length up to whole blocks: a slot's 8
    pages here, one block) and the pages x 3 layers, and gathers nothing;
    ONE decode executable served every length; a mixed step still gathers
    its chunk row's one-row view. A flat pool has no pages to hand over:
    its decode gathers a pow2 view as ever."""
    eng, st = served.eng, served.eng.step_stats
    pg = eng.paged
    dec = [r for r in served.records if "latent_tokens_attended" in r]
    assert len({r["latent_tokens_attended"] for r in dec}) > 8
    if served.form == "rows":
        assert not any(pg.in_place) and not eng._reads_pages
        assert st.page_block == 0 and st.global_pages_read == 0
        assert all(r["latent_view_tokens"] in (4 * 64, 4 * 128)
                   and "global_pages_read" not in r for r in dec)
        return
    assert pg.in_place == [True] * 3 and eng._reads_pages
    assert st.page_block == pg.pages_per_slot == 8
    for r in dec:
        live, rest = divmod(r["latent_view_tokens"], 8 * pg.page_size)
        assert 1 <= live <= eng.max_slots and rest == 0
        assert r["latent_tokens_attended"] <= r["latent_view_tokens"]
        assert r["global_pages_read"] == live * 8 * 3
        chunked = r.get("prefill_chunk_capacity", 0) // 16
        # a chunk row's one-row view, whole pages; nothing for the plane
        assert (r["view_pages"] > 0) == (chunked > 0)
    assert st.global_pages_read == sum(r["global_pages_read"] for r in dec)
    assert _jitted(eng._pg_decode)._cache_size() == 1


def test_a_chunk_rows_view_beside_rows_read_in_place(served):
    """A 70-token prompt chunks beside a 30-token one that decodes: its
    view in a mixed step is its own ``done`` + a chunk up to a power of two
    AND no narrower than the decoding row's length + a chunk gives (64),
    the narrowest a gathered engine builds there, so its first chunks (16
    and 32 wide by themselves) build no mixed program of their own."""
    if served.form == "rows":
        pytest.skip("a flat pool has no pages to read in place")
    eng = served.eng
    with eng._lock:
        seen = eng.steptrace.records(limit=1)[-1]["seq"]
    lead = eng.submit(served.prompts[0][:30], GREEDY)
    lead.next_item()
    eng.submit(served.prompts[1],
               dataclasses.replace(GREEDY, max_tokens=2)).result()
    lead.result()
    with eng._lock:
        mixed = [r for r in eng.steptrace.records(limit=60)
                 if r["seq"] > seen and "global_pages_read" in r
                 and r["view_pages"]]
    # 70 tokens: chunks at done = 0 .. 64; one row's view of 16-row pages
    assert [r["view_pages"] * 16 for r in mixed] == [64, 64, 64, 64, 128]


class _Gathered(dsv3.DeepSeekV3):
    """The same model on the gathered path: a decode program gets a pow2
    view of every layer."""
    reads_pages = False


def test_pages_in_place_give_the_gathered_paths_tokens(served):
    """The 40-token prompt again, alone, through an engine whose decode
    GATHERS (``reads_pages`` False): the same greedy tokens and the same
    last-position logits as the rows read in place gave."""
    if served.form == "rows":
        pytest.skip("a flat pool gathers already")
    twin = InferenceEngine(_Gathered(served.cfg), served.params, max_slots=4,
                           cache_len=128, kv_layout="paged",
                           chunked_prefill=16, cache_dtype=jnp.float32)
    assert twin.paged.form == "pages"
    assert not any(twin.paged.in_place) and not twin._reads_pages
    twin.step_stats.capture = []
    handle = twin.submit(served.prompts[0], GREEDY)
    while twin.step():
        pass
    assert handle.result() == served.tokens[0]
    (want,) = [c["last_logits"] for c in twin.step_stats.capture
               if c["last_logits"]]
    (got,) = [c["last_logits"] for c in served.captured
              if c["kind"] == "chunk" and c["last_logits"]]
    np.testing.assert_allclose(next(iter(got.values())),
                               next(iter(want.values())), atol=1e-5)
    # a gathered decode's view is every slot x a pow2 width
    dec = [r for r in twin.steptrace.records(limit=50)
           if "latent_tokens_attended" in r]
    assert dec and all(r["latent_view_tokens"] == 4 * 64
                       and "global_pages_read" not in r for r in dec)
    twin.stop()


def test_rows_read_in_place_are_booked_up_to_whole_blocks():
    """Five layers over slots longer than a block (32 pages of 16 rows):
    ``latent_view_tokens`` is the live rows' lengths up to whole blocks of
    ONE reader, ``global_pages_read`` those pages x the five layers that
    read them; an idle slot reads nothing."""
    cfg = dsv3.deepseek_v3_config(compute_dtype="float32", experts_held=8,
                                  n_layer=5, max_seq_len=2048)
    eng = _engine(cfg, dsv3.random_params(cfg, 3, jnp.float32),
                  cache_len=2048)
    st = eng.step_stats
    assert st.page_block == 32 and st.block_rows == 512
    assert st.page_readers == 5
    # slots 0, 1 and 3 decode at lengths 512, 513 and 1,301 (their new
    # row included); slot 2 is idle
    eng.slot_len[:] = [511, 512, 0, 1300]
    st.note_decode_view([0, 1, 3], eng.cache_len)
    blocks = 1 + 2 + 3
    assert st.latent_tokens_attended == 512 + 513 + 1301
    assert st.latent_view_tokens == blocks * 512
    assert st.global_pages_read == blocks * 32 * 5
    eng.stop()


def test_latent_pool_bytes_read_right(served):
    cfg, eng = served.cfg, served.eng
    row = cfg.n_layer * cfg.latent_dim * 4     # the model's own row
    assert kv_row_bytes(dsv3.DeepSeekV3(cfg), jnp.float32) == row
    assert kv_row_bytes(dsv3.DeepSeekV3(cfg), jnp.bfloat16) == row // 2
    # stored by pages, each layer's row is padded to whole lane tiles
    width = (lane_whole(cfg.latent_dim) if served.form == "pages"
             else cfg.latent_dim)
    n_pages = eng.paged.pool.num_pages
    assert eng.paged.row_bytes == cfg.n_layer * width * 4
    assert eng.paged.pool_bytes == eng.paged.row_bytes * n_pages * 16
    assert eng.paged.kv[0]["ckv"].shape == (
        (n_pages, 16, width) if served.form == "pages"
        else (n_pages * 16, width))
    kv = eng.debug_kv()
    assert kv["page_bytes"] == eng.paged.row_bytes * 16
    assert kv["buffers"] == {"ckv": {"form": served.form,
                                     "row_bytes": width * 4}}
    # a latent has no head axis: every pool leaf replicates under a mesh
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    for layer in PagedKV._pool_shardings(eng.paged.kv, mesh):
        assert all(s.spec == jax.sharding.PartitionSpec()
                   for s in layer.values())


@pytest.fixture
def small(monkeypatch):
    """``small(form, **engine options)``: a latent engine whose pool is in
    ``form``, stepped by the test."""
    cfg = dsv3.deepseek_v3_config(compute_dtype="float32", experts_held=8)
    params = dsv3.random_params(cfg, 3, jnp.float32)

    def make(form, **kw):
        _steer(monkeypatch, form)
        eng = _engine(cfg, params, **kw)
        assert eng.paged.form == form
        return types.SimpleNamespace(cfg=cfg, params=params, eng=eng)

    return make


def _drain(eng, prompts, sp):
    handles = [eng.submit(p, sp) for p in prompts]
    while eng.step():
        pass
    return [h.result() for h in handles]


def _row_width(eng):
    return eng.paged.tails[0]["ckv"][0]


def _page_rows(eng, layer, page):
    """One physical page's 16 rows at the model's width, in either form."""
    buf, (width,) = eng.paged.kv[layer]["ckv"], eng.paged.tails[layer]["ckv"]
    rows = buf[page] if eng.paged.form == "pages" else buf[page * 16:
                                                           page * 16 + 16]
    return np.asarray(rows[:, :width])


@pytest.mark.parametrize("form", FORMS)
def test_one_shot_prefill_writes_its_rows(small, form):
    """No chunking: a prompt's rows reach the pool through
    ``_paged_write_rows_fn`` (bucket-wide, the padding to the trash page),
    12 and 37 tokens: inside one page and across two boundaries."""
    sv = small(form, chunked_prefill=None)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(4, sv.cfg.vocab_size, n).tolist()
               for n in (12, 37)]
    got = _drain(sv.eng, prompts, GREEDY)
    for prompt, tokens in zip(prompts, got):
        assert tokens == _own_generate(sv.cfg, sv.params, prompt, 10)[0]
    sv.eng.paged.pool.check_leaks(0)


@pytest.mark.parametrize("form", FORMS)
def test_cow_fork_copies_the_page(small, form):
    """``_paged_page_copy_fn``: a write window on a shared page forks it;
    the writer's private copy holds the page's rows, the sharer's page is
    untouched."""
    eng = small(form).eng
    pool = eng.paged.pool
    pages = pool.alloc(2)
    eng.paged.map_shared(0, list(pages))
    pool.share(pages)                           # a phantom second reader
    rows = jnp.arange(32 * _row_width(eng), dtype=jnp.float32).reshape(
        1, 32, -1) + 1.0
    eng.paged.kv = eng._pg_write_rows(
        eng.paged.kv, [{"ckv": rows}] * eng.paged.n_layers,
        jnp.asarray(eng.paged.rows_scatter_idx([0], [32], 32)))
    before = _page_rows(eng, 0, pages[1])
    np.testing.assert_array_equal(before, np.asarray(rows[0, 16:]))
    eng._paged_cow_fork(0, 20, 4)               # a window inside page 1
    forked = int(eng.paged.block_tables[0, 1])
    assert forked != pages[1] and pool.refcount(forked) == 1
    for layer in range(eng.paged.n_layers):
        np.testing.assert_array_equal(_page_rows(eng, layer, forked), before)
        np.testing.assert_array_equal(_page_rows(eng, layer, pages[1]),
                                      before)
    eng.paged.release_slot(0)
    pool.release(pages)
    pool.check_leaks(0)


@pytest.mark.parametrize("form", FORMS)
def test_preemption_and_resume_keep_the_tokens(small, form):
    """A pool for two of three requests: preemption fires, the requeued
    request re-prefills over its registered pages, and every stream is the
    model's own."""
    sv = small(form, kv_pool_tokens=96, prefix_cache=True)
    sp = SamplingParams(temperature=0.0, greedy=True, max_tokens=40)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(4, sv.cfg.vocab_size, 20).tolist()
               for _ in range(3)]
    got = _drain(sv.eng, prompts, sp)
    assert sv.eng.preemptions > 0
    for prompt, tokens in zip(prompts, got):
        assert tokens == _own_generate(sv.cfg, sv.params, prompt, 40)[0]
    sv.eng.prefix_cache.clear()
    sv.eng.paged.pool.check_leaks(0)


@pytest.mark.parametrize("form", FORMS)
def test_an_unmapped_page_reads_the_trash_page(small, form):
    """A view wider than a slot's mapped pages: the pages behind them are
    the trash page's rows (beyond the row's index, never attended), and
    the gather index is whole pages only where the pool is stored so."""
    eng = small(form).eng
    pg = eng.paged
    (page,) = pg.pool.alloc(1)
    pg.map_shared(1, [page])
    width = _row_width(eng)
    mark = lambda v: [{"ckv": jnp.full((1, 16, width), v, jnp.float32)}  # noqa: E731
                      ] * pg.n_layers
    # the mapped page holds 5s; a discarded write (length 0) leaves 7s in
    # the trash page
    pg.kv = eng._pg_write_rows(
        pg.kv, mark(5.0), jnp.asarray(pg.rows_scatter_idx([1], [16], 16)))
    pg.kv = eng._pg_write_rows(
        pg.kv, mark(7.0), jnp.asarray(pg.rows_scatter_idx([1], [0], 16)))
    idx = pg.view_idx(64)
    assert idx.shape == ((4, 4) if form == "pages" else (4, 64))
    if form == "pages":
        assert idx[1].tolist() == [page, 0, 0, 0]
    np.testing.assert_array_equal(idx, pg.view_idx(64, slots=np.arange(4)))
    view = eng._paged_view(pg.kv, jnp.asarray(idx),
                           jnp.zeros((4,), jnp.int32))
    for layer in view:
        assert layer["ckv"].shape == (4, 64, width)
        got = np.asarray(layer["ckv"])
        assert (got[1, :16] == 5).all() and (got[1, 16:] == 7).all()
        assert (got[0] == 7).all()              # a slot with no page at all
    pg.release_slot(1)
    pg.pool.check_leaks(0)


def test_engine_refuses_what_the_model_cannot_meet():
    cfg = dsv3.deepseek_v3_config(compute_dtype="float32", experts_held=8)
    params = dsv3.random_params(cfg, 3, jnp.float32)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    for kw, match in (({"mesh": mesh}, "mesh"),
                      ({"kv_layout": "contiguous"}, "contiguous"),
                      ({"speculative_k": 2}, "speculative")):
        with pytest.raises(ValueError, match=match):
            _engine(cfg, params, **kw)


@dataclasses.dataclass(frozen=True)
class _NoMSquared(dsv3.DeepSeekV3Config):
    """The wrong variant: YaRN's tables without the m^2 of the softmax
    scale."""

    @property
    def attention_scale(self):
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


@pytest.mark.parametrize("wrong", [False, True])
def test_the_cells_check_fails_a_wrong_variant(wrong):
    """benchmark/runners/serve_latent_cell.py::check, as the chip runs
    it, at toy size: the right model passes, the same weights served
    WITHOUT m^2 fail (the query / key projections are scaled up so that
    attention is not flat: under N(0, 0.02) at width 128 every score is
    ~0 and no scale shows)."""
    kw = dict(compute_dtype="bfloat16", experts_held=8, hidden_size=128,
              yarn=(40.0, 32, 32.0, 1.0, 1.0, 1.0))
    right = dsv3.deepseek_v3_config(**kw)
    served_cfg = (_NoMSquared(**dataclasses.asdict(right)) if wrong
                  else right)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 8 if any(
            k in jax.tree_util.keystr(path)
            for k in ("q_b_proj", "kv_a_proj", "kv_b_proj")) else a,
        dsv3.random_params(right, 5, jnp.bfloat16))
    eng = _engine(served_cfg, params, cache_dtype=jnp.bfloat16,
                  cache_len=256)
    eng.start()
    try:
        sv = types.SimpleNamespace(engine=eng, cfg=right, params=params,
                                   geom=ref.geometry(right))
        out = cell.check(sv, {"prompt_tokens": {"min": 32, "max": 200}}, 7)
    finally:
        eng.stop()
    assert out["ok"] is (not wrong), out
    if wrong:   # by the logits, not by a technicality
        assert out["worst"]["rms_over_std"] > 5 * ref.LOGIT_RMS_TOL, out
