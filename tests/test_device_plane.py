"""Device-level performance plane (obs/cost.py + obs/prof.py).

Pins the tentpole's contracts:

- ONE cost model: ``obs/cost.py`` reproduces the committed BENCH
  artifact's audited ``flops_per_token``/``mfu`` numbers exactly
  (BENCH_r04.json — the last artifact whose bench leg ran; r05's
  backend was down), for both the eval-shape path the bench uses and
  the analytic serving geometry, so the live gauges and the artifact
  MFU can never disagree.
- Per-phase device gauges (``llm_dispatch_mfu`` /
  ``llm_dispatch_hbm_bw_util`` / tokens-per-dispatch), compile-event
  counters, device-memory gauges, and SLO goodput render strictly on a
  real server and carry sane values.
- ``POST /debug/profile``: end-to-end on the CPU backend — 200, a
  capture directory containing a Perfetto-loadable trace, 409 while a
  capture is in flight, one at a time.
- ``obs.meter.profile_trace``: reentrancy-safe, trace stopped on
  exception.
"""

import json
import sys
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from promparse import parse_exposition

from llm_in_practise_tpu.obs import cost
from llm_in_practise_tpu.obs.meter import DispatchMeter, GoodputMeter

# BENCH_r04.json, extra.qlora — the 14B rung this repo's MFU story is
# anchored on (measured on the real chip, "TPU v5 lite"):
R04_QLORA = {
    "flops_per_token": 57218170880.0,
    "tokens_per_sec_per_chip": 1260.6,
    "mfu": 0.3661,
    "peak_bf16_flops": 197e12,
}
# BENCH_r04.json, extra.gptlike_pretrain (same chip):
R04_GPTLIKE = {
    "flops_per_token": 218628096.0,
    "tokens_per_sec": 357800.3,
    "mfu": 0.3971,
}


# --- the one cost model vs the committed artifacts ---------------------------


def test_gptlike_flop_model_matches_bench_r04():
    """eval-shape path (exactly what bench.bench_gptlike computes):
    same inputs → same flops_per_token → same mfu to 4 decimals."""
    from llm_in_practise_tpu.models.gpt import GPT, gptlike_config

    cfg = gptlike_config(32768, seq_len=256, dropout=0.0,
                         compute_dtype="bfloat16")
    model = GPT(cfg)
    abstract = jax.eval_shape(
        lambda r: model.init(r, jnp.ones((2, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    m = cost.matmul_param_count(abstract, tied_head=True)
    f_tok = cost.flops_per_token(m, cfg.n_layer, 256, cfg.embed_dim,
                                 train_full=True)
    assert f_tok == R04_GPTLIKE["flops_per_token"]
    mfu = (f_tok * R04_GPTLIKE["tokens_per_sec"]
           / R04_QLORA["peak_bf16_flops"])
    assert round(mfu, 4) == pytest.approx(R04_GPTLIKE["mfu"], abs=1e-4)


def test_14b_analytic_geometry_matches_bench_r04():
    """The serving-side analytic geometry reproduces the 14B training
    rung's matmul-param count and flops_per_token WITHOUT building the
    tree — the two derivations (eval-shape in bench, closed-form in
    CostModel) must agree or the live gauges and artifact MFU fork."""
    from llm_in_practise_tpu.models.qwen3 import Qwen3Config

    from bench import G14B, SEQ

    cfg = Qwen3Config(vocab_size=151936, max_seq_len=SEQ,
                      tie_word_embeddings=True, n_layer=40, **G14B)
    geom = cost.geometry_from_config(cfg)
    f_tok = cost.flops_per_token(geom.matmul_params, cfg.n_layer, SEQ,
                                 cfg.n_head * cfg.head_dim,
                                 train_full=False)
    assert f_tok == R04_QLORA["flops_per_token"]
    mfu = (f_tok * R04_QLORA["tokens_per_sec_per_chip"]
           / R04_QLORA["peak_bf16_flops"])
    assert round(mfu, 4) == pytest.approx(R04_QLORA["mfu"], abs=1e-4)


def test_bench_reexports_are_the_cost_module():
    """The dedup satellite: bench.py and the tools must share obs/cost's
    objects, not carry copies that can drift again."""
    import bench

    assert bench.flops_per_token is cost.flops_per_token
    assert bench.matmul_param_count is cost.matmul_param_count
    assert bench.chip_peak is cost.chip_peak
    assert bench.PEAKS is cost.PEAKS


@pytest.mark.parametrize("kind,raises", [("cpu", False),
                                         ("weird-accelerator", True)])
def test_peaks_tables_and_fallbacks(kind, raises, monkeypatch):
    """Table rows resolve by substring; the CPU backend keeps a
    rendering; an accelerator kind in no row raises instead of
    borrowing a v5e's rates — and ``CostModel.from_model`` does not
    eat the raise."""
    import types

    assert cost._lookup("TPU v5 lite", cost.PEAKS, 0) == 197e12
    assert cost._lookup("TPU v6e", cost.HBM_BW, 0) == 1640e9
    model = types.SimpleNamespace(config=types.SimpleNamespace(
        n_layer=2, vocab_size=64, embed_dim=32, mlp_ratio=4))
    if raises:
        for fn in (cost.chip_hbm_bw, cost.chip_ici_bw):
            with pytest.raises(ValueError, match="matches no row"):
                fn(kind)
        monkeypatch.setattr(
            jax, "devices",
            lambda: [types.SimpleNamespace(device_kind=kind)])
        with pytest.raises(ValueError, match="matches no row"):
            cost.CostModel.from_model(model, {})
    else:
        got_kind, peak = cost.chip_peak()    # the test backend IS cpu
        assert got_kind == kind and peak > 0
        assert cost.chip_hbm_bw(kind) > 0 and cost.chip_ici_bw(kind) > 0
        cm = cost.CostModel.from_model(model, {})
        assert cm.device_kind == kind and cm.peak_flops == peak


def test_device_memory_stats_fail_open():
    # CPU backend reports no memory stats — must be {} not an exception
    assert cost.device_memory_stats() == {}
    assert cost.hbm_stats() == {}


def test_serving_cost_model_math():
    geom = cost.Geometry(matmul_params=1000, n_layer=2, attn_dim=8,
                         kv_dim=4)
    cm = cost.CostModel(geometry=geom, weight_bytes=2000,
                        kv_bytes_per_token=16, peak_flops=1e6,
                        peak_hbm_bw=1e6)
    # one token, one key: 2·m + 4·D·L·1
    assert cm.step_flops(1, 1) == 2 * 1000 + 4 * 8 * 2
    # chunk of 4 at offset 10 attends 4·10 + 1+2+3+4 keys
    assert cost.CostModel.chunk_keys(4, 10) == 50
    # 3-step block at context 7 attends (7+1)+(7+2)+(7+3)
    assert cost.CostModel.block_keys(3, 7) == 27
    # bytes: n weight passes + kv reads + writes
    assert cm.step_bytes(2, 10, 3) == 2 * 2000 + 16 * 13
    assert cm.mfu(5e5, 1.0) == 0.5
    assert cm.hbm_util(1e6, 2.0) == 0.5
    assert cm.mfu(1.0, 0.0) is None     # degenerate dt never divides


def test_cost_model_from_model_fail_open():
    class NoConfig:
        pass

    assert cost.CostModel.from_model(NoConfig(), {}) is None


# --- dispatch meter phases / goodput unit surface ----------------------------


def test_dispatch_meter_phase_rolling_accounting():
    dm = DispatchMeter(window=4)
    for i in range(6):
        dm.note_phase("decode", tokens=2, duration_s=0.1, mfu=0.5,
                      hbm_bw_util=0.25)
    snap = dm.phase_snapshot()["decode"]
    assert snap["dispatches"] == 6 and snap["tokens_total"] == 12
    assert snap["tokens_per_dispatch"] == 2.0
    assert snap["mfu"] == pytest.approx(0.5)
    assert snap["hbm_bw_util"] == pytest.approx(0.25)
    # a phase without utilization samples still reports tokens
    dm.note_phase("prefill", tokens=7, duration_s=0.2)
    assert "mfu" not in dm.phase_snapshot()["prefill"]


def test_goodput_meter_thresholds_and_deadline():
    gp = GoodputMeter()
    assert not gp.enabled
    assert gp.observe(tokens=5, ttft_s=100.0) is False  # disabled: no-op
    gp.configure(ttft_slo_s=1.0, tpot_slo_s=0.1)
    assert gp.observe(tokens=5, ttft_s=0.5, tpot_s=0.05) is False
    assert gp.observe(tokens=3, ttft_s=2.0, tpot_s=0.05) is True
    assert gp.observe(tokens=4, ttft_s=0.5, tpot_s=0.5) is True
    # total-latency (deadline) path: 1.0 + 9·0.1 = 1.9 s budget
    assert gp.observe(tokens=10, total_s=1.5) is False
    assert gp.observe(tokens=10, total_s=2.5) is True
    snap = gp.snapshot()
    assert snap["tokens_ok"] == 5 + 10 and snap["tokens_violated"] == 3 + 4 + 10
    assert snap["requests_ok"] == 2 and snap["requests_violated"] == 3
    assert sum(snap["blame"].values()) == 3   # no tracer → "unknown"
    assert set(snap["blame"]) == {"unknown"}


def test_goodput_blame_picks_longest_phase_span():
    from llm_in_practise_tpu.obs.trace import Tracer, new_context

    tracer = Tracer(enabled=True)
    ctx = new_context()
    tracer.record("engine.queue_wait", ctx, duration_s=0.01)
    tracer.record("engine.decode", ctx, duration_s=5.0)
    tracer.record("api.stream_flush", ctx, duration_s=0.02)
    gp = GoodputMeter(ttft_slo_s=0.001, tracer=tracer)
    gp.observe(tokens=1, ttft_s=1.0, trace_id=ctx.trace_id)
    assert gp.snapshot()["blame"] == {"engine.decode": 1}


# --- profile_trace: reentrancy + exception safety ----------------------------


def test_profile_trace_reentrant_and_stops_on_exception(tmp_path):
    from llm_in_practise_tpu.obs.meter import profile_trace

    f = jax.jit(lambda x: x + 1)
    with profile_trace(str(tmp_path / "outer")):
        # nested entry must be a no-op, not a jax "already active" raise
        with profile_trace(str(tmp_path / "inner")):
            f(jnp.ones(2)).block_until_ready()
    with pytest.raises(ValueError):
        with profile_trace(str(tmp_path / "exc")):
            raise ValueError("boom")
    # the exception exit stopped the trace: a fresh capture must start
    with profile_trace(str(tmp_path / "after")):
        f(jnp.ones(3)).block_until_ready()
    assert any((tmp_path / "after").rglob("*"))


# --- the live server: device-plane families + /debug/profile -----------------


class _ByteTok:
    def encode(self, text):
        return list(text.encode("utf-8", errors="replace")[:200])

    def decode(self, ids):
        return bytes(int(i) % 256 for i in ids).decode("utf-8",
                                                       errors="replace")


@pytest.fixture(scope="module")
def device_server():
    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.serve.api import OpenAIServer
    from llm_in_practise_tpu.serve.engine import InferenceEngine

    cfg = GPTConfig(vocab_size=256, seq_len=256, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(model, params, max_slots=2, cache_len=256,
                             cache_dtype=jnp.float32,
                             chunked_prefill=64,
                             ttft_slo_s=120.0, tpot_slo_s=60.0)
    srv = OpenAIServer(engine, _ByteTok(), model_name="device-plane")
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    yield f"http://127.0.0.1:{port}", engine
    srv.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=60) as r:
        return r.read().decode()


def _post(url, payload, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _chat(base, content):
    return _post(base + "/v1/chat/completions", {
        "model": "device-plane", "max_tokens": 4, "temperature": 0.0,
        "messages": [{"role": "user", "content": content}]})


def test_device_plane_families_render_strict(device_server):
    base, engine = device_server
    assert engine.cost_model is not None     # GPT geometry is covered
    _chat(base, "short prompt")
    _chat(base, "x" * 150)                   # chunked prefill path too
    fams = parse_exposition(_get(base + "/metrics"))
    mfu = fams["llm_dispatch_mfu"]
    assert mfu.kind == "gauge"
    phases = {dict(k[1])["phase"] for k in mfu.samples}
    assert {"prefill", "decode"} <= phases
    for (_, labels), value in mfu.samples.items():
        assert 0.0 <= value <= 2.0, (labels, value)
    assert fams["llm_dispatch_hbm_bw_util"].kind == "gauge"
    tok = fams["llm_dispatch_tokens_per_dispatch"]
    assert all(v > 0 for v in tok.samples.values())
    # compile telemetry: the engine's first-use programs compiled on
    # this thread's requests
    key = ("llm_compile_events_total", frozenset())
    assert fams["llm_compile_events_total"].samples[key] >= 1
    skey = ("llm_compile_seconds_total", frozenset())
    assert fams["llm_compile_seconds_total"].samples[skey] > 0
    # device memory: CPU reports none — family present, zero samples,
    # still a strict-parse pass (the fail-open contract)
    assert fams["llm_device_hbm_bytes"].kind == "gauge"
    assert fams["llm_device_hbm_bytes"].samples == {}
    # goodput: generous SLOs → everything ok, nothing violated
    ok = ("llm_goodput_tokens_total", frozenset({("slo", "ok")}))
    bad = ("llm_goodput_tokens_total", frozenset({("slo", "violated")}))
    assert fams["llm_goodput_tokens_total"].samples[ok] >= 8
    assert fams["llm_goodput_tokens_total"].samples[bad] == 0


def test_bench_artifact_embeds_device_plane(device_server):
    _, engine = device_server
    import bench

    snap = bench.obs_snapshot(engine=engine)
    plane = snap["device_plane"]
    assert "decode" in plane["dispatch_phases"]
    assert plane["compile"]["events"] >= 1
    assert plane["cost_model"]["weight_bytes"] > 0
    assert plane["goodput"]["tokens_ok"] >= 8


def test_post_debug_profile_end_to_end(device_server):
    """Acceptance: POST /debug/profile on the CPU backend returns a
    capture directory containing a Perfetto-loadable trace."""
    base, _ = device_server
    status, payload = _post(base + "/debug/profile", {"duration_s": 0.2})
    assert status == 200
    import pathlib

    trace_dir = pathlib.Path(payload["trace_dir"])
    assert trace_dir.is_dir()
    files = [pathlib.Path(f) for f in payload["files"]]
    assert files and all(f.exists() for f in files)
    # the Chrome-trace gz Perfetto opens directly
    assert payload["perfetto"], payload
    assert all(f.endswith(".trace.json.gz") for f in payload["perfetto"])


def test_post_debug_profile_one_at_a_time(device_server):
    base, _ = device_server
    results = {}

    def long_capture():
        results["long"] = _post(base + "/debug/profile",
                                {"duration_s": 1.5})[0]

    t = threading.Thread(target=long_capture)
    t.start()
    # wait until the long capture holds the lock, then collide with it
    import time

    from llm_in_practise_tpu.obs.prof import get_profiler

    prof = get_profiler()
    deadline = time.monotonic() + 10
    while (not prof._lock.locked()
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert prof._lock.locked(), "long capture never started"
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(base + "/debug/profile", {"duration_s": 0.1})
    assert exc.value.code == 409
    t.join(timeout=30)
    assert results["long"] == 200


def test_post_debug_profile_409_when_external_trace_active(
        device_server, tmp_path):
    """A bench running profile_trace around its hot loop must make
    /debug/profile answer 409 — never a 200 with an empty capture."""
    base, _ = device_server
    from llm_in_practise_tpu.obs.meter import profile_trace

    with profile_trace(str(tmp_path / "hot-loop")):
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(base + "/debug/profile", {"duration_s": 0.1})
        assert exc.value.code == 409
    # trace released: a capture works again
    status, payload = _post(base + "/debug/profile", {"duration_s": 0.1})
    assert status == 200 and payload["files"]


def test_post_debug_profile_bad_duration(device_server):
    base, _ = device_server
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(base + "/debug/profile", {"duration_s": "soon"})
    assert exc.value.code == 422


# --- gateway goodput ---------------------------------------------------------


def test_gateway_goodput_and_blame(device_server):
    base, _ = device_server
    from llm_in_practise_tpu.serve.gateway import (
        Gateway, RetryPolicy, Router, Upstream,
    )

    # impossible SLOs: every routed token is a violation, with blame
    gw = Gateway(Router([Upstream(base, "device-plane", group="chat")]),
                 retry_policy=RetryPolicy(backoff_s=0.01),
                 health_check_interval_s=0,
                 ttft_slo_s=1e-9, tpot_slo_s=1e-9)
    status, resp = gw.handle_completion({
        "model": "chat", "max_tokens": 4, "temperature": 0.0,
        "messages": [{"role": "user", "content": "goodput probe"}]})
    assert status == 200
    snap = gw.goodput.snapshot()
    assert snap["tokens_violated"] == resp["usage"]["completion_tokens"]
    assert snap["requests_violated"] == 1 and snap["requests_ok"] == 0
    # single-process stack: the engine's phase spans are in the shared
    # ring, so blame names a real phase, not "unknown"
    assert set(snap["blame"]) <= set(GoodputMeter.BLAME_SPANS)
    fams = parse_exposition(gw.metrics_text())
    bad = ("llm_goodput_tokens_total", frozenset({("slo", "violated")}))
    assert fams["llm_goodput_tokens_total"].samples[bad] >= 1
    assert fams["llm_slo_blame_total"].kind == "counter"

    # achievable SLOs: tokens land in slo=ok
    gw2 = Gateway(Router([Upstream(base, "device-plane", group="chat")]),
                  retry_policy=RetryPolicy(backoff_s=0.01),
                  health_check_interval_s=0,
                  ttft_slo_s=300.0, tpot_slo_s=300.0)
    status, resp = gw2.handle_completion({
        "model": "chat", "max_tokens": 4, "temperature": 0.0,
        "messages": [{"role": "user", "content": "ok probe"}]})
    assert status == 200
    snap = gw2.goodput.snapshot()
    assert snap["tokens_ok"] == resp["usage"]["completion_tokens"]
    assert snap["requests_violated"] == 0


def test_gateway_goodput_disabled_by_default(device_server):
    base, _ = device_server
    from llm_in_practise_tpu.serve.gateway import (
        Gateway, RetryPolicy, Router, Upstream,
    )

    gw = Gateway(Router([Upstream(base, "device-plane", group="chat")]),
                 retry_policy=RetryPolicy(backoff_s=0.01),
                 health_check_interval_s=0)
    status, _ = gw.handle_completion({
        "model": "chat", "max_tokens": 2, "temperature": 0.0,
        "messages": [{"role": "user", "content": "no slo"}]})
    assert status == 200
    snap = gw.goodput.snapshot()
    assert snap["tokens_ok"] == 0 and snap["tokens_violated"] == 0
    # the families still render (all-zero) and parse strictly
    fams = parse_exposition(gw.metrics_text())
    assert fams["llm_goodput_tokens_total"].kind == "counter"


# --- engine goodput over real requests ---------------------------------------


def test_engine_goodput_counts_finished_requests(device_server):
    _, engine = device_server
    from llm_in_practise_tpu.serve.engine import SamplingParams

    before = engine.stats.goodput.snapshot()
    req = engine.submit(list(range(16)),
                        SamplingParams(greedy=True, max_tokens=4))
    out = req.result()
    assert len(out) >= 1
    after = engine.stats.goodput.snapshot()
    assert (after["requests_ok"] + after["requests_violated"]
            == before["requests_ok"] + before["requests_violated"] + 1)


def test_mixed_step_records_both_phases():
    """The fused dispatch must keep feeding BOTH phase gauges (the
    dissection survives the fusion)."""
    from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
    from llm_in_practise_tpu.serve.engine import (
        InferenceEngine, SamplingParams,
    )

    cfg = GPTConfig(vocab_size=256, seq_len=512, n_layer=1, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(model, params, max_slots=2, cache_len=512,
                             cache_dtype=jnp.float32, chunked_prefill=32,
                             mixed_step=True)
    rng = np.random.default_rng(0)
    # one decoding slot + one long prompt mid-prefill → fused steps
    r1 = engine.submit(list(map(int, rng.integers(0, 256, 8))),
                       SamplingParams(greedy=True, max_tokens=48))
    r2 = engine.submit(list(map(int, rng.integers(0, 256, 300))),
                       SamplingParams(greedy=True, max_tokens=4))
    while engine.step():
        pass
    r1.result(), r2.result()
    assert engine.mixed_blocks > 0, "no fused step ran; test is vacuous"
    snap = engine.dispatch_meter.phase_snapshot()
    assert snap["prefill"]["dispatches"] > 0
    assert snap["decode"]["dispatches"] > 0
    assert "mfu" in snap["decode"] and "hbm_bw_util" in snap["decode"]
