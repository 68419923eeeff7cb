"""Multi-chip tensor-parallel decode replicas (ISSUE 10 / ROADMAP item 1).

``--tensor-parallel-size N`` is a production decode-replica path, not a
bare-engine demo: these tests pin the full serving composition sharded
over the device mesh —

- golden-token parity: tp ∈ {1, 2, 4} is byte-identical across
  {contiguous, paged} × {spec off, ngram}, with the params REALLY
  distributed over the mesh;
- draft-model speculation under TP (the small draft replicates across
  the mesh — the old CLI fail-fast is gone);
- packed int8 trees shard via quant/sharding.py component shardings
  joined to the serving rule table (`shard_params_for_serving`);
- disagg handoff BOTH directions: a single-chip prefill replica feeds
  a multi-chip decode replica (the documented fleet shape) and a
  sharded prefill replica feeds a single-chip consumer — entries
  reshard on hput/hclaim (device_get assembles, the consumer's jitted
  insert re-places);
- the 1-jitted-dispatch-per-step invariant still holds under TP
  (DispatchMeter, mixed prefill+decode load);
- the int8 quantized collective (parallel/collectives.py, ZeRO++
  idiom) matches psum within its error bound and the golden-token
  check gates the opt-in;
- serve_openai's validation: the quantized_dir/draft fail-fasts are
  deleted, and so is the ``--scan-layers`` flag (the server builds
  the unrolled layout only);
- `llm_collective_{bytes,seconds}_total` and `llm_tp_size` render at
  /metrics with live values;
- the XLA_FLAGS recipe works from a clean subprocess (no harness
  conftest), so the CPU-parity suite is reproducible outside pytest.

Skip-guarded via tests/envcaps.py: the suite needs >= 4 devices (the
conftest forces 8 virtual CPU devices; a bare 1-device env re-arms the
skips with the probe's reason).
"""

import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import envcaps
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.parallel import strategy as S
from llm_in_practise_tpu.serve.disagg import LocalHandoff, new_handoff_id
from llm_in_practise_tpu.serve.engine import (
    InferenceEngine,
    SamplingParams,
    shard_params_for_serving,
)

pytestmark = pytest.mark.skipif(
    envcaps.host_device_count() < 4, reason=envcaps.tp_devices_reason(4))

PROMPT = [1, 2, 3, 4, 5] * 6
LONG = [(i * 7 + 3) % 64 for i in range(64)]
SP = SamplingParams(greedy=True, max_tokens=24)


@pytest.fixture(scope="module")
def model_params():
    # 4 heads so the KV heads divide tp ∈ {2, 4}; embed 32 so every
    # row/column-parallel contraction divides too
    cfg = GPTConfig(vocab_size=64, seq_len=192, n_layer=2, n_head=4,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def _mesh(tp: int):
    strat = S.tensor_parallel(model=tp, data=1)
    return strat, strat.build_mesh(jax.devices()[:tp])


def _tp_engine(model, params, tp: int, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("cache_len", 192)
    kw.setdefault("cache_dtype", jnp.float32)
    if tp <= 1:
        return InferenceEngine(model, params, **kw)
    strat, mesh = _mesh(tp)
    sharded = shard_params_for_serving(params, strat, mesh)
    return InferenceEngine(model, sharded, mesh=mesh, **kw)


@pytest.fixture(scope="module")
def ref_tokens(model_params):
    model, params = model_params
    return _tp_engine(model, params, 1).generate(PROMPT, SP)


# --- golden parity matrix ----------------------------------------------------


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("spec", ["off", "ngram"])
def test_tp_golden_parity(model_params, ref_tokens, tp, layout, spec):
    """The acceptance bar: tp ∈ {2, 4} output byte-identical to tp=1
    across KV layouts and speculation, params really distributed."""
    model, params = model_params
    kw = dict(kv_layout=layout)
    if spec == "ngram":
        kw.update(speculative_k=3)
    eng = _tp_engine(model, params, tp, **kw)
    assert eng.tp == tp
    kernel = eng.params["block_0"]["attn"]["q_proj"]["kernel"]
    assert len(kernel.sharding.device_set) == tp
    assert eng.generate(PROMPT, SP) == ref_tokens
    if spec == "ngram":
        assert eng.spec_rounds > 0        # speculation really ran sharded
    # collective attribution booked per dispatch (analytic plane)
    assert eng.collective_bytes_total > 0
    assert eng.collective_seconds_total > 0


def test_tp_draft_model_speculation(model_params, ref_tokens):
    """Draft-model speculation under TP (the deleted CLI fail-fast):
    the draft replicates across the mesh, target-as-draft makes
    acceptance total, tokens stay byte-identical."""
    model, params = model_params
    eng = _tp_engine(model, params, 2, kv_layout="paged",
                     speculative_k=3, draft_model=model,
                     draft_params=params)
    # the draft tree is REPLICATED over the mesh, not committed to one
    # device next to the sharded target
    leaf = jax.tree_util.tree_leaves(eng.draft_params)[0]
    assert len(leaf.sharding.device_set) == 2
    assert eng.generate(PROMPT, SP) == ref_tokens
    assert eng.spec_accepted == eng.spec_proposed > 0


def test_tp_int8_packed_tree(model_params):
    """Packed quantized serving sharded (quant/sharding.py joined to
    the serving rule table through shard_params_for_serving): int8 TP
    output equals the single-chip int8 output exactly."""
    from llm_in_practise_tpu.quant.int8 import quantize_tree
    from llm_in_practise_tpu.serve.quantized import QuantizedModel

    model, params = model_params
    qtree = quantize_tree(
        params, predicate=lambda s, v: s.endswith("/kernel")
        and getattr(v, "ndim", 0) == 2)
    qref = InferenceEngine(
        QuantizedModel(model, use_kernels=False), qtree, max_slots=2,
        cache_len=192, cache_dtype=jnp.float32).generate(PROMPT, SP)
    strat, mesh = _mesh(2)
    sq = shard_params_for_serving(qtree, strat, mesh)
    leaf = sq["block_0"]["attn"]["q_proj"]["kernel"]
    # the packed component array itself is distributed
    assert len(leaf.q.sharding.device_set) == 2
    eng = InferenceEngine(QuantizedModel(model, mesh=mesh), sq,
                          max_slots=2, cache_len=192,
                          cache_dtype=jnp.float32, mesh=mesh,
                          kv_layout="paged")
    assert eng.generate(PROMPT, SP) == qref


# --- disaggregation across mesh shapes ---------------------------------------


def _drain_prefill(pre, handle):
    while pre.step():
        pass
    for _ in range(200):
        if handle.finish_reason is not None:
            return
        time.sleep(0.02)
    raise AssertionError("handoff publish never finished")


@pytest.mark.parametrize("direction", ["one_to_many", "many_to_one"])
def test_tp_disagg_handoff(model_params, ref_tokens, direction):
    """Cross-TP handoff, both directions. one_to_many is the documented
    fleet shape: single-chip prefill replicas feed a multi-chip paged
    decode replica; the claimed entry's head-sharded rows reshard at
    admission (page scatter / insert under the consumer's mesh).
    many_to_one pins the reverse (a sharded prefill's device_get
    assembles full rows on the wire)."""
    model, params = model_params
    store = LocalHandoff()
    if direction == "one_to_many":
        pre = _tp_engine(model, params, 1, role="prefill", handoff=store)
        dec = _tp_engine(model, params, 2, kv_layout="paged",
                         speculative_k=3, role="decode")
    else:
        pre = _tp_engine(model, params, 2, role="prefill", handoff=store)
        dec = _tp_engine(model, params, 1, role="decode")
    hid = new_handoff_id()
    h = pre.submit(PROMPT, SP, handoff_id=hid)
    _drain_prefill(pre, h)
    assert h.finish_reason == "handoff"
    entry = store.claim(hid)
    assert entry is not None
    r = dec.submit(PROMPT, SP, kv_entry=entry)
    while dec.step():
        pass
    assert list(r) == ref_tokens
    # the decode replica stayed interference-free: the claim admitted
    # as a direct insert, zero local prefill work
    assert dec.kv_admitted == 1
    assert dec.local_prefills == 0


# --- dispatch accounting under TP --------------------------------------------


def test_tp_one_dispatch_per_step_under_mixed_load(model_params):
    """The fused mixed step's 1-dispatch-per-step invariant survives
    sharding: long prompt mid-chunked-prefill + an active decoder on a
    tp=2 paged engine still costs exactly ONE device dispatch per
    step."""
    model, params = model_params
    eng = _tp_engine(model, params, 2, kv_layout="paged",
                     chunked_prefill=16)
    # decoder prompt < chunk so it one-shot admits and is DECODING
    # while the long prompt chunks (the test_mixed_step idiom — a
    # prompt finishing its own prefill then decoding is legitimately
    # a 2-dispatch step and not what this invariant is about)
    h = eng.submit([3, 1, 4, 1, 5, 9],
                   SamplingParams(greedy=True, max_tokens=64))
    eng.step()                                # admit + first token
    hl = eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
    steps_mixed = 0
    while hl.first_token_time is None:
        eng.step()
        steps_mixed += 1
        assert steps_mixed < 16, "long prompt never activated"
        if eng.slot_prefill:
            assert eng.dispatch_meter.last_step == 1
    assert steps_mixed >= 2
    assert h.n_generated > 1


# --- quantized collectives ---------------------------------------------------


def test_quantized_psum_matches_psum(model_params):
    """Unit bar for the ZeRO++ two-hop: the int8 all-reduce equals the
    exact psum within its per-chunk quantization bound."""
    from llm_in_practise_tpu.parallel.collectives import (
        row_parallel_matmul,
    )

    _, mesh = _mesh(4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    ref = x @ k
    exact = row_parallel_matmul(x, k, mesh, quantized=False)
    np.testing.assert_allclose(np.asarray(exact), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    quant = row_parallel_matmul(x, k, mesh, quantized=True)
    err = float(jnp.max(jnp.abs(quant - ref)) / jnp.max(jnp.abs(ref)))
    assert err < 0.05, f"int8 collective error {err} out of bound"
    # jit-compatible (it runs inside every engine program)
    jitted = jax.jit(
        lambda a, b: row_parallel_matmul(a, b, mesh, quantized=True)
    )(x, k)
    np.testing.assert_allclose(np.asarray(jitted), np.asarray(quant))
    # non-divisible contraction falls back to the implicit-SPMD matmul
    x3 = jax.random.normal(jax.random.PRNGKey(2), (2, 30))
    k3 = jax.random.normal(jax.random.PRNGKey(3), (30, 8))
    np.testing.assert_allclose(
        np.asarray(row_parallel_matmul(x3, k3, mesh, quantized=True)),
        np.asarray(x3 @ k3), rtol=1e-6)


def test_quantized_collectives_golden_gate(model_params, ref_tokens):
    """The opt-in's gate end-to-end: golden_token_check compares the
    wrapped forward against the plain one; when it passes, a full
    engine run under the int8 collective reproduces the plain greedy
    stream (this tiny model passes on the CPU backend — a flipping env
    exercises the CLI's fallback instead)."""
    from llm_in_practise_tpu.parallel.collectives import (
        TPQuantizedCollectives,
        golden_token_check,
    )

    model, params = model_params
    strat, mesh = _mesh(2)
    sharded = shard_params_for_serving(params, strat, mesh)
    wrapped = TPQuantizedCollectives(model, mesh)
    ok = golden_token_check(model, wrapped, sharded, vocab_size=64)
    assert isinstance(ok, bool)
    if not ok:
        pytest.skip("int8 collective flips greedy tokens on this "
                    "backend — the CLI falls back to plain collectives")
    eng = InferenceEngine(wrapped, sharded, max_slots=2, cache_len=192,
                          cache_dtype=jnp.float32, mesh=mesh,
                          kv_layout="paged")
    assert eng.tp_quantized_collectives     # wire-byte attribution halves
    assert eng.generate(PROMPT, SP) == ref_tokens


# --- CLI validation ----------------------------------------------------------


class _CliError(Exception):
    pass


def _validate(**kw):
    sys.path.insert(0, "examples")
    from examples.serve_openai import validate_args

    defaults = dict(quantized_dir=None, lora_modules=[], tp=1,
                    tp_quantized_collectives=False, role="both",
                    kv_remote=None, draft_model_path=None,
                    speculative=None)
    defaults.update(kw)
    args = types.SimpleNamespace(**defaults)

    def error(msg):
        raise _CliError(msg)

    validate_args(args, error)
    return args


def test_cli_tp_fail_fasts_deleted():
    """The ISSUE 10 satellite: TP × quantized_dir and TP × draft model
    are ACCEPTED combinations now."""
    _validate(tp=8, quantized_dir="/tmp/q")
    _validate(tp=8, draft_model_path="/tmp/d", speculative=4)
    # decode replicas still resolve the speculation default under TP
    args = _validate(tp=8, role="decode", kv_remote="h:1")
    assert args.speculative == 4


def test_cli_scan_layers_flag_is_gone(capsys):
    """The server builds one kind of engine: ``--scan-layers`` is an
    unknown argument, and the parsed namespace carries no such key."""
    sys.path.insert(0, "examples")
    from examples.serve_openai import build_parser

    with pytest.raises(SystemExit) as exc:
        build_parser().parse_args(["--scan-layers"])
    assert exc.value.code == 2
    assert "unrecognized arguments: --scan-layers" in capsys.readouterr().err
    assert not hasattr(build_parser().parse_args([]), "scan_layers")


def test_cli_quantized_collectives_combos():
    with pytest.raises(_CliError, match="tensor-parallel-size > 1"):
        _validate(tp_quantized_collectives=True)
    with pytest.raises(_CliError, match="quantized_dir"):
        _validate(tp=2, tp_quantized_collectives=True,
                  quantized_dir="/tmp/q")
    _validate(tp=2, tp_quantized_collectives=True)     # the happy path


# --- metrics -----------------------------------------------------------------


def test_tp_collective_metrics_render(model_params):
    """llm_tp_size / llm_collective_{bytes,seconds}_total render at
    /metrics with live values on a sharded engine (and zeros at tp=1 —
    one stable family set for the docs census)."""
    from llm_in_practise_tpu.serve.api import OpenAIServer

    class _Tok:
        def encode(self, t):
            return list(t.encode()[:16])

        def decode(self, ids):
            return bytes(int(i) % 256 for i in ids).decode(
                "utf-8", "replace")

    model, params = model_params
    eng = _tp_engine(model, params, 2, kv_layout="paged")
    eng.generate(PROMPT, SP)
    srv = OpenAIServer(eng, _Tok(), model_name="tp-test")
    text = srv.metrics_text()
    assert "llm_tp_size 2" in text
    byte_line = [ln for ln in text.splitlines()
                 if ln.startswith("llm_collective_bytes_total")][0]
    assert float(byte_line.split()[-1]) > 0
    sec_line = [ln for ln in text.splitlines()
                if ln.startswith("llm_collective_seconds_total")][0]
    assert float(sec_line.split()[-1]) > 0
    # tp=1: families render, values zero (no conditional census gap)
    eng1 = _tp_engine(model, params, 1)
    text1 = OpenAIServer(eng1, _Tok(), model_name="tp1").metrics_text()
    assert "llm_tp_size 1" in text1
    assert "llm_collective_bytes_total 0" in text1


# --- bench smoke -------------------------------------------------------------


def test_tp_ladder_smoke(tmp_path):
    """The BENCH_TP_LADDER artifact's CPU smoke: reduced training and
    request counts, structure + the golden-parity gate + live
    collective counters on the sharded leg."""
    from tools.tp_ladder_bench import run_ladder

    artifact = run_ladder(train_steps=40, n_requests=6, max_tokens=24,
                          legs=(1, 2),
                          concurrencies=(1,), quantized_leg=False,
                          out_path=str(tmp_path / "ladder.json"))
    assert set(artifact["legs"]) == {"tp1", "tp2"}
    assert artifact["golden_parity_across_legs"]
    assert artifact["legs"]["tp1"]["collective_bytes_timed"] == 0
    assert artifact["legs"]["tp2"]["collective_bytes_timed"] > 0
    assert "llm_tp_size 2" in artifact["legs"]["tp2"]["metrics_snapshot"]


# --- the env recipe, from a clean subprocess ---------------------------------


_SUBPROCESS_PARITY = r"""
import jax, jax.numpy as jnp
assert len(jax.devices()) == 8, jax.devices()
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.parallel import strategy as S
from llm_in_practise_tpu.serve.engine import (
    InferenceEngine, SamplingParams, shard_params_for_serving)
cfg = GPTConfig(vocab_size=64, seq_len=96, n_layer=1, n_head=2,
                embed_dim=16, dropout=0.0, pos_embedding="rope")
model = GPT(cfg)
params = model.init(jax.random.PRNGKey(0),
                    jnp.ones((1, 4), jnp.int32))["params"]
sp = SamplingParams(greedy=True, max_tokens=8)
ref = InferenceEngine(model, params, max_slots=1, cache_len=96,
                      cache_dtype=jnp.float32).generate([1, 2, 3, 4], sp)
strat = S.tensor_parallel(model=2, data=1)
mesh = strat.build_mesh(jax.devices()[:2])
eng = InferenceEngine(model, shard_params_for_serving(params, strat, mesh),
                      max_slots=1, cache_len=96, cache_dtype=jnp.float32,
                      mesh=mesh, kv_layout="paged")
assert eng.generate([1, 2, 3, 4], sp) == ref
print("TP_PARITY_OK")
"""


def test_tp_env_recipe_subprocess(tmp_path):
    """The documented XLA_FLAGS recipe stands on its own: a clean
    subprocess (no pytest conftest) gets 8 virtual devices and
    reproduces tp=2 parity — what docs/serving-tp.md tells operators
    to run on a CPU dev box."""
    import os

    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.path.dirname(os.path.dirname(
               os.path.abspath(__file__)))}
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_PARITY],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "TP_PARITY_OK" in proc.stdout
