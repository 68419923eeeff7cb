"""Serving-ladder harness correctness: the in-process (engine-attributable)
ladder loses no requests, and the HTTP client records WHY a request failed
instead of swallowing it into a bare success-rate dip (VERDICT r2 item 2)."""

import jax
import jax.numpy as jnp
import pytest

from deploy.benchmark.bench_serve import one_request, run_level_inprocess
from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.serve.engine import InferenceEngine


@pytest.fixture(scope="module")
def engine():
    cfg = GPTConfig(vocab_size=64, seq_len=128, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(model, params, max_slots=4, cache_len=128,
                          cache_dtype=jnp.float32)
    eng.start()
    yield eng
    eng.stop()


def test_inprocess_ladder_lossless(engine):
    prompts = [[1, 2, 3, 4, 5], [7, 3] * 6, list(range(1, 20))]
    row = run_level_inprocess(engine, prompts, concurrency=8,
                              n_requests=24, max_tokens=8)
    assert row["success_rate"] == 1.0
    assert row["failures"] == {}
    assert row["output_tps"] > 0
    assert row["ttft_p50_ms"] > 0 and row["ttft_p99_ms"] >= row["ttft_p50_ms"]


def test_http_failure_reason_recorded():
    # nothing listens on this port: the client must return the reason,
    # not just ok=False
    ok, ttft, tpot, n, reason = one_request(
        "http://127.0.0.1:9", "m", "hi", 4, timeout=2)
    assert not ok and n == 0
    assert reason and "Error" in reason
