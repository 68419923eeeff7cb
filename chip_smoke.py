#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` needs one attached TPU and drives the two main
paths once, in ONE process, through the entry points a user runs, at
the published widths of Qwen3-8B (hidden 4096, intermediate 12288, 32
query / 8 KV heads of 128, vocab 151,936, 36 layers, RoPE theta 1e6,
QK-norm) with weights made from ``--seed``:

- ``serve``: a W8A16 tree behind the engine and HTTP server that
  ``examples/serve_openai.py`` builds (the same ``build_server``), a few
  ``/v1/chat/completions`` requests over a loopback port, and the
  engine's prefill logits against a plain float32 forward of the same
  weights.
- ``train``: a few QLoRA steps of ``bench.build_qlora_scan_step`` —
  the measured path — whose compiled step must hold flash attention's
  forward and backward kernels.
- ``kernels``: the NF4 / Int4 / AWQ production dispatch, ``nf4_matmul``'s
  gradient and flash attention forward+backward at transformer shapes,
  each compiled for the chip (never interpreted) and compared with a
  plain reference.

``python chip_smoke.py --chips 4`` runs only the tensor-parallel serving
path on four chips and the one-device engine it is compared with.

Every phase prints one JSON line as it ends; any failure raises at once.
The LAST stdout line is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it. Without a TPU the script exits non-zero before
any phase and prints no result. It starts no child process: one process
holds the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import http.client
import importlib.metadata
import json
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

import bench
from examples import qwen3_lora_sft, serve_openai
from llm_in_practise_tpu.core.compile_cache import enable_compilation_cache
from llm_in_practise_tpu.core.mesh import require_tpu
from llm_in_practise_tpu.data.sft import render_chatml, self_cognition_records
from llm_in_practise_tpu.models.qwen3 import Qwen3, Qwen3Config
from llm_in_practise_tpu.obs import cost
from llm_in_practise_tpu.obs.cost import device_memory_stats as memory_stats
from llm_in_practise_tpu.obs.hbm import get_ledger
from llm_in_practise_tpu.ops import nf4_matmul as nf4_kernel
from llm_in_practise_tpu.ops.attention import dense_attention
from llm_in_practise_tpu.ops.flash_attention import flash_attention
from llm_in_practise_tpu.ops.int8_matmul import int8_matmul
from llm_in_practise_tpu.peft.fused import fused_kernel_matmul
from llm_in_practise_tpu.quant import awq, int4, int8, nf4
from llm_in_practise_tpu.serve.quantized import QuantizedModel


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one smoke run. :data:`QWEN3_8B` is the only plan the
    command line runs; the CPU rehearsal in the tests passes a tiny one
    with ``on_chip=False``."""

    geom: dict              # Qwen3Config widths (bench.G8B's keys)
    vocab: int
    serve_layers: int
    train_layers: int
    tp_layers: int
    slots: int
    cache_len: int
    chunk: int              # --enable-chunked-prefill CHUNK
    long_prompt: int        # tokens of the one chunked prompt (> chunk)
    train_batch: int
    train_seq: int
    train_steps: int
    matmul_shapes: tuple    # ((K, N), ...)
    matmul_ms: tuple        # activation rows: decode shape, prefill shape
    flash_shape: tuple      # (B, L, H, D)
    # True: every kernel's compiled HLO must hold a tpu_custom_call and
    # the runtime must report memory stats. False only in the rehearsal.
    on_chip: bool = True


# Published config: huggingface.co/Qwen/Qwen3-8B config.json. Departures
# the smoke makes are printed on its first line (`departures`).
QWEN3_8B = Plan(
    geom=bench.G8B, vocab=151936,
    serve_layers=36, train_layers=36, tp_layers=36,
    slots=16, cache_len=1024, chunk=256, long_prompt=300,
    train_batch=2, train_seq=bench.SEQ, train_steps=12,
    matmul_shapes=((4096, 12288), (12288, 4096)), matmul_ms=(16, 1024),
    flash_shape=(1, 2048, 32, 128),
)
DEPARTURES = (
    "tie_word_embeddings=True (published: false) — serving keeps the "
    "bf16 head out of a 16 GB chip's budget and the measured QLoRA path "
    "feeds the embedding to its fused cross-entropy",
    "max_position_embeddings cut to the cache/sequence length in use "
    "(RoPE tables only)",
    "weights are seeded N(0, 0.02) draws, W8A16 (serve) / NF4 (train)",
)

# Engine (bf16 activations, 2^-9 relative rounding at every one of ~10
# tensors per layer, int8 weights dequantised in bf16) against a float32
# forward at `highest` matmul precision of the SAME int8 weights: the
# roundings add like a random walk over depth, ~1% of the logits' spread
# at 36 layers, and the maximum over 151,936 logits sits ~4.5 sigma out.
# Bounds are in units of the reference logits' standard deviation.
LOGIT_RMS_TOL = 0.05
LOGIT_MAX_TOL = 0.25
# One matmul / attention kernel (bf16 operands, f32 accumulation, bf16
# result) against float32 `highest` on the same operands: operand and
# result rounding are each 2^-9 relative, so errors stay under 2% of the
# result's largest magnitude.
KERNEL_TOL = 2e-2


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def baseline(plan: Plan) -> dict:
    """What the device and the HBM ledger hold before any phase put
    anything there (on the chip: the runtime's floor and empty books)."""
    stats = memory_stats()
    if plan.on_chip and "bytes_in_use" not in stats:
        raise RuntimeError("device.memory_stats() reports no bytes_in_use")
    return {"bytes_in_use": stats.get("bytes_in_use", 0),
            "ledger": get_ledger().baseline()}


def release(plan: Plan, phase: str, base: dict) -> dict:
    """After a phase dropped its trees: the ledger's accounts must be
    back where ``base`` found them and the runtime's bytes_in_use back
    near its floor."""
    gc.collect()
    if plan.on_chip:    # compiled programs hold device memory too
        jax.clear_caches()
        gc.collect()
    held = get_ledger().leaked_since(base["ledger"])
    if held:
        raise RuntimeError(f"{phase}: ledger accounts not released: {held}")
    stats = memory_stats()
    above = stats.get("bytes_in_use", 0) - base["bytes_in_use"]
    # compiled programs and the allocator's own bookkeeping stay; a
    # leaked weight tree or KV pool is gigabytes
    if plan.on_chip and above > 256 * 2**20:
        raise RuntimeError(
            f"{phase}: {above} bytes still in use above the floor")
    return {"ledger_bytes_held": sum(held.values()),
            "bytes_in_use_above_floor": above,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def compiled_hlo(fn, *args, on_chip: bool):
    """Compile ``fn`` for its arguments; on the chip its HLO must hold a
    Pallas kernel (``tpu_custom_call``) — not a reference, not the
    interpreter. Returns (compiled, number of custom calls)."""
    compiled = jax.jit(fn).lower(*args).compile()
    n = compiled.as_text().count("tpu_custom_call")
    if on_chip and n == 0:
        raise RuntimeError(f"{fn.__name__}: no tpu_custom_call in the HLO")
    return compiled, n


def close_enough(got, want, tol: float, what: str) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise RuntimeError(f"{what}: shape {got.shape} vs {want.shape} "
                           "or non-finite values")
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    if err > tol:
        raise RuntimeError(f"{what}: error {err:.4g} of the reference's "
                           f"largest magnitude exceeds {tol}")
    return err


# ---------------------------------------------------------------- serve


def build_tokenizer():
    """The ChatML-aware BPE ``examples/qwen3_lora_sft.py`` trains, made in
    memory from the in-repo self-cognition records; its ids are a subset
    of the model's published vocabulary."""
    return qwen3_lora_sft.train_tokenizer(
        self_cognition_records(n=64), "Smoke", "Repo")


def serve_config(plan: Plan, n_layer: int) -> Qwen3Config:
    return Qwen3Config(
        vocab_size=plan.vocab, n_layer=n_layer, max_seq_len=plan.cache_len,
        rope_theta=1e6, tie_word_embeddings=True, compute_dtype="bfloat16",
        **plan.geom)


def serve_args(plan: Plan, *extra: str):
    """The command line the smoke serves under: the CLI's defaults
    (paged KV, unrolled layers, fused mixed step) plus what a 16 GB chip
    needs at 8B (bf16 KV, 16 slots x 1K) and chunked prefill, so the
    fused mixed step has a chunk to fuse."""
    parser = serve_openai.build_parser()
    args = parser.parse_args([
        "--model_name", "qwen3-8b-smoke", "--host", "127.0.0.1",
        "--port", "0", "--max_slots", str(plan.slots),
        "--cache_len", str(plan.cache_len),
        "--kv-cache-dtype", "bfloat16",
        "--enable-chunked-prefill", str(plan.chunk), *extra])
    serve_openai.validate_args(args, parser.error)
    return args, parser.error


@contextlib.contextmanager
def serving(plan: Plan, cfg: Qwen3Config, tok, take_params, *extra: str):
    """The server ``examples/serve_openai.py`` would start for this
    command line — the same ``build_server`` — over a seeded tree
    (``take_params()``), listening on a loopback port. Yields
    ``(engine, port, args)``; shuts the server down on exit."""
    args, error = serve_args(plan, *extra)
    server = serve_openai.build_server(
        args, tok, lambda mesh: (QuantizedModel(Qwen3(cfg), mesh=mesh),
                                 take_params()), error)
    port = server.serve(host=args.host, port=args.port, background=True)
    try:
        yield server.engine, port, args
    finally:
        server.shutdown()


def http_json(port: int, method: str, path: str, payload=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        body = None if payload is None else json.dumps(payload)
        conn.request(method, path, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"{method} {path}: HTTP {resp.status} "
                           f"{data[:300]!r}")
    return data


# With the smoke's tokenizer a short chat renders to 34-36 tokens and at
# most 24 are generated, so every short request prefills in the 64 bucket
# and decodes in the 64-wide paged view: one compiled program each at
# full depth, where shorter prompts would walk the 16/32/64 widths.
SYSTEM = ("You are a helpful assistant named Smoke, trained by Repo. "
          "I am an AI assistant.")


def chat(port: int, content: str, max_tokens: int, stream: bool) -> dict:
    data = http_json(port, "POST", "/v1/chat/completions", {
        "model": "qwen3-8b-smoke", "max_tokens": max_tokens,
        "temperature": 0.0, "stream": stream,
        "messages": [{"role": "system", "content": SYSTEM},
                     {"role": "user", "content": content}]})
    if not stream:
        out = json.loads(data)
        got = out["usage"]["completion_tokens"]
        if got != max_tokens:
            raise RuntimeError(
                f"asked for {max_tokens} tokens, usage says {got}")
        return out
    events = [line[5:].strip() for line in data.decode().splitlines()
              if line.startswith("data:")]
    if events[-1] != "[DONE]":
        raise RuntimeError(f"stream did not end with [DONE]: {events[-1]!r}")
    last = json.loads(events[-2])
    if last["choices"][0]["finish_reason"] != "length":
        raise RuntimeError(f"stream finished with {last['choices'][0]}")
    return last


def drive_requests(port: int, plan: Plan, tok, engine) -> list[int]:
    """One non-stream, one stream, then four concurrent requests of
    different lengths (one longer than the prefill chunk, so it is
    chunk-prefilled while the others decode). Returns the token counts
    asked for; ``/debug/requests`` must account for every one."""
    asked = [12, 9]
    chat(port, "Who are you?", asked[0], stream=False)
    chat(port, "Introduce yourself.", asked[1], stream=True)
    phrase = "Who are you? "
    long_text = phrase * (plan.long_prompt // len(tok.encode(phrase)) + 1)
    group = [("What can you do?", 16), ("Who trained you?", 24),
             ("Tell me about yourself.", 20), (long_text, 8)]
    errors: list[BaseException] = []

    def one(content, n, stream):
        try:
            chat(port, content, n, stream)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(c, n, i % 2 == 1))
               for i, (c, n) in enumerate(group)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    if errors or any(t.is_alive() for t in threads):
        raise RuntimeError(f"concurrent requests failed: {errors}")
    asked += [n for _, n in group]
    done = json.loads(http_json(port, "GET", "/debug/requests"))["finished"]
    got = sorted(r["completion_tokens"] for r in done)
    reasons = {r["finish_reason"] for r in done}
    if got != sorted(asked) or reasons != {"length"}:
        raise RuntimeError(f"asked for {sorted(asked)} tokens, the engine "
                           f"finished {got} with {reasons}")
    if max(r["prompt_tokens"] for r in done) <= plan.chunk:
        raise RuntimeError("no prompt was longer than the prefill chunk")
    if engine.mixed_blocks == 0:
        raise RuntimeError("the fused mixed step never ran")
    return asked


def metric(text: str, name: str) -> float:
    m = re.search(rf"^{re.escape(name)}(?:{{[^}}]*}})? (\S+)$", text, re.M)
    if m is None:
        raise RuntimeError(f"/metrics has no {name}")
    return float(m.group(1))


def reference_logits(params, cfg: Qwen3Config, ids) -> np.ndarray:
    """Last-position logits of a plain forward of the SAME int8 tree:
    no cache, no paging, no batching, float32 at `highest` matmul
    precision, one layer dequantised at a time so it fits. Written from
    the Qwen3 equations in ``jax.numpy`` — it shares only the int8
    decode with the code under test."""
    half = cfg.head_dim // 2
    group = cfg.n_head // cfg.n_kv_head

    def rms(x, scale):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + cfg.rms_norm_eps) * scale

    def rope(x, cos, sin):      # HF rotate_half lanes: i pairs i + D/2
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def w(block, *path):
        leaf = functools.reduce(lambda d, k: d[k], path, block)["kernel"]
        return int8.decode(leaf, jnp.float32)

    @jax.jit
    def layer(x, block):
        n = x.shape[0]
        pos = jnp.arange(n, dtype=jnp.float32)[:, None]
        inv = 1.0 / cfg.rope_theta ** (
            jnp.arange(0, cfg.head_dim, 2, dtype=jnp.float32)
            / cfg.head_dim)
        cos, sin = jnp.cos(pos * inv)[:, None], jnp.sin(pos * inv)[:, None]
        h = rms(x, block["ln1"]["scale"])
        q = (h @ w(block, "attn", "q_proj")).reshape(n, cfg.n_head, -1)
        k = (h @ w(block, "attn", "k_proj")).reshape(n, cfg.n_kv_head, -1)
        v = (h @ w(block, "attn", "v_proj")).reshape(n, cfg.n_kv_head, -1)
        q = rope(rms(q, block["attn"]["q_norm"]["scale"]), cos, sin)
        k = rope(rms(k, block["attn"]["k_norm"]["scale"]), cos, sin)
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * cfg.head_dim ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        x = x + a.reshape(n, -1) @ w(block, "attn", "out_proj")
        h = rms(x, block["ln2"]["scale"])
        gate = jax.nn.silu(h @ w(block, "mlp", "gate_proj"))
        return x + (gate * (h @ w(block, "mlp", "up_proj"))) @ w(
            block, "mlp", "down_proj")

    @jax.jit
    def head(x, scale, rows):
        return rms(x[-1], scale) @ rows.astype(jnp.float32).T

    with jax.default_matmul_precision("highest"):
        embed = params["tok_embed"]["embedding"]
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        for i in range(cfg.n_layer):
            x = layer(x, params[f"block_{i}"])
        step = 16384                # vocab rows per head matmul
        out = [head(x, params["ln_f"]["scale"], embed[i:i + step])
               for i in range(0, cfg.vocab_size, step)]
        return np.concatenate([np.asarray(o) for o in out])


def probe_ids(tok) -> list[int]:
    return tok.encode(render_chatml(
        [{"role": "system", "content": SYSTEM},
         {"role": "user", "content": "Who created you?"}]))


def engine_prefill_logits(engine, ids) -> np.ndarray:
    """Last-position logits of the engine's own one-shot prefill program
    (the bucketed ``_prefill`` every short prompt goes through)."""
    bucket = engine._bucket_for(len(ids))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(ids)] = ids
    last, _ = engine._prefill(engine.params, jnp.asarray(padded),
                              jnp.asarray([len(ids)], np.int32))
    return np.asarray(last[0], np.float32)


def logit_error(got: np.ndarray, want: np.ndarray, what: str) -> dict:
    if got.shape != want.shape or not np.isfinite(got).all():
        raise RuntimeError(f"{what}: logits shape {got.shape} vs "
                           f"{want.shape} or non-finite")
    spread = float(np.std(want))
    rms = float(np.sqrt(np.mean((got - want) ** 2))) / spread
    worst = float(np.max(np.abs(got - want))) / spread
    if rms > LOGIT_RMS_TOL or worst > LOGIT_MAX_TOL:
        raise RuntimeError(
            f"{what}: logits off by rms {rms:.4g} / max {worst:.4g} of "
            f"their spread (bounds {LOGIT_RMS_TOL} / {LOGIT_MAX_TOL})")
    return {"rms_over_std": round(rms, 5), "max_over_std": round(worst, 5),
            "argmax_agrees": bool(got.argmax() == want.argmax())}


def check_cost_model(engine) -> dict:
    kind = jax.devices()[0].device_kind
    cm = engine.cost_model
    if cm is None or cm.device_kind != kind:
        raise RuntimeError(f"engine cost model {cm} is not for {kind!r}")
    if jax.devices()[0].platform != "cpu" and not any(
            sub in kind.lower() and cm.peak_flops == peak * cm.tp
            for sub, peak in cost.PEAKS):
        raise RuntimeError(f"peaks of {kind!r} came from no table row")
    return {"device_kind": cm.device_kind, "peak_flops": cm.peak_flops,
            "peak_hbm_bw": cm.peak_hbm_bw}


def phase_serve(plan: Plan, seed: int, base: dict) -> None:
    t0 = time.perf_counter()
    cfg = serve_config(plan, plan.serve_layers)
    params, quant_s = bench._distinct_nf4_base(cfg, Qwen3, fmt="int8",
                                               seed=seed)
    tok = build_tokenizer()
    with serving(plan, cfg, tok, lambda: params) as (engine, port, args):
        t1 = time.perf_counter()
        asked = drive_requests(port, plan, tok, engine)
        traffic_s = time.perf_counter() - t1
        health = json.loads(http_json(port, "GET", "/health"))
        if health["status"] != "ok":
            raise RuntimeError(f"/health says {health}")
        metrics = http_json(port, "GET", "/metrics").decode()
        metric(metrics, "llm_build_info")
        probe = probe_ids(tok)
        closeness = logit_error(
            engine_prefill_logits(engine, probe),
            reference_logits(params, cfg, probe), "serve prefill")
        emit(phase="serve", seconds=round(time.perf_counter() - t0, 1),
             layout={"kv": args.kv_layout, "mixed_step": args.mixed_step,
                     "chunked_prefill": args.chunked_prefill,
                     "kv_cache_dtype": args.kv_cache_dtype},
             n_layer=cfg.n_layer, quantize_s=round(quant_s, 1),
             weight_bytes=engine.cost_model.weight_bytes,
             requests=len(asked), tokens_served=sum(asked),
             traffic_s=round(traffic_s, 1),
             compile_s=round(engine.compile_meter.compile_seconds, 1),
             compiled_programs=engine.compile_meter.compile_events,
             mixed_blocks=engine.mixed_blocks,
             prefill_logits_vs_f32=closeness,
             cost_model=check_cost_model(engine),
             llm_hbm_unattributed_bytes=metric(
                 metrics, "llm_hbm_unattributed_bytes"),
             ledger_device_bytes=get_ledger().device_bytes(),
             memory_stats=memory_stats())
    del engine, params
    emit(phase="serve.released", **release(plan, "serve", base))


# ---------------------------------------------------------------- train


def phase_train(plan: Plan, seed: int, base: dict) -> None:
    t0 = time.perf_counter()
    built = bench.build_qlora_scan_step(
        plan.vocab, seed=seed, n_layer=plan.train_layers, **plan.geom)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, plan.vocab,
                                 (plan.train_batch, plan.train_seq)),
                    jnp.int32)
    batch = (x, jnp.roll(x, -1, axis=1))
    key = jax.random.PRNGKey(seed)
    t1 = time.perf_counter()
    step = built.qstep.lower(built.lora, built.opt_state, built.qparams,
                             batch, key).compile()
    compile_s = time.perf_counter() - t1
    # scan + remat: the forward scan holds flash's forward kernel, the
    # backward scan its recomputed forward and both backward kernels —
    # the only Pallas calls here (the NF4 base dequantises in XLA)
    n_calls = step.as_text().count("tpu_custom_call")
    if plan.on_chip and n_calls < 3:
        raise RuntimeError(
            f"train step holds {n_calls} tpu_custom_call(s): flash "
            "attention's forward and backward gave way to dense")
    lora, opt = built.lora, built.opt_state
    losses = []
    t1 = time.perf_counter()
    for _ in range(plan.train_steps):      # the SAME batch every step
        lora, opt, loss = step(lora, opt, built.qparams, batch, key)
        losses.append(float(loss))
    step_s = (time.perf_counter() - t1) / plan.train_steps
    if not np.isfinite(losses).all() or not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on a repeated batch: {losses}")
    emit(phase="train", seconds=round(time.perf_counter() - t0, 1),
         n_layer=built.cfg.n_layer, params_total=built.n_total,
         batch=plan.train_batch, seq=plan.train_seq,
         quantize_s=round(built.quant_s, 1),
         compile_s=round(compile_s, 1), step_s=round(step_s, 3),
         flash_custom_calls=n_calls, losses=[round(v, 5) for v in losses],
         memory_stats=memory_stats())
    del built, lora, opt, step
    emit(phase="train.released", **release(plan, "train", base))


# -------------------------------------------------------------- kernels


def phase_kernels(plan: Plan, seed: int) -> None:
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    results = {}
    nf4_kernel.XLA_FALLBACKS.clear()
    highest = functools.partial(jax.default_matmul_precision, "highest")

    def dispatch(x, t):
        return fused_kernel_matmul(x, t, jnp.bfloat16)

    for k, n in plan.matmul_shapes:
        w = jnp.asarray(rng.normal(0, 0.02, (k, n)), jnp.float32)
        s = jnp.asarray(np.exp(rng.normal(0, 0.3, (k,))), jnp.float32)
        tensors = {
            "nf4": (nf4.quantize(w), nf4.dequantize),
            "int4": (int4.rtn_quantize(w), int4.decode),
            "awq": (awq.AWQTensor(int4.rtn_quantize(w * s[:, None]),
                                  1.0 / s), awq.decode),
        }
        for name, (t, decode) in tensors.items():
            with highest():
                w_ref = decode(t, jnp.float32)
            for m in plan.matmul_ms:
                x = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.bfloat16)
                run, _ = compiled_hlo(dispatch, x, t, on_chip=plan.on_chip)
                with highest():
                    want = x.astype(jnp.float32) @ w_ref
                results[f"{name}_{m}x{k}x{n}"] = close_enough(
                    run(x, t), want, KERNEL_TOL, f"{name} {m}x{k}x{n}")
        # QLoRA's backward through the frozen base: dx = dy @ W^T
        t, m = tensors["nf4"][0], plan.matmul_ms[-1]
        x = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.bfloat16)
        dy = jnp.asarray(rng.normal(0, 1, (m, n)), jnp.bfloat16)

        def nf4_grad(x, t, dy):
            return jax.grad(lambda x: jnp.sum(
                nf4_kernel.nf4_matmul(x, t).astype(jnp.float32)
                * dy.astype(jnp.float32)))(x)

        run, _ = compiled_hlo(nf4_grad, x, t, dy, on_chip=plan.on_chip)
        with highest():
            want = dy.astype(jnp.float32) @ nf4.dequantize(
                t, jnp.float32).T
        results[f"nf4_grad_{m}x{k}x{n}"] = close_enough(
            run(x, t, dy), want, KERNEL_TOL, f"nf4 grad {m}x{k}x{n}")
    if nf4_kernel.XLA_FALLBACKS:
        raise RuntimeError("a fused matmul gave way to XLA dequant: "
                           f"{dict(nf4_kernel.XLA_FALLBACKS)}")

    # ops/int8_matmul.py has no production caller (W8A16 serves through
    # XLA): one compile-and-compare, nothing more
    k, n = plan.matmul_shapes[0]
    t = int8.quantize(jnp.asarray(rng.normal(0, 0.02, (k, n)), jnp.float32))
    x = jnp.asarray(rng.normal(0, 1, (plan.matmul_ms[0], k)), jnp.bfloat16)
    run, _ = compiled_hlo(lambda x, t: int8_matmul(x, t, jnp.bfloat16),
                          x, t, on_chip=plan.on_chip)
    with highest():
        want = x.astype(jnp.float32) @ int8.decode(t, jnp.float32)
    results["int8_pallas"] = close_enough(run(x, t), want, KERNEL_TOL,
                                          "int8 pallas")

    q, kk, v, do = (jnp.asarray(rng.normal(0, 1, plan.flash_shape),
                                jnp.bfloat16) for _ in range(4))

    def weighted(attn):
        def fn(q, k, v, do):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v), q, k, v)
            return (out,) + vjp(do.astype(out.dtype))
        return fn

    run, n_calls = compiled_hlo(weighted(flash_attention), q, kk, v, do,
                                on_chip=plan.on_chip)
    if plan.on_chip and n_calls < 3:
        raise RuntimeError(f"flash fwd+bwd holds {n_calls} custom calls")
    f32 = [a.astype(jnp.float32) for a in (q, kk, v, do)]
    with highest():
        want = jax.jit(weighted(dense_attention))(*f32)
    for name, got, ref in zip(("out", "dq", "dk", "dv"),
                              run(q, kk, v, do), want):
        results[f"flash_{name}"] = close_enough(
            got, ref, KERNEL_TOL, f"flash {name} {plan.flash_shape}")
    emit(phase="kernels", seconds=round(time.perf_counter() - t0, 1),
         checks=len(results), worst_error=round(max(results.values()), 5),
         tolerance=KERNEL_TOL, xla_fallbacks=0,
         errors={k: round(v, 5) for k, v in results.items()})


# ------------------------------------------------------------ four chips


def shard_report(engine, n: int) -> dict:
    """No device may hold more than its share: the largest kernels and
    the KV pool must each lie in ``n`` equal shards on ``n`` devices."""
    flat = jax.tree_util.tree_leaves(engine.params)
    biggest = sorted(flat, key=lambda a: a.nbytes)[-3:]
    pool = [buf for layer in engine.paged.kv for buf in layer.values()]
    for arr in biggest + pool[:2]:
        shards = arr.addressable_shards
        sizes = {s.data.nbytes for s in shards}
        if (len({s.device for s in shards}) != n
                or sizes != {arr.nbytes // n}):
            raise RuntimeError(
                f"array {arr.shape} {arr.dtype} is not in {n} equal "
                f"shards: {[(str(s.device), s.data.nbytes) for s in shards]}")
    per_device = [memory_stats(d) for d in jax.devices()[:n]]
    used = [s.get("bytes_in_use", 0) for s in per_device]
    if min(used) and max(used) > 1.5 * min(used):
        raise RuntimeError(f"devices are unevenly loaded: {used}")
    return {"largest_param_shard_bytes": biggest[-1].nbytes // n,
            "kv_pool_shard_bytes": pool[0].nbytes // n,
            "per_device_memory_stats": per_device}


def phase_tp(plan: Plan, seed: int, base: dict, chips: int) -> None:
    """The sharded serving path users depend on (``--tensor-parallel-size
    4``) against the one-device engine in the same process."""
    t0 = time.perf_counter()
    cfg = serve_config(plan, plan.tp_layers)
    tree = [bench._distinct_nf4_base(cfg, Qwen3, fmt="int8", seed=seed)[0]]
    tok = build_tokenizer()
    probe = probe_ids(tok)
    logits, compile_s = {}, {}
    report = None
    # the TP server takes the tree out of this frame (``tree.pop``), so
    # the only copy left after sharding is the sharded one
    for name, take, extra in (
            ("one_device", lambda: tree[0], ()),
            ("tp", tree.pop, ("--tensor-parallel-size", str(chips)))):
        with serving(plan, cfg, tok, take, *extra) as (engine, port, _):
            chat(port, "Who are you?", 8, stream=False)
            chat(port, "Introduce yourself.", 6, stream=True)
            logits[name] = engine_prefill_logits(engine, probe)
            compile_s[name] = round(engine.compile_meter.compile_seconds, 1)
            if name == "tp":
                if engine.tp != chips:
                    raise RuntimeError(f"engine.tp is {engine.tp}")
                report = shard_report(engine, chips)
        del engine
        gc.collect()    # the engine is a cycle; its tree must go now
    released = release(plan, "tp", base)
    emit(phase="tp", chips=chips, seconds=round(time.perf_counter() - t0, 1),
         n_layer=cfg.n_layer, compile_s=compile_s,
         tp_vs_one_device_logits=logit_error(
             logits["tp"], logits["one_device"], "tp prefill"),
         **report, released=released)


# ----------------------------------------------------------------- main


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the weights and the inputs")
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4: only the tensor-parallel serving path "
                             "and the one-device engine it is compared "
                             "with")
    opts = parser.parse_args()
    dev = require_tpu()
    devices = jax.devices()
    if len(devices) < opts.chips:
        raise RuntimeError(f"--chips {opts.chips} needs that many devices; "
                           f"JAX found {len(devices)}")
    plan = QWEN3_8B
    cache_dir = enable_compilation_cache()
    emit(platform=dev.platform, device_kind=dev.device_kind,
         device_count=len(devices), jax=jax.__version__,
         jaxlib=importlib.metadata.version("jaxlib"),
         libtpu=importlib.metadata.version("libtpu"),
         tpu_runtime=dev.client.platform_version.splitlines(),
         compile_cache_dir=cache_dir,
         # an (almost) empty directory: every compile_s below is cold
         compile_cache_entries=(len(os.listdir(cache_dir))
                                if cache_dir else None),
         seed=opts.seed, model="Qwen3-8B widths", departures=DEPARTURES)
    base = baseline(plan)
    if opts.chips == 4:
        phase_tp(plan, opts.seed, base, opts.chips)
    else:
        phase_serve(plan, opts.seed, base)
        phase_train(plan, opts.seed, base)
        phase_kernels(plan, opts.seed)
    emit(summary="all phases passed",
         peak_bytes_in_use=memory_stats().get("peak_bytes_in_use"),
         claim=None)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
