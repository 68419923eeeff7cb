"""OpenAI-compatible API server over a checkpoint — the serving entry point.

TPU-native counterpart of the reference's
``Scripts/inference/07-deepseek1.5b-api-infr.py`` (FastAPI
``/v1/chat/completions`` with usage accounting and uvicorn main) plus what
that script stubs out (``stream`` → 501, ``:110-112``): here streaming SSE
works, requests batch continuously onto KV-cache slots (vLLM-style), and
``/metrics`` exports the Prometheus names the reference's platform scrapes
(``Inference_Platfrom/README.md:1676-1692``).

Run: ``python examples/serve_openai.py [--port 8000]`` then
``curl localhost:8000/v1/chat/completions -d '{"messages": [...]}'``.
"""

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from llm_in_practise_tpu.ckpt import checkpoint as ckpt
from llm_in_practise_tpu.data import BPETokenizer
from llm_in_practise_tpu.models import Qwen3, Qwen3Config
from llm_in_practise_tpu.serve.api import OpenAIServer
from llm_in_practise_tpu.serve.engine import InferenceEngine


def validate_args(args, error) -> None:
    """Flag-combination validation, split from :func:`main` so the
    rules are unit-testable without loading a checkpoint
    (tests/test_tp_serving.py). ``error`` is ``parser.error`` (raises
    SystemExit with the message). Mutates ``args.speculative`` to the
    role-resolved value.

    ISSUE 10 deleted the ``--tensor-parallel-size`` fail-fasts against
    ``--quantized_dir`` (packed leaves now shard via
    quant/sharding.py component shardings) and ``--draft-model-path``
    (the small draft replicates across the mesh).
    """
    if args.quantized_dir and args.lora_modules:
        error("--lora-modules with --quantized_dir is not supported "
              "(adapters cannot merge into packed 4-bit kernels)")
    if args.tp_quantized_collectives and args.tp <= 1:
        error("--tp-quantized-collectives requires "
              "--tensor-parallel-size > 1 (there is no collective to "
              "quantize on one chip)")
    if args.tp_quantized_collectives and args.quantized_dir:
        error("--tp-quantized-collectives with --quantized_dir is not "
              "supported: packed trees run their matmuls through the "
              "fused dequant interceptor, which the quantized-"
              "collective interceptor does not compose with")
    if args.lora_modules:
        # fail fast at the CLI — a typo'd spec or missing checkpoint
        # should not surface as a traceback after the (slow) base
        # checkpoint restore (ISSUE 15 registry wiring)
        import os as _os

        from llm_in_practise_tpu.serve.adapters import parse_lora_modules

        try:
            modules = parse_lora_modules(args.lora_modules)
        except ValueError as e:
            error(f"--lora-modules: {e}")
        for name, path in modules.items():
            if name == getattr(args, "model_name", None):
                error(f"--lora-modules: adapter name {name!r} collides "
                      "with --model_name (the base model's served name)")
            ckpt_file = (_os.path.join(path, "adapter.msgpack")
                         if _os.path.isdir(path) else path)
            if not _os.path.exists(ckpt_file):
                error(f"--lora-modules {name}: no adapter checkpoint "
                      f"at {path} (want adapter.msgpack + sidecar from "
                      "ckpt.save_named)")
    if args.role != "both" and not args.kv_remote:
        error(f"--role {args.role} requires --kv-remote: the KV handoff "
              "between the prefill and decode pools travels through the "
              "shared kv_pool server")
    # a draft model still needs an EXPLICIT K (checked before the
    # decode-role default below resolves one, or the requirement would
    # be silently bypassed on --role decode)
    if args.draft_model_path and args.speculative is None:
        error("--draft-model-path requires --speculative K")
    # decode replicas default speculation ON (ISSUE 9 / ROADMAP item 4):
    # the fused verify-inside-the-block round is the production decode
    # path once no prefill ever shares the replica; --speculative 0
    # opts out explicitly. Only the ngram proposer can be defaulted
    # (the draft-model path was handled above).
    from llm_in_practise_tpu.serve.disagg import default_speculative_k

    resolved_spec = default_speculative_k(args.role, args.speculative)
    if args.role == "decode" and args.speculative is None:
        print(f"decode replica: ngram speculation ON by default "
              f"(k={resolved_spec}; --speculative 0 disables)")
    args.speculative = resolved_spec
    if args.draft_model_path and args.speculative is None:
        # --speculative 0 resolved the opt-out: a draft model with
        # speculation off is contradictory — fail at the CLI, not with
        # an engine ValueError traceback after the checkpoint loads
        error("--draft-model-path with --speculative 0 is "
              "contradictory: drop the draft model or pass a "
              "positive K")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--model_path", default="/tmp/qwen3_merged/model.msgpack")
    p.add_argument("--tokenizer_path", default="/tmp/qwen3_sft_bpe.json")
    p.add_argument("--model_name", default="qwen3-tpu")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--max_slots", type=int, default=8,
                   help="concurrent sequences in the continuous batch")
    p.add_argument("--cache_len", type=int, default=512)
    p.add_argument("--lora-modules", dest="lora_modules", nargs="*",
                   default=[], metavar="NAME=PATH",
                   help="serve LoRA adapters as extra model names "
                        "(vLLM --lora-modules parity)")
    p.add_argument("--enable-prefix-caching", dest="prefix_caching",
                   action="store_true",
                   help="reuse prompt-prefix KV across requests "
                        "(vLLM APC parity)")
    p.add_argument("--session-store", dest="session_store",
                   action="store_true",
                   help="session-native serving (serve/sessions.py): "
                        "requests carrying a session id (X-Session-ID "
                        "header or body field) keep their conversation "
                        "KV pinned across turns, and finished turns "
                        "publish to the kv-pool handoff namespace when "
                        "--kv-remote is set — the fleet-wide warm path "
                        "behind the gateway's --routing ring")
    p.add_argument("--session-ttl", dest="session_ttl", type=float,
                   default=600.0, metavar="SECONDS",
                   help="idle TTL for pinned session KV "
                        "(with --session-store)")
    p.add_argument("--enable-chunked-prefill", dest="chunked_prefill",
                   type=int, nargs="?", const=256, default=None,
                   metavar="CHUNK",
                   help="prefill long prompts in CHUNK-token steps "
                        "interleaved with decode (vLLM parity; default 256)")
    p.add_argument("--tensor-parallel-size", dest="tp", type=int, default=1,
                   help="shard the model over N devices for serving "
                        "(vLLM --tensor-parallel-size parity)")
    p.add_argument("--kv-offload", dest="kv_offload", action="store_true",
                   help="tiered KV: offload evicted/finished prefix KV to "
                        "host RAM and re-hit it (LMCache local-CPU parity)")
    p.add_argument("--kv-remote", dest="kv_remote", default=None,
                   metavar="HOST:PORT",
                   help="share prefix KV through a kv_pool server at "
                        "HOST:PORT (LMCache lm:// parity; start one with "
                        "python -m llm_in_practise_tpu.serve.kv_pool)")
    p.add_argument("--role", default="both",
                   choices=["prefill", "decode", "both"],
                   help="disaggregated serving role (llm-d parity): "
                        "'prefill' replicas only prefill and hand the "
                        "prompt KV to the pool's handoff namespace; "
                        "'decode' replicas claim it and run pure decode "
                        "(zero prefill interference); 'both' (default) "
                        "is a full replica. prefill/decode require "
                        "--kv-remote (the handoff travels through the "
                        "shared pool) and a gateway running the disagg "
                        "router (examples/serve_gateway.py --routing "
                        "disagg)")
    p.add_argument("--speculative", dest="speculative", type=int,
                   nargs="?", const=4, default=None, metavar="K",
                   help="ngram/prompt-lookup speculative decoding: draft K "
                        "tokens per step, verify in one forward (lossless "
                        "for greedy; vLLM ngram speculator parity). The "
                        "fused spec round verifies the K drafts in ONE "
                        "dispatch. DEFAULT ON for --role decode replicas "
                        "(K=4) — pass --speculative 0 to disable there")
    p.add_argument("--no-mixed-step", dest="mixed_step",
                   action="store_false", default=True,
                   help="disable the fused mixed-batch step (default ON: "
                        "while prompts chunk-prefill AND slots decode, one "
                        "dispatch advances every prefill chunk and "
                        "decodes every ready row — mixed-load steps cost "
                        "1 dispatch instead of 2)")
    p.add_argument("--draft-model-path", dest="draft_model_path",
                   default=None,
                   help="checkpoint of a SMALLER model for draft-model "
                        "speculative decoding (requires --speculative; "
                        "vLLM speculative_model parity — the ngram "
                        "speculator runs when this is omitted)")
    p.add_argument("--max-queue", dest="max_queue", type=int, default=None,
                   metavar="N",
                   help="admission control: reject (HTTP 429 queue_full) "
                        "once N requests wait — ingress backpressure at "
                        "the engine")
    p.add_argument("--queue-timeout", dest="queue_timeout", type=float,
                   default=None, metavar="SECONDS",
                   help="admission control: shed requests that waited "
                        "past this deadline (HTTP 429 queue_full) — the "
                        "gateway's retry policy routes them elsewhere")
    p.add_argument("--trace-file", dest="trace_file", default=None,
                   metavar="PATH",
                   help="append Chrome trace events (one JSON per line) "
                        "for every request span to PATH — open in "
                        "Perfetto / chrome://tracing; the in-memory "
                        "span ring is always on at GET /debug/traces "
                        "(LLM_TPU_TRACE=off disables tracing)")
    p.add_argument("--ttft-slo", dest="ttft_slo", type=float, default=None,
                   metavar="SECONDS",
                   help="SLO goodput accounting: TTFT threshold — "
                        "tokens of requests that miss it count as "
                        "llm_goodput_tokens_total{slo=violated}")
    p.add_argument("--tpot-slo", dest="tpot_slo", type=float, default=None,
                   metavar="SECONDS",
                   help="SLO goodput accounting: per-token (TPOT) "
                        "threshold (docs/observability.md device plane)")
    p.add_argument("--kv-layout", dest="kv_layout", default="paged",
                   choices=["paged", "contiguous"],
                   help="KV cache layout (docs/paged-kv.md): 'paged' "
                        "(default) carves one pool into fixed-size "
                        "pages behind per-slot block tables — admission "
                        "reserves actual pages, prefixes share "
                        "refcounted pages (COW), handoff ships only "
                        "live pages (vLLM PagedAttention parity); "
                        "'contiguous' is the previous slot-owns-a-"
                        "cache_len-region layout, kept as a fallback "
                        "for one release (golden tokens are identical)")
    p.add_argument("--kv-page-size", dest="kv_page_size", type=int,
                   default=16, metavar="TOKENS",
                   help="tokens per KV page (paged layout; vLLM "
                        "block_size parity)")
    p.add_argument("--kv-pool-tokens", dest="kv_pool_tokens", type=int,
                   default=None, metavar="TOKENS",
                   help="page-pool capacity in tokens (paged layout); "
                        "default max_slots*cache_len — set LOWER than "
                        "that to serve more slots than worst-case "
                        "contexts would allow, relying on page-granular "
                        "admission + preemption")
    p.add_argument("--kv-cache-dtype", dest="kv_cache_dtype",
                   default="float32", choices=["float32", "bfloat16", "fp8"],
                   help="KV cache storage dtype; fp8 (e4m3) halves KV HBM "
                        "vs bf16 (vLLM --kv-cache-dtype fp8 parity)")
    p.add_argument("--tp-quantized-collectives",
                   dest="tp_quantized_collectives", action="store_true",
                   help="int8 activation all-reduce for the row-parallel "
                        "TP matmuls (ZeRO++ idiom, arxiv 2306.10209): "
                        "halves the per-token interconnect traffic. "
                        "LOSSY opt-in — greedy tokens are checked "
                        "against the plain path at startup and the flag "
                        "falls back (with a warning) on mismatch "
                        "(docs/serving-tp.md)")
    p.add_argument("--quantized_dir", default=None,
                   help="serve a packed 4-bit export from "
                        "examples/quantize_ptq.py (weights stay packed in "
                        "HBM, fused dequant matmuls — vLLM "
                        "compressed-tensors serving parity; composes "
                        "with --tensor-parallel-size via "
                        "quant/sharding.py component shardings)")
    return p


def load_checkpoint(args, mesh):
    """``(model, params)`` from the command line's checkpoint: a packed
    4-bit export (``--quantized_dir``) or a merged msgpack
    (``--model_path``)."""
    if args.quantized_dir:
        from llm_in_practise_tpu.quant import io as quant_io
        from llm_in_practise_tpu.serve.quantized import QuantizedModel

        params, meta = quant_io.load_packed(args.quantized_dir)
        if meta.get("family") == "gpt":  # the hermetic PTQ demo's model
            from llm_in_practise_tpu.models import GPT, GPTConfig

            base = GPT(GPTConfig.from_dict(meta["config"]))
        else:
            base = Qwen3(Qwen3Config.from_dict(meta["config"]))
        model = QuantizedModel(base, mesh=mesh)
        print(f"packed 4-bit model: {args.quantized_dir} "
              f"({meta.get('method')}, ppl {meta.get('ppl')}) "
              f"| devices: {jax.devices()}")
    else:
        params, meta = ckpt.restore_checkpoint(args.model_path)
        model = Qwen3(Qwen3Config.from_dict(meta["config"]))
        print(f"model: {args.model_path} | devices: {jax.devices()}")
    return model, params


def build_server(args, tok, load_model, error) -> OpenAIServer:
    """Everything between a validated command line and ``serve()``:
    mesh, model, layout, sharding, KV tiers, adapters, sessions, the
    engine and the HTTP server around it. ``load_model(mesh)`` returns
    ``(model, params)`` — :func:`load_checkpoint` for the CLI, a seeded
    tree for ``chip_smoke.py``, which drives this same function.
    ``error`` is ``parser.error``."""
    # the mesh exists BEFORE the model loads: a packed QuantizedModel
    # needs it at construction (mesh -> the SPMD-partitionable XLA
    # dequant path; Pallas custom calls are opaque to the partitioner)
    mesh = None
    if args.tp > 1:
        from llm_in_practise_tpu.parallel import strategy as S

        strat = S.tensor_parallel(model=args.tp, data=1)
        mesh = strat.build_mesh(jax.devices()[: args.tp])

    model, params = load_model(mesh)

    from llm_in_practise_tpu.data.sft import IM_END

    shard_fn = None
    if args.tp > 1:
        from llm_in_practise_tpu.serve.engine import shard_params_for_serving

        # quant-aware (ISSUE 10): packed Int8/Int4/NF4/AWQ leaves get
        # component shardings from the same serving rule table, so an
        # int8 14B loads shard-parallel instead of failing fast
        shard_fn = lambda p: shard_params_for_serving(p, strat, mesh)
        params = shard_fn(params)
        print(f"tensor parallel over {args.tp} devices"
              + (" (packed quantized tree, component shardings)"
                 if args.quantized_dir else ""))
        if args.role == "decode":
            # the documented disagg fleet shape (docs/serving-tp.md):
            # multi-chip decode replicas fed by single-chip prefill
            print(f"fleet shape: --role decode with tp={args.tp} — "
                  "single-chip prefill replicas feed this replica "
                  "through the kv-pool handoff (entries reshard on "
                  "claim)")
    if args.tp_quantized_collectives:
        # golden-token-checked opt-in (ZeRO++ idiom, lossy): the int8
        # collective serves only if its greedy tokens match the plain
        # path on the probe prompt — else warn and fall back. One gate
        # policy, shared with tools/tp_ladder_bench.py.
        from llm_in_practise_tpu.parallel.collectives import (
            maybe_quantized_collectives,
        )

        model, _ = maybe_quantized_collectives(model, mesh, params)

    # KV is only valid under the weights that produced it, so every served
    # model (base + each adapter) gets its OWN tiered pool; the remote
    # server is shared but namespaced per model name (LMCache semantics).
    def make_kv_pool(model_name):
        if not (args.kv_offload or args.kv_remote):
            return None
        from llm_in_practise_tpu.serve.kv_pool import (
            HostKVPool, RemoteKVClient, TieredKV,
        )

        remote = None
        if args.kv_remote:
            rhost, rport = args.kv_remote.rsplit(":", 1)
            remote = RemoteKVClient((rhost, int(rport)),
                                    namespace=model_name)
        return TieredKV(HostKVPool(), remote)

    if args.kv_offload or args.kv_remote:
        tiers = "HBM->host" + ("->remote" if args.kv_remote else "")
        print(f"tiered KV pool: {tiers} (namespaced per model)")

    draft_model = draft_params = None
    if args.draft_model_path:  # combos validated at the argparse block
        draft_params, draft_meta = ckpt.restore_checkpoint(
            args.draft_model_path)
        draft_model = Qwen3(Qwen3Config.from_dict(draft_meta["config"]))
        print(f"draft model: {args.draft_model_path}")

    # disaggregated serving: the handoff store rides the shared pool
    # server (pin-until-claimed namespace, serve/disagg.py). Any replica
    # with a pool connection gets one — "both" replicas then still serve
    # /internal/handoff/prefill and claim entries when a role pool is
    # degraded. Per MODEL: each served name (base + every adapter) gets
    # its own namespace, so cross-model handoffs can never collide.
    def make_handoff(model_name):
        if not args.kv_remote:
            return None
        from llm_in_practise_tpu.serve.disagg import RemoteHandoff

        rhost, rport = args.kv_remote.rsplit(":", 1)
        return RemoteHandoff((rhost, int(rport)), namespace=model_name)

    handoff = make_handoff(args.model_name)
    if handoff is not None and args.role != "both":
        print(f"disaggregated role: {args.role} "
              f"(handoff via {args.kv_remote})")

    engine_kw = dict(
        max_slots=args.max_slots, cache_len=args.cache_len,
        eos_id=tok.token_to_id(IM_END),
        cache_dtype={"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                     "fp8": jnp.float8_e4m3fn}[args.kv_cache_dtype],
        prefix_cache=args.prefix_caching,
        chunked_prefill=args.chunked_prefill, mesh=mesh,
        speculative_k=args.speculative,
        mixed_step=args.mixed_step,
        max_queue=args.max_queue,
        queue_timeout_s=args.queue_timeout,
        ttft_slo_s=args.ttft_slo, tpot_slo_s=args.tpot_slo,
        draft_model=draft_model, draft_params=draft_params,
        kv_layout=args.kv_layout,
        kv_page_size=args.kv_page_size,
        kv_pool_tokens=args.kv_pool_tokens,
    )
    # batched multi-LoRA (ISSUE 15): adapters ride the BASE engine's
    # fused dispatch through an AdapterRegistry — one base-weight copy,
    # mixed-adapter slots in one step. The legacy engine-per-adapter
    # path remains only for tiered/remote KV setups, where each served
    # model needs its own pool + handoff namespace (one weight set per
    # engine); build_adapter_engines warns when it takes it.
    lora_modules = {}
    adapter_registry = None
    if args.lora_modules:
        from llm_in_practise_tpu.serve.adapters import parse_lora_modules

        lora_modules = parse_lora_modules(args.lora_modules)
        if not (args.kv_offload or args.kv_remote):
            from llm_in_practise_tpu.serve.multi_lora import AdapterRegistry

            adapter_registry = AdapterRegistry(params, mesh=mesh)
    session_store = None
    if args.session_store:
        from llm_in_practise_tpu.serve.sessions import SessionStore

        session_store = SessionStore(ttl_s=args.session_ttl)
        warm = ("fleet warm path via " + args.kv_remote
                if args.kv_remote else "local pins only (no --kv-remote)")
        print(f"session store: ttl {args.session_ttl:g}s, {warm}")
    engine = InferenceEngine(model, params,
                             kv_pool=make_kv_pool(args.model_name),
                             role=args.role, handoff=handoff,
                             adapter_registry=adapter_registry,
                             session_store=session_store,
                             **engine_kw)
    adapters = {}
    if lora_modules and adapter_registry is not None:
        from llm_in_practise_tpu.serve.multi_lora import AdapterHandle

        for name, path in lora_modules.items():
            adapter_registry.register(name, path)
        adapters = {name: AdapterHandle(engine, name)
                    for name in lora_modules}
        print(f"adapters (batched multi-LoRA, one shared engine): "
              f"{sorted(adapters)}")
    elif lora_modules:
        from llm_in_practise_tpu.serve.adapters import (
            build_adapter_engines,
        )

        # adapter engines skip the draft: the draft approximates the
        # BASE distribution, and each copy would cost its own draft KV
        adapter_kw = {k: v for k, v in engine_kw.items()
                      if not k.startswith("draft_")}
        adapters = build_adapter_engines(
            model, params, lora_modules,
            param_transform=shard_fn,
            # per-model tiers AND per-model handoff namespace: adapter
            # requests disaggregate exactly like the base model's
            engine_kw_for=lambda name: {"kv_pool": make_kv_pool(name),
                                        "role": args.role,
                                        "handoff": make_handoff(name)},
            **adapter_kw
        )
        print(f"adapters: {sorted(adapters)}")
    if args.trace_file:
        from llm_in_practise_tpu.obs.trace import get_tracer

        get_tracer().set_trace_file(args.trace_file)
        print(f"chrome trace events -> {args.trace_file} "
              "(open in Perfetto)")
    return OpenAIServer(engine, tok, model_name=args.model_name,
                        adapters=adapters, role=args.role,
                        handoff=handoff)


def main():
    p = build_parser()
    args = p.parse_args()
    validate_args(args, p.error)
    tok = BPETokenizer.load(args.tokenizer_path)
    server = build_server(args, tok,
                          functools.partial(load_checkpoint, args), p.error)
    print(f"serving on {args.host}:{args.port} "
          f"(/v1/chat/completions, /v1/models, /health, /metrics, "
          f"/debug/traces)")
    server.serve(host=args.host, port=args.port)


if __name__ == "__main__":
    main()
