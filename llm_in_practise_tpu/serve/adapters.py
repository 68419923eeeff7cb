"""Serve-time LoRA adapter loading — vLLM ``--lora-modules`` parity.

The reference serves fine-tuned adapters with
``vllm serve … --enable-lora --lora-modules qwen3-8b-lora=/path/to/adapter``
(``Fine-Tuning/README.md:340-361``): one base model, extra model names
backed by LoRA deltas, selected per request via the OpenAI ``model`` field.

Since ISSUE 15 this module is a thin compatibility shim over
``serve/multi_lora.py``: :func:`build_adapter_engines` builds ONE shared
:class:`InferenceEngine` with an :class:`~.multi_lora.AdapterRegistry`
and returns engine-shaped :class:`~.multi_lora.AdapterHandle` views, so
every adapter rides the same fused dispatch and the base weights live in
HBM exactly once. The legacy engine-per-adapter merged-weight path is
kept (with a warning) only for callers passing per-adapter engine
kwargs (``engine_kw_for`` — separate kv pools / handoff namespaces imply
separate weight sets). Adapters are the ``adapter.msgpack`` +
``adapter.json`` pairs written by ``examples/qwen3_lora_sft.py`` /
``ckpt.save_named``.
"""

from __future__ import annotations

import os

from llm_in_practise_tpu.ckpt import checkpoint as ckpt_lib
from llm_in_practise_tpu.obs.logging import get_logger
from llm_in_practise_tpu.peft import LoRAConfig, merge_lora
from llm_in_practise_tpu.serve.engine import InferenceEngine

_log = get_logger("serve.adapters")


def parse_lora_modules(specs: list[str]) -> dict[str, str]:
    """``["name=/path", ...]`` → {name: path} (the vLLM CLI syntax)."""
    out = {}
    for spec in specs:
        name, sep, path = spec.partition("=")
        if not sep or not name or not path:
            raise ValueError(f"expected name=path, got {spec!r}")
        out[name] = path
    return out


def load_adapter(base_params, adapter_path: str):
    """Restore one adapter checkpoint and merge it into ``base_params``."""
    if os.path.isdir(adapter_path):
        adapter_path = os.path.join(adapter_path, "adapter.msgpack")
    lora_params, meta = ckpt_lib.restore_checkpoint(adapter_path)
    if "lora_config" not in meta:
        raise ValueError(
            f"{adapter_path} has no lora_config metadata sidecar"
        )
    cfg = LoRAConfig.from_dict(meta["lora_config"])
    return merge_lora(base_params, lora_params, cfg)


def build_adapter_engines(
    model,
    base_params,
    modules: dict[str, str],
    param_transform=None,
    engine_kw_for=None,
    **engine_kw,
):
    """Adapter-name → engine-shaped handle map for ``OpenAIServer``.

    Default (registry) path: ONE shared :class:`InferenceEngine` carrying
    an :class:`~.multi_lora.AdapterRegistry`; each name maps to an
    :class:`~.multi_lora.AdapterHandle` that pins its adapter on
    ``submit``. Mixed-adapter slots batch into the same fused dispatch
    and base HBM is paid once regardless of the adapter count.

    Legacy (merged-weight engine-per-adapter) fallback, warned:
    ``engine_kw_for`` given — per-adapter kwargs (kv pools, handoff
    namespaces) assume one weight set per engine.

    ``param_transform`` (optional) post-processes the params handed to
    each built engine — e.g. :func:`..serve.engine.shard_params_for_serving`
    so they follow the base engine's tensor-parallel placement instead of
    replicating host arrays onto every mesh device.

    ``engine_kw_for(name)`` (optional, legacy-only) returns per-adapter
    kwargs merged over ``engine_kw``.
    """
    if engine_kw_for is not None:
        _log.warning(
            "legacy engine-per-adapter path (per-adapter engine kwargs "
            "requested): each of the %d adapter(s) pays full base-model "
            "HBM — the batched multi-LoRA registry (serve/multi_lora.py) "
            "shares one engine across adapters", len(modules))

        def prep(path):
            merged = load_adapter(base_params, path)
            return param_transform(merged) if param_transform else merged

        return {
            name: InferenceEngine(
                model, prep(path), **{**engine_kw, **engine_kw_for(name)})
            for name, path in modules.items()
        }

    from llm_in_practise_tpu.serve.multi_lora import (
        AdapterHandle,
        AdapterRegistry,
    )

    registry = AdapterRegistry(base_params, mesh=engine_kw.get("mesh"))
    params = (param_transform(base_params) if param_transform
              else base_params)
    engine = InferenceEngine(model, params, adapter_registry=registry,
                             **engine_kw)
    for name, path in modules.items():
        registry.register(name, path)
    return {name: AdapterHandle(engine, name) for name in modules}
