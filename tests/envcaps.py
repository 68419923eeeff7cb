"""Environment capability probes backing tier-1 skip-guards.

Some tier-1 tests need a capability the CPU test backend lacks
(multi-process collectives, host memory spaces, enough devices).
Carrying them as F's would make the dot count a known-failure ledger
instead of a signal. Each probe below asserts ONE precise capability;
the skip reason carries the probe's finding, so a skip reads as "this
env cannot run this" and the test re-arms on an env that can.

Keep probes cheap and side-effect-free: they run at collection time in
every tier-1 invocation.
"""

from __future__ import annotations

import functools
import inspect


@functools.lru_cache(maxsize=None)
def shard_map_has_check_vma() -> bool:
    """``jax.shard_map`` — the API the in-tree ring/Ulysses attention,
    pipeline and TP collectives call — takes ``check_vma``. Without it
    every shard_map path raises TypeError before any math runs."""
    import jax

    return "check_vma" in inspect.signature(jax.shard_map).parameters


SHARD_MAP_CHECK_VMA_REASON = (
    "jax.shard_map() has no check_vma kwarg — the sequence-parallel, "
    "pipeline and TP paths pass it explicitly")


@functools.lru_cache(maxsize=None)
def backend_platform() -> str:
    """Initializes the JAX backend — call ONLY from inside a probe or
    a lazy reason function, never at module import."""
    import jax

    return jax.devices()[0].platform


@functools.lru_cache(maxsize=None)
def multiprocess_collectives_supported() -> bool:
    """The CPU backend refuses multi-process computations outright
    (``INVALID_ARGUMENT: Multiprocess computations aren't implemented
    on the CPU backend``) — two-process allreduce tests need a real
    accelerator backend."""
    return backend_platform() != "cpu"


def multiprocess_reason() -> str:
    return (f"multiprocess collectives are not implemented on the "
            f"{backend_platform()} backend (XlaRuntimeError "
            "INVALID_ARGUMENT from jax.distributed two-process "
            "allgather)")


@functools.lru_cache(maxsize=None)
def host_device_count() -> int:
    """How many devices the backend exposes — the tensor-parallel
    serving suite needs >= 4 (the conftest forces
    ``--xla_force_host_platform_device_count=8`` virtual CPU devices;
    a bare env without the flag, or a 1-chip TPU host, re-arms the
    skips automatically)."""
    import jax

    try:
        return len(jax.devices())
    except Exception:
        return 0


def tp_devices_reason(need: int) -> str:
    return (f"tensor-parallel serving tests need >= {need} devices; "
            f"this backend exposes {host_device_count()} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 with "
            f"JAX_PLATFORMS=cpu, as tests/conftest.py does)")


@functools.lru_cache(maxsize=None)
def has_pinned_host_memory() -> bool:
    """ZeRO-offload places optimizer state in the ``pinned_host``
    memory space; the CPU backend only exposes ``unpinned_host``."""
    import jax

    try:
        return any(m.kind == "pinned_host"
                   for m in jax.devices()[0].addressable_memories())
    except Exception:
        return False


def pinned_host_reason() -> str:
    return (f"device {backend_platform()!r} exposes no pinned_host "
            "memory space (ValueError from device_put with "
            "memory_kind=pinned_host); ZeRO-offload placement needs an "
            "accelerator backend")
