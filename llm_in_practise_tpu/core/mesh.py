"""Mesh & topology: the TPU-native replacement for process groups.

The reference wires distributed training through
``dist.init_process_group("nccl"|"gloo")`` plus per-strategy wrapper engines
(DDP / FSDP / DeepSpeed — see reference
``LLM_Distributed_Trainning/PyTorch/ddp_basics/ddp_gpt_wikitext2.py:170-186``).
Here a single ``jax.sharding.Mesh`` with named axes subsumes all of those:

- ``data``   — batch sharding (DDP parity; gradient all-reduce compiled by XLA)
- ``fsdp``   — parameter/optimizer/grad sharding (ZeRO-3 / FSDP parity)
- ``model``  — tensor parallelism (attention heads / FFN hidden)
- ``expert`` — MoE expert parallelism
- ``seq``    — sequence/context parallelism (ring attention)

Strategies in :mod:`llm_in_practise_tpu.parallel.strategy` pick axis sizes and
parameter partition rules; XLA inserts the ICI/DCN collectives.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis names, in mesh order.
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "model"
AXIS_EXPERT = "expert"
AXIS_SEQ = "seq"
MESH_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_TENSOR, AXIS_EXPERT, AXIS_SEQ)

# Batch dims are sharded over both data-like axes so DP and FSDP compose.
BATCH_AXES = (AXIS_DATA, AXIS_FSDP)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. ``-1`` on at most one axis means "all remaining".

    Mirrors the knob surface of the reference launchers (``--nproc_per_node``,
    DeepSpeed ``hostfile`` slots) as a declarative topology instead of env vars.
    """

    data: int = -1
    fsdp: int = 1
    model: int = 1
    expert: int = 1
    seq: int = 1

    def sizes(self) -> tuple[int, ...]:
        return (self.data, self.fsdp, self.model, self.expert, self.seq)

    def resolve(self, n_devices: int, *, allow_subset: bool = False) -> tuple[int, ...]:
        sizes = list(self.sizes())
        wildcards = [i for i, s in enumerate(sizes) if s == -1]
        if len(wildcards) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {self}")
        fixed = math.prod(s for s in sizes if s != -1)
        if wildcards:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wildcards[0]] = n_devices // fixed
        elif fixed != n_devices and not (allow_subset and fixed < n_devices):
            # A silently-undersized mesh would train on a fraction of the
            # hardware; require explicit opt-in (debug meshes) instead.
            raise ValueError(
                f"mesh {sizes} wants {fixed} devices but {n_devices} are "
                "available (pass allow_subset=True for a deliberate subset)"
            )
        return tuple(sizes)


def build_mesh(
    spec: MeshSpec | None = None, devices=None, *, allow_subset: bool = False
) -> Mesh:
    """Build a 5-axis device mesh covering all available devices.

    ``allow_subset`` lets a fully-pinned spec use the first N devices (debug
    meshes) — single-process only: in a multi-process run a subset would
    hold only the coordinator's devices and hang every other process at the
    first collective.
    """
    spec = spec or MeshSpec()
    devices = list(devices if devices is not None else jax.devices())
    if allow_subset and jax.process_count() > 1:
        raise ValueError(
            "allow_subset is single-process only: a device subset in a "
            "multi-process run would hold only some processes' devices and "
            "hang the rest at the first collective — size the mesh to the "
            "full device count instead"
        )
    shape = spec.resolve(len(devices), allow_subset=allow_subset)
    n = math.prod(shape)
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def single_device_mesh() -> Mesh:
    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


def require_tpu() -> jax.Device:
    """The first attached device, which must be a TPU.

    For processes that asked for the chip (``chip_smoke.py``,
    ``bench.py``, ``tools/tpu_*``): they fail here, naming what JAX
    found, instead of reporting the CPU backend's numbers as a
    device's."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"this entry point needs a TPU; JAX found platform "
            f"{dev.platform!r} (device_kind {dev.device_kind!r})")
    return dev


def batch_sharding(mesh: Mesh, seq_sharded: bool = False) -> NamedSharding:
    """Sharding for a per-step batch: leading dim split over data×fsdp.

    ``seq_sharded`` additionally splits the second (sequence) dim over the
    ``seq`` axis — the input layout for sequence-parallel training.
    """
    if seq_sharded:
        return NamedSharding(mesh, PartitionSpec(BATCH_AXES, AXIS_SEQ))
    return NamedSharding(mesh, PartitionSpec(BATCH_AXES))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def local_batch_size(mesh: Mesh, global_batch_size: int) -> int:
    n = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
    if global_batch_size % n != 0:
        raise ValueError(f"global batch {global_batch_size} not divisible by {n}")
    return global_batch_size // n
