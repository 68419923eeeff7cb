"""Checkpoint save/restore — the reference's five tiers, one API.

Tiers covered (see SURVEY §5.4):
1. weights-only; 2. weights + vocab + config metadata; 3. full training state
(model + opt + step + best metric; RNG determinism via recorded seed/step);
rotation keep-last-N (``DeepSeekLike_spare_MoE_wikitext2.py:550-572``) and
``latest`` / ``best_model`` naming + auto-resume
(``temp/ddp_gpt_bpe_tokenizer_02.py:356-383,497-498``). Multi-host: only the
coordinator process writes (rank-0 gating parity).

Format: flax msgpack for the array pytree + a JSON sidecar for metadata
(config dicts, vocab, step). Works on any pytree, including sharded arrays
(gathered on save for these sizes; Orbax-style fully-sharded async save is a
later tier).
"""

from __future__ import annotations

import json
import os
import re

import jax
import numpy as np
from flax import serialization

from llm_in_practise_tpu.core import dist

_CKPT_RE = re.compile(r"^(?P<prefix>.+)_(?P<step>\d{8})\.msgpack$")


def _host_pytree(tree):
    """Bring a (possibly sharded) pytree fully addressable on host."""
    def to_host(x):
        if isinstance(x, jax.Array):
            return np.asarray(jax.device_get(x))
        return x
    return jax.tree_util.tree_map(to_host, tree)


def save_checkpoint(
    ckpt_dir: str,
    tree,
    step: int,
    *,
    prefix: str = "ckpt",
    keep: int = 5,
    metadata: dict | None = None,
) -> str | None:
    """Write ``{prefix}_{step:08d}.msgpack`` (+ .json sidecar); rotate old."""
    if not dist.is_coordinator():
        return None
    os.makedirs(ckpt_dir, exist_ok=True)
    path = os.path.join(ckpt_dir, f"{prefix}_{step:08d}.msgpack")
    data = serialization.to_bytes(_host_pytree(tree))
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)
    meta = dict(metadata or {})
    meta["step"] = int(step)
    with open(path.replace(".msgpack", ".json"), "w") as f:
        json.dump(meta, f, ensure_ascii=False, indent=1, default=str)
    _rotate(ckpt_dir, prefix, keep)
    return path


def save_named(ckpt_dir: str, tree, name: str, metadata: dict | None = None) -> str | None:
    """Unrotated named checkpoint, e.g. ``best_model`` / final weights."""
    if not dist.is_coordinator():
        return None
    os.makedirs(ckpt_dir, exist_ok=True)
    path = os.path.join(ckpt_dir, f"{name}.msgpack")
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(serialization.to_bytes(_host_pytree(tree)))
    os.replace(tmp, path)
    if metadata is not None:
        with open(os.path.join(ckpt_dir, f"{name}.json"), "w") as f:
            json.dump(metadata, f, ensure_ascii=False, indent=1, default=str)
    return path


def latest_checkpoint(ckpt_dir: str, prefix: str = "ckpt") -> str | None:
    if not os.path.isdir(ckpt_dir):
        return None
    best = None
    for fname in os.listdir(ckpt_dir):
        m = _CKPT_RE.match(fname)
        if m and m.group("prefix") == prefix:
            step = int(m.group("step"))
            if best is None or step > best[0]:
                best = (step, os.path.join(ckpt_dir, fname))
    return best[1] if best else None


def restore_checkpoint(path: str, target=None):
    """Restore pytree from ``path``. With ``target`` (a template pytree)
    returns the same structure; without, returns nested dicts of numpy arrays.
    Returns (tree, metadata_dict)."""
    with open(path, "rb") as f:
        data = f.read()
    tree = (
        serialization.from_bytes(target, data)
        if target is not None
        else serialization.msgpack_restore(data)
    )
    meta_path = path.replace(".msgpack", ".json")
    meta = {}
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
    return tree, meta


def _rotate(ckpt_dir: str, prefix: str, keep: int) -> None:
    entries = []
    for fname in os.listdir(ckpt_dir):
        m = _CKPT_RE.match(fname)
        if m and m.group("prefix") == prefix:
            entries.append((int(m.group("step")), fname))
    entries.sort()
    for _, fname in entries[:-keep] if keep > 0 else []:
        os.remove(os.path.join(ckpt_dir, fname))
        sidecar = os.path.join(ckpt_dir, fname.replace(".msgpack", ".json"))
        if os.path.exists(sidecar):
            os.remove(sidecar)
