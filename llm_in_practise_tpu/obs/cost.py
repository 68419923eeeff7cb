"""The repo's ONE analytic FLOP / HBM-byte cost model.

Until this module existed the audited accounting lived copy-pasted in
four places — ``bench.py`` (the per-token training FLOP model the MFU
gate is built on), ``tools/tpu_mfu_ablation.py`` /
``tools/tpu_mfu_ablation_14b.py`` (which imported pieces of it), and a
timing probe that hand-rolled a ``6·N`` variant against a hard-coded
v5e peak — and the serving stack could not see it at all:
nothing at ``/metrics`` said whether a replica was compute-bound or
bandwidth-bound. This module is the single source of truth:

- **Chip peaks**: one table of published rates by ``device_kind``
  substring, each row naming its source (:data:`PEAKS` bf16 FLOP/s,
  :data:`HBM_BW` and :data:`ICI_BW` bytes/s are its columns);
  :func:`chip_peak` / :func:`chip_hbm_bw` resolve the attached device.
  An accelerator that is in no row raises; only the CPU backend gets
  nominal stand-ins so the tests' renderings keep a value.
- **Training model** (moved verbatim from ``bench.py``; its BENCH_*
  ``mfu`` numbers are pinned against this code by
  ``tests/test_device_plane.py``): :func:`matmul_param_count` +
  :func:`flops_per_token`.
- **Serving model**: :class:`CostModel` — FLOPs and HBM bytes per
  prefill chunk / decode block, derived from model geometry and the
  actual resident weight bytes (quantized trees count their *packed*
  bytes — exactly what the HBM controller streams). The engine's
  :class:`~llm_in_practise_tpu.obs.meter.DispatchMeter` consumes it to
  export live per-phase MFU / bandwidth-utilization gauges.
- **Device memory telemetry**: :func:`device_memory_stats` — whatever
  ``device.memory_stats()`` reports (bytes in use / peak / limit),
  fail-open ``{}`` on a backend that reports nothing (CPU).

Conventions (unchanged from the audited bench model): a matmul weight
element costs 2 FLOPs per token forward; causal attention costs
``4·k·D`` per new token attending ``k`` keys (``QKᵀ`` + ``AV``,
``D`` = query width). Utilizations are *useful*-work fractions —
padding, draft-model proposals, and host work all show up as lost
utilization, which is the point.
"""

from __future__ import annotations

import dataclasses

# Chip rates by ``device_kind`` substring (first match wins). Every row
# names its source: the "Key chip specifications" of that generation's
# page in the Google Cloud TPU documentation (peak bf16 compute, HBM
# bandwidth, inter-chip interconnect bandwidth per chip — Gbit/s there,
# bytes/s here).
#   (substrings, bf16 FLOP/s, HBM bytes/s, ICI bytes/s per chip, source)
_CHIPS = (
    (("v6 lite", "v6e"), 918e12, 1640e9, 448e9,
     'Google Cloud documentation, "TPU v6e"'),
    (("v5p",), 459e12, 2765e9, 600e9,
     'Google Cloud documentation, "TPU v5p"'),
    (("v5 lite", "v5e"), 197e12, 819e9, 200e9,
     'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB '
     "HBM at 819 GB/s, 1,600 Gbit/s interconnect"),
    (("v4",), 275e12, 1228e9, 300e9,
     'Google Cloud documentation, "TPU v4"'),
    (("v3",), 123e12, 900e9, 140e9,
     'Google Cloud documentation, "TPU v3"'),
)


def _column(i: int):
    return tuple((sub, row[i]) for row in _CHIPS for sub in row[0])


PEAKS = _column(1)    # bf16 peak FLOP/s
# Finding 13 measured isolated decode matmuls streaming at essentially
# the datasheet HBM rates, so a llm_dispatch_hbm_bw_util near 1.0 means
# the dispatch is honestly bandwidth-bound, not "the model flatters us".
HBM_BW = _column(2)   # bytes/s
# Aggregate inter-chip interconnect bandwidth per chip — the divisor
# behind the analytic `llm_collective_seconds_total` attribution for
# tensor-parallel serving (docs/serving-tp.md). These are optimistic
# all-links-busy numbers; a ring all-reduce uses a subset, so treat the
# derived seconds as a LOWER bound on collective time.
ICI_BW = _column(3)   # bytes/s

# What the CPU backend (device_kind "cpu") divides by, so the tests'
# gauge renderings keep a value. Not any device's rates: a CPU run's
# utilizations are renderings, never measurements. An accelerator whose
# device_kind matches no row raises instead of borrowing these.
FALLBACK_PEAK = 197e12
FALLBACK_HBM_BW = 819e9
FALLBACK_ICI_BW = 200e9


def _lookup(kind: str, table, cpu_value: float) -> float:
    low = kind.lower()
    for sub, value in table:
        if sub in low:
            return value
    if low == "cpu":
        return cpu_value
    raise ValueError(
        f"device_kind {kind!r} matches no row of obs/cost.py's chip "
        "table: add its published rates (with their source) before "
        "reporting a utilization on it")


def chip_peak() -> tuple[str, float]:
    """(device_kind, bf16 peak FLOP/s) of the attached accelerator."""
    import jax

    kind = jax.devices()[0].device_kind
    return kind, _lookup(kind, PEAKS, FALLBACK_PEAK)


def chip_hbm_bw(kind: str | None = None) -> float:
    """HBM bandwidth (bytes/s) for ``kind`` (default: attached device)."""
    if kind is None:
        kind, _ = chip_peak()
    return _lookup(kind, HBM_BW, FALLBACK_HBM_BW)


def chip_ici_bw(kind: str | None = None) -> float:
    """Aggregate ICI bandwidth (bytes/s per chip) for ``kind``."""
    if kind is None:
        kind, _ = chip_peak()
    return _lookup(kind, ICI_BW, FALLBACK_ICI_BW)


def device_memory_stats(device=None) -> dict:
    """Whatever memory facts the runtime reports — each key optional, so
    a backend exposing only ``bytes_limit`` still informs callers (the
    CPU backend reports nothing and returns ``{}``; telemetry must fail
    open, never fail a scrape)."""
    try:
        import jax

        dev = device if device is not None else jax.local_devices()[0]
        s = dev.memory_stats() or {}
    except Exception:  # noqa: BLE001 — no backend / no stats: fail open
        return {}
    out = {}
    for ours, theirs in (("bytes_in_use", "bytes_in_use"),
                         ("peak_bytes_in_use", "peak_bytes_in_use"),
                         ("bytes_limit", "bytes_limit")):
        v = s.get(theirs)
        if v is not None:
            out[ours] = int(v)
    return out


def hbm_stats() -> dict:
    """``bench.py``-artifact shape of :func:`device_memory_stats`:
    ``hbm_bytes_in_use`` / ``hbm_bytes_limit`` / derived headroom."""
    s = device_memory_stats()
    used, limit = s.get("bytes_in_use"), s.get("bytes_limit")
    out = {}
    if used is not None:
        out["hbm_bytes_in_use"] = used
    if limit is not None:
        out["hbm_bytes_limit"] = limit
    if used is not None and limit is not None:
        out["hbm_headroom_gib"] = round((limit - used) / 2**30, 2)
    return out


def tree_bytes(params) -> int:
    """Resident bytes of a (possibly quantized) param tree — the HBM
    traffic one full weight read costs. Quantized containers are
    pytrees whose leaves are the packed/absmax arrays, so this sums
    exactly what the controller streams."""
    import jax

    return sum(getattr(x, "nbytes", 0) or 0 for x in jax.tree.leaves(params))


# --------------------------------------------------------------------------
# Training model (moved verbatim from bench.py — its artifact MFU numbers
# are pinned against this code)
# --------------------------------------------------------------------------


def matmul_param_count(params, *, tied_head: bool) -> int:
    """Total elements of kernels that run as matmuls per token: every 2-D
    leaf except the embedding gather; the tied head re-uses the embedding
    as a true matmul, so it is added back once."""
    from llm_in_practise_tpu.utils.tree import flatten_with_paths

    n = 0
    embed_size = 0
    for path, leaf in flatten_with_paths(params).items():
        # kernels only — in stacked (scan/MoE) layouts norm scales are
        # 2-D too, but they never hit the MXU. 3-D kernels' full size is
        # the per-token matmul weight count.
        if not (path.endswith("/kernel") or path.endswith("/embedding")):
            continue
        if getattr(leaf, "ndim", 0) not in (2, 3):
            continue
        if "tok_embed" in path or "pos_embed" in path:
            embed_size = max(embed_size, leaf.size)
            continue
        n += leaf.size
    if tied_head:
        n += embed_size
    return n


def flops_per_token(m: int, n_layer: int, seq: int, dim: int,
                    *, train_full: bool) -> float:
    """Per-token FLOPs. ``m`` = matmul param elements (2 FLOPs each fwd);
    attention (causal, avg S/2 keys): QK^T + AV = 4·(S/2)·D per layer fwd.
    Full training = 3× fwd (bwd = dX + dW). QLoRA freezes the base, so the
    weight-gradient matmuls are skipped: 2× fwd for the matmul part, but
    attention backward is still full (no weights there) = 3× its fwd."""
    matmul_fwd = 2.0 * m
    attn_fwd = 2.0 * n_layer * seq * dim  # 4·(S/2)·D per layer
    if train_full:
        return 3.0 * (matmul_fwd + attn_fwd)
    return 2.0 * matmul_fwd + 3.0 * attn_fwd


# --------------------------------------------------------------------------
# Serving model — per-dispatch FLOPs and HBM bytes
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Matmul/attention geometry a serving forward pays per token."""

    matmul_params: int     # weight elements hit by matmuls per token
    n_layer: int
    attn_dim: int          # query width per token (n_head · head_dim)
    kv_dim: int            # KV width per cached token (n_kv_head · head_dim)
    # residual-stream width — the payload of the row-parallel activation
    # all-reduces under tensor parallelism (2 per layer: attention
    # out-projection + MLP down-projection). 0 = unknown (collective
    # attribution renders nothing).
    hidden: int = 0
    # vocab width — the lm_head's logits reduction is row-parallel too
    # (rule table: P("model", "fsdp") on its in axis) and on large-vocab
    # models it is a third of the per-token wire. 0 = unknown.
    vocab: int = 0


def geometry_from_config(cfg) -> Geometry | None:
    """Derive :class:`Geometry` from a model config, or ``None`` for
    families the analytic model doesn't cover (MLA/MoE — fail open:
    the gauges simply don't render there).

    The Qwen3 branch reproduces :func:`matmul_param_count` on the real
    tree EXACTLY (verified in tests): head ``V·d`` (tied or not — the
    logits matmul runs either way) + per layer ``d·q + 2·d·kv`` (QKV)
    + ``q·d`` (output) + ``3·d·inter`` (gate/up/down)."""
    if cfg is None:
        return None
    n_layer = getattr(cfg, "n_layer", None)
    vocab = getattr(cfg, "vocab_size", None)
    if not n_layer or not vocab or hasattr(cfg, "n_experts"):
        return None     # routed experts: the work follows the routing
    if hasattr(cfg, "hidden_size") and hasattr(cfg, "n_kv_head"):
        d = cfg.hidden_size
        q = cfg.n_head * cfg.head_dim
        kv = cfg.n_kv_head * cfg.head_dim
        inter = cfg.intermediate_size
        m = vocab * d + n_layer * (d * q + 2 * d * kv + q * d
                                   + 3 * d * inter)
        return Geometry(m, n_layer, q, kv, hidden=d, vocab=vocab)
    if hasattr(cfg, "embed_dim") and hasattr(cfg, "mlp_ratio"):
        # GPT-family: MHA (kv width == q width), 2-matmul MLP (in/out)
        d = cfg.embed_dim
        inter = int(cfg.mlp_ratio * d)
        m = vocab * d + n_layer * (4 * d * d + 2 * d * inter)
        return Geometry(m, n_layer, d, d, hidden=d, vocab=vocab)
    return None


@dataclasses.dataclass(frozen=True)
class CostModel:
    """FLOPs/bytes per serving dispatch + the chip peaks to divide by.

    ``weight_bytes`` is the RESIDENT tree (packed bytes for quantized
    formats); ``kv_bytes_per_token`` is one cached token's KV rows
    across all layers. Build with :meth:`from_model` (returns ``None``
    when the geometry can't be derived)."""

    geometry: Geometry
    weight_bytes: int
    kv_bytes_per_token: int
    peak_flops: float
    peak_hbm_bw: float
    device_kind: str = "unknown"
    # tensor-parallel extent of the serving mesh (the ``model`` axis):
    # FLOPs/bytes stay GLOBAL (each chip does its shard's share), so the
    # peaks multiply by ``tp`` and every utilization reads per-chip —
    # the ISSUE 10 per-chip attribution convention. ``ici_bw`` divides
    # the analytic per-chip collective wire bytes into seconds.
    tp: int = 1
    ici_bw: float = FALLBACK_ICI_BW

    @classmethod
    def from_model(cls, model, params, *, cache_dtype=None,
                   tp: int = 1) -> "CostModel | None":
        """Derive a cost model from a live model + its (possibly
        quantized) param tree, priced at the attached device's table
        row. Returns ``None`` when the model family isn't covered —
        callers treat that as "no device plane", not an error. An
        accelerator whose ``device_kind`` is in no table raises
        (:func:`_lookup`). ``tp``: tensor-parallel extent of the serving
        mesh's ``model`` axis — peaks scale by it so MFU/BW utilizations
        attribute per chip."""
        geom = geometry_from_config(getattr(model, "config", None))
        if geom is None:
            return None
        import jax.numpy as jnp

        itemsize = jnp.dtype(cache_dtype or jnp.bfloat16).itemsize
        device_kind, peak = chip_peak()
        tp = max(int(tp), 1)
        return cls(
            geometry=geom,
            weight_bytes=tree_bytes(params),
            kv_bytes_per_token=geom.n_layer * 2 * geom.kv_dim * itemsize,
            peak_flops=peak * tp,
            peak_hbm_bw=chip_hbm_bw(device_kind) * tp,
            device_kind=device_kind,
            tp=tp,
            ici_bw=chip_ici_bw(device_kind),
        )

    # -- FLOPs ---------------------------------------------------------------

    def step_flops(self, new_tokens: int, attended_keys: float) -> float:
        """FLOPs of one forward over ``new_tokens`` positions that
        together attend ``attended_keys`` keys (sum over the new
        tokens of each one's visible context, itself included)."""
        g = self.geometry
        return (2.0 * g.matmul_params * new_tokens
                + 4.0 * g.attn_dim * g.n_layer * attended_keys)

    @staticmethod
    def chunk_keys(chunk_len: int, start: int) -> float:
        """``attended_keys`` of a causal prefill chunk of ``chunk_len``
        tokens starting at context offset ``start``."""
        return chunk_len * start + chunk_len * (chunk_len + 1) / 2.0

    @staticmethod
    def block_keys(n_steps: int, context: int) -> float:
        """``attended_keys`` of ``n_steps`` sequential decode steps for
        ONE slot whose context is ``context`` at block start."""
        return n_steps * context + n_steps * (n_steps + 1) / 2.0

    # -- bytes ---------------------------------------------------------------

    def step_bytes(self, weight_passes: float, kv_read_tokens: float,
                   new_tokens: int) -> float:
        """HBM bytes of a dispatch: ``weight_passes`` full weight reads
        (a decode block of n steps reads the tree n times; a prefill
        chunk reads it once), ``kv_read_tokens`` cached tokens' KV rows
        read by attention, plus the writes of the ``new_tokens`` fresh
        rows. Activations are deliberately excluded (noise next to
        weights at serving batch sizes)."""
        return (weight_passes * self.weight_bytes
                + self.kv_bytes_per_token * (kv_read_tokens + new_tokens))

    # -- collectives (tensor parallel) ---------------------------------------

    def collective_bytes(self, new_tokens: float,
                         quantized: bool = False) -> float:
        """Per-chip ICI wire bytes of one forward's row-parallel
        all-reduces over ``new_tokens`` positions: 2 per layer
        (attention out-projection + MLP down-projection, ``hidden``
        elements each) PLUS the lm_head's logits reduction (``vocab``
        elements — a third of the per-token wire on large-vocab
        models), all at ring-all-reduce traffic ``2·(tp-1)/tp`` per
        chip. Activations are priced at bf16 (2 bytes); the int8
        quantized collective (``--tp-quantized-collectives``,
        parallel/collectives.py) halves the LAYER part — the lm_head
        reduction is deliberately never quantized (argmax fragility),
        so its bytes stay bf16. Returns 0 at tp=1 or unknown
        geometry."""
        if self.tp <= 1 or self.geometry.hidden <= 0:
            return 0.0
        layer_elems = 2.0 * self.geometry.n_layer * self.geometry.hidden \
            * new_tokens
        head_elems = float(self.geometry.vocab) * new_tokens
        per_elem = 1.0 if quantized else 2.0
        return 2.0 * (self.tp - 1) / self.tp * (
            layer_elems * per_elem + head_elems * 2.0)

    def collective_seconds(self, nbytes: float) -> float:
        """Analytic LOWER-bound seconds those wire bytes cost at the
        chip's aggregate ICI bandwidth (docs/observability.md states
        the caveat — XLA overlaps collectives with compute)."""
        if nbytes <= 0 or self.ici_bw <= 0:
            return 0.0
        return nbytes / self.ici_bw

    # -- utilizations --------------------------------------------------------

    def mfu(self, flops: float, seconds: float) -> float | None:
        if seconds <= 0:
            return None
        return flops / seconds / self.peak_flops

    def hbm_util(self, nbytes: float, seconds: float) -> float | None:
        if seconds <= 0:
            return None
        return nbytes / seconds / self.peak_hbm_bw
