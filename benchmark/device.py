"""What the benchmark knows about the chip: that there is one, what JAX
calls it, its published peaks, and its memory high-water mark."""

from __future__ import annotations

# Published peaks by ``device_kind`` substring. A kind in no row is an
# error, never a default. (bf16 FLOP/s, HBM bytes/s, source)
PEAKS = (
    (("v5 lite", "v5e"), 197e12, 819e9,
     'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM '
     "at 819 GB/s"),
    (("v6 lite", "v6e"), 918e12, 1640e9,
     'Google Cloud documentation, "TPU v6e"'),
    (("v5p",), 459e12, 2765e9, 'Google Cloud documentation, "TPU v5p"'),
    (("v4",), 275e12, 1228e9, 'Google Cloud documentation, "TPU v4"'),
)


def peaks(device_kind: str) -> tuple[float, float]:
    """(peak bf16 FLOP/s, peak HBM bytes/s) of one chip of this kind."""
    low = device_kind.lower()
    for subs, flops, bw, _ in PEAKS:
        if any(s in low for s in subs):
            return flops, bw
    raise ValueError(f"device_kind {device_kind!r} is in no row of "
                     "benchmark/device.py's table of published peaks")


def require_chips(n: int) -> list:
    """The first ``n`` accelerator devices. Raises where JAX found the CPU
    backend or fewer chips than the cell asks for: a measurement path
    never falls back."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise RuntimeError("no accelerator: JAX found only the CPU backend")
    if len(devices) < n:
        raise RuntimeError(f"the cell needs {n} chip(s); JAX found "
                           f"{len(devices)}")
    return devices[:n]


def describe(devices: list) -> dict:
    """The ``device`` object of a result line, as JAX reports it;
    ``memory_peak_bytes`` is the peak on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
