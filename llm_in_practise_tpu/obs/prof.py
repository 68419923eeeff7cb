"""On-demand XLA profiler capture + jit compile telemetry.

Two device-plane facilities the serving stack was missing:

- :class:`ProfilerCapture` — the machinery behind ``POST
  /debug/profile`` (served by every HTTP server via
  ``serve/http_util.py``): a bounded-duration ``jax.profiler`` trace
  into a fresh directory, ONE capture at a time process-wide
  (``jax.profiler`` supports a single active trace; a second concurrent
  request gets a 409, not a crashed profiler). The response carries the
  capture directory and the Perfetto-loadable ``*.trace.json.gz`` files
  the profiler wrote, so "grab me a device trace of the live replica"
  is one curl instead of a redeploy with ``profile_trace`` wired in.
- :class:`CompileMeter` — wraps the engine's jitted programs and counts
  executable-cache misses plus the seconds they cost (a cache miss's
  call time IS compile+run; the run part is noise next to a multi-second
  compile, and from the serving thread's point of view the whole stall
  is what matters — a recompile that eats 40 s of decode is exactly
  what ``llm_compile_seconds_total`` exists to surface). Uses the
  jitted callable's ``_cache_size`` introspection when available and
  degrades to counting nothing (never to breaking the call) when not.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time


class ProfilerBusyError(RuntimeError):
    """A capture is already in progress (one at a time, process-wide)."""


class ProfilerCapture:
    """Bounded on-demand ``jax.profiler`` capture.

    ``base_dir`` defaults to ``$LLM_TPU_PROFILE_DIR`` or a per-process
    directory under the system temp dir; each capture gets a fresh
    timestamped subdirectory (captures never clobber each other)."""

    MAX_DURATION_S = 60.0
    MIN_DURATION_S = 0.05

    def __init__(self, base_dir: str | None = None):
        self.base_dir = (base_dir
                         or os.environ.get("LLM_TPU_PROFILE_DIR")
                         or os.path.join(tempfile.gettempdir(),
                                         f"llm_tpu_profile_{os.getpid()}"))
        self._lock = threading.Lock()
        # counter lock, NOT the capture lock: busy rejections happen
        # exactly when _lock could not be acquired, and concurrent 409s
        # racing a bare `+= 1` would lose counts
        self._stats_lock = threading.Lock()
        self.captures = 0           # guarded-by: _stats_lock
        self.busy_rejections = 0    # guarded-by: _stats_lock

    def capture(self, duration_s: float = 2.0) -> dict:
        """Record ``duration_s`` (clamped to [MIN, MAX]) of device
        activity; returns ``{"trace_dir", "duration_s", "files",
        "perfetto"}``. Raises :class:`ProfilerBusyError` when a capture
        is already running."""
        duration = min(max(float(duration_s), self.MIN_DURATION_S),
                       self.MAX_DURATION_S)
        if not self._lock.acquire(blocking=False):
            with self._stats_lock:
                self.busy_rejections += 1
            raise ProfilerBusyError(
                "a profiler capture is already in progress — retry when "
                "it finishes (captures are bounded at "
                f"{self.MAX_DURATION_S:.0f}s)")
        try:
            # route through the one trace context (reentrancy-safe,
            # stops on exception) instead of raw start/stop_trace
            from llm_in_practise_tpu.obs import meter

            # a trace someone ELSE started (bench profile_trace around
            # its hot loop) makes our profile_trace degrade to a no-op
            # — that must be a 409, never a 200 with an empty capture
            if meter._profile_lock.locked():
                with self._stats_lock:
                    self.busy_rejections += 1
                raise ProfilerBusyError(
                    "a jax.profiler trace is already active in this "
                    "process (profile_trace around a hot loop?) — "
                    "retry when it finishes")
            with self._stats_lock:
                n_prior = self.captures
            out_dir = os.path.join(
                self.base_dir,
                time.strftime("capture-%Y%m%d-%H%M%S")
                + f"-{n_prior}")
            os.makedirs(out_dir, exist_ok=True)
            with meter.profile_trace(out_dir):
                time.sleep(duration)
            files = sorted(
                os.path.join(root, name)
                for root, _, names in os.walk(out_dir)
                for name in names)
            if not files:
                # the locked() check above raced a concurrent
                # profile_trace entry and ours no-opped: an empty
                # "capture" is a busy outcome, not a success
                raise ProfilerBusyError(
                    "capture produced no trace — a concurrent "
                    "jax.profiler trace was active; retry")
            with self._stats_lock:
                self.captures += 1
            return {
                "trace_dir": out_dir,
                "duration_s": duration,
                "files": files,
                # the Chrome-trace gz the profiler writes next to the
                # xplane protobuf — https://ui.perfetto.dev opens it
                "perfetto": [f for f in files
                             if f.endswith(".trace.json.gz")],
            }
        finally:
            self._lock.release()


_default_profiler: ProfilerCapture | None = None
_default_lock = threading.Lock()


def get_profiler() -> ProfilerCapture:
    """Process-wide capture singleton — every server's
    ``POST /debug/profile`` shares the one-at-a-time lock."""
    global _default_profiler
    with _default_lock:
        if _default_profiler is None:
            _default_profiler = ProfilerCapture()
        return _default_profiler


class CompileMeter:
    """Executable-cache-miss accounting over wrapped jitted callables.

    ``wrap(fn)`` returns a callable that, per invocation, checks whether
    ``fn``'s jit cache grew — growth means this call traced+compiled (or
    loaded a persistent-cache entry: still a stall the serving thread
    paid) and the call's wall time is booked as compile seconds.
    Thread-safe counters; scrapers read plain attributes."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compile_events = 0      # guarded-by: _lock
        self.compile_seconds = 0.0   # guarded-by: _lock

    def note(self, seconds: float) -> None:
        with self._lock:
            self.compile_events += 1
            self.compile_seconds += float(seconds)

    def wrap(self, fn):
        # reach ``_cache_size`` through ``fn`` on every call: the bound
        # C++ method object is invisible to the cycle collector, and a
        # closure over it pins the jitted function — and through its
        # bound-method target the whole engine, weights, KV and
        # compiled programs — for the life of the process
        def counted(*args, **kwargs):
            before = fn._cache_size()
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            if fn._cache_size() > before:
                self.note(time.perf_counter() - t0)
            return out

        counted.__wrapped__ = fn
        return counted
