"""Host-gap flight recorder — the per-step engine-loop timeline.

The device plane (obs/cost.py, PR 4) books what happens *inside* a
dispatch; this module books the time *between* dispatches — the host
work (queue scans, admission, index building, drafting, sampling
commits) that arxiv 2311.03687's runtime dissection shows dominating
bandwidth-bound decode, and that the ROADMAP item-3 async-overlap
refactor must drive to zero. The recorder turns "the chip never waits
on Python" from a hope into a gated, regression-tested quantity:

- :class:`StepTrace` — a bounded ring of per-step records. The engine
  brackets each ``step()`` with :meth:`step_begin`/:meth:`step_end`,
  marks named host activities with :meth:`scope`, and brackets every
  dispatch with :meth:`window_begin` / :meth:`window_issued` /
  :meth:`window_end`. At step end the record partitions the step's
  wall clock into ``{activity: seconds}`` + dispatch-window seconds
  (``device_s``) + an ``other`` remainder, so coverage
  (1 − other/wall) is a first-class number the serve benches gate on
  (≥ 95 %).
- **A dispatch window is host time too.** It runs from the first line
  that prepares a dispatch to the instant its results are on the host,
  and has two parts: ``issue`` (argument building, host-to-device
  copies, COW forks, the Python dispatch — the device may be idle) and
  ``wait`` (the fetch the code already blocks on). ``device_s`` is
  their sum: an UPPER bound on device-busy time on the host's clock,
  not a device measurement. ``issue_s`` / ``wait_s`` say how much of it
  the host spent before the device could start.
- **Two programs in flight** (the engine issues step n+1 before it reads
  step n, serve/engine.py): windows are kept in issue order and closed
  oldest first. A record's window lane then TILES: while a dispatch is
  being prepared the lane reads ``issue:<its phase>``, otherwise
  ``wait:<phase of the oldest unread program>``; a lane segment ends where
  the next begins, at the step's end at the latest, and goes on at the next
  step's begin. ``device_s`` is that lane's length: the part of the step's
  wall during which at least one issued program was unread (``issue_s`` +
  ``wait_s`` still), so ``wall_s - device_s`` reads "wall with nothing in
  flight". Host scopes count only the time outside the lane (what they
  did under an unread program was hidden by it; their gross spans are in
  ``segments``). :meth:`window_end` returns a window from the later of
  its begin and the previous window's end, so the windows booked to a
  request never overlap either.
- **One timeline.** Every record carries ``segments``: ``(name, t0,
  t1)`` for each scope and each window part on the ``time.time()`` axis
  of ``start_s`` (one ``perf_counter`` read per edge plus the step's
  clock offset), so a device idle gap from a profile can be laid over
  what the engine thread was doing at that instant. The same edges open
  and close ``jax.profiler.TraceAnnotation``s (``engine_step``,
  ``engine:<activity>``, ``engine:issue:<phase>``,
  ``engine:wait:<phase>``), so any profile holds them on the
  profiler's own clock above the device ops.
- **By thread state** (the partition no cover bends): ``step_begin`` /
  ``step_end`` read ``time.thread_time()`` beside the wall clock, and the
  engine puts :meth:`fetch` around every call in which it FORCES a device
  value (``jax.device_get``, ``block_until_ready``, ``np.asarray`` of a
  device array). A record's ``wall_s`` is ``cpu_s`` (the engine thread's
  CPU seconds outside fetches: host work, covered or not) + ``blocked_s``
  (wall inside fetches: waiting for the device; ``blocked_s <= wait_s``)
  + ``stalled_s`` (neither: waiting to get the GIL back from a handler
  thread, descheduled, asleep in a lock). A fetch is a segment
  ``fetch:<phase of the oldest unread window>`` inside that window's
  ``wait:`` lane segment and an ``engine:fetch:<phase>`` annotation: in a
  profile, an idle gap that ends where a fetch begins is the host
  arriving late, one inside a fetch is the device's own. ``read_seq``
  names the step that issued the program read.
- **Scopes nest**: entering an inner scope pauses the enclosing one, so
  ``index_build`` inside ``admit`` is attributed once, not twice.
  The window lane's time is no scope's (the ``dispatch_wait`` leftover
  is what the scope holds outside the lane: cost-model arithmetic,
  booking).
- **Single-writer**: every mutation happens on the engine thread.
  Scrape threads read :meth:`snapshot` — an atomically swapped dict
  rebuilt once per step — so ``/metrics`` callbacks can never see a
  half-updated step (the torn-read class graftlint's lock pass flags).
- **Dual-lane Perfetto export**: with a Chrome-JSONL sink attached to
  the tracer (``--trace-file`` / ``LLM_TPU_TRACE_FILE``), each step's
  segments are written as trace events on two synthetic threads:
  "engine host lane" (the activities) and "dispatch window lane (host
  clock)" (each window as an ``issue`` and a ``wait`` slice). Both are
  HOST time; where the device was busy is in a ``jax.profiler``
  capture, under the annotations above.

``LLM_TPU_STEPTRACE=off`` disables recording entirely (every hook
degrades to an attribute check; golden tokens are identical either way
— pinned by ``tests/test_steptrace.py``).

Activity glossary (docs/observability.md "Host timeline"):

=================  ==========================================================
``queue_drain``    pending-queue scans: timeout sheds + dequeues
``admit``          admission bookkeeping — prefix lookup, page reservation,
                   slot setup (inner segments excluded)
``plan``           the step's plan: run-ahead, speculation, fusibility checks
``index_build``    host assembly of dispatch inputs (token, index,
                   gather/scatter arrays)
``draft_propose``  speculative drafting on the host (ngram scan or
                   draft-model sync + roll)
``grammar_compile`` lazy per-state grammar compilation — vocab-wide token
                   classification on first visit of an automaton state
                   (serve/constrain.py, ISSUE 12)
``grammar_mask``   per-step staging of the grammar logit masks for
                   constrained slots (compiled-state lookups + array fill)
``adapter_gather`` per-dispatch assembly of the multi-LoRA bank args —
                   slot→row index build + bank snapshot handoff
                   (serve/multi_lora.py, ISSUE 15)
``dispatch_wait``  the dispatch scopes net of their windows' booked time
``sample_commit``  per-token commit/emit loops + prefill finalization
``publish``        handoff entry gather/queue on the engine thread
``other``          unattributed remainder (the coverage gate bounds it)
=================  ==========================================================
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

ACTIVITIES = ("queue_drain", "admit", "plan", "index_build",
              "draft_propose", "grammar_compile", "grammar_mask",
              "adapter_gather", "dispatch_wait", "sample_commit",
              "publish", "other")

# synthetic Chrome-trace thread ids for the dual-lane view; request
# spans use real thread idents (< 2^31), so these can't collide
HOST_LANE_TID = (1 << 31) + 1
WINDOW_LANE_TID = (1 << 31) + 2

_MAX_SEGMENTS_PER_STEP = 256    # bound on one record's ``segments``


def _close(annotation) -> None:
    annotation.__exit__(None, None, None)


def _enabled_from_env() -> bool:
    return os.environ.get("LLM_TPU_STEPTRACE", "").lower() not in (
        "off", "0", "false")


class _Scope:
    """Reusable context manager for one named activity — allocated once
    per (recorder, name) so the hot loop pays attribute access, not
    object churn. Engine-thread only, non-reentrant per name (the
    engine never nests a scope inside itself)."""

    __slots__ = ("_st", "name")

    def __init__(self, st: "StepTrace", name: str):
        self._st = st
        self.name = name

    def __enter__(self):
        self._st._enter(self.name)
        return self

    def __exit__(self, *exc):
        self._st._exit()
        return False


class _Fetch:
    """Reusable context manager around a call that forces a device
    value (see :meth:`StepTrace.fetch`). Allocated once a recorder;
    engine thread only, never nested."""

    __slots__ = ("_st", "_t0", "_cpu0", "_name", "_ann")

    def __init__(self, st: "StepTrace"):
        self._st = st
        self._ann = None

    def __enter__(self):
        st = self._st
        if st._recording:
            phase, _, _, seq = st._open[0] if st._open else ("none",) * 4
            self._name = "fetch:" + phase
            if st._read_seq is None and st._open:
                st._read_seq = seq
            # (an annotation under way is what says "recording" at exit)
            self._ann = TraceAnnotation("engine:" + self._name)
        self._t0 = time.perf_counter()
        self._cpu0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        cpu = time.thread_time() - self._cpu0
        now = time.perf_counter()
        if self._ann is not None:
            _close(self._ann)
            self._ann = None
            st = self._st
            st._blocked_s += now - self._t0
            st._fetch_cpu_s += cpu
            st._segment(self._name, self._t0, now)
        return False


class _NoopScope:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SCOPE = _NoopScope()


class StepTrace:
    """Bounded per-step flight recorder for the engine loop.

    Thread model: ``step_begin``/``step_end``/``scope``/``window_*``
    run on the engine thread only (single writer). The ring is guarded
    for the ``/debug``-style readers; cumulative totals and fractions
    are published through an atomically swapped snapshot dict that
    scrape threads read without locks.
    """

    def __init__(self, capacity: int = 4096, *, enabled: bool | None = None,
                 window: int = 50):
        self.enabled = _enabled_from_env() if enabled is None else enabled
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)  # guarded-by: _lock
        self._lock = threading.Lock()
        # --- engine-thread state (single writer, no lock) ---
        self._scopes = {name: _Scope(self, name) for name in ACTIVITIES}
        self._fetch = _Fetch(self)
        # [name, last reading of the uncovered clock, acc, enter_perf,
        # annotation]
        self._stack: list[list] = []
        self._step_t0: float | None = None
        self._step_wall0 = 0.0
        self._step_ann = None
        self._acts: dict[str, float] = {}
        self._device_s = 0.0
        self._issue_s = 0.0
        self._dispatches = 0
        self._segments: list[tuple] = []   # (name, t0, t1) on perf_counter
        # the thread-state partition: thread_time at the step's begin,
        # wall and CPU seconds inside fetches, the step that issued the
        # program read first
        self._step_cpu0 = 0.0
        self._cpu_owed_s = 0.0
        self._blocked_s = 0.0
        self._fetch_cpu_s = 0.0
        self._read_seq: int | None = None
        self._lock_wait_s = 0.0
        self._gap_before_s = 0.0
        self._chunk_rows = 0
        self._chunk_row_slots = 0
        self._block = [0, 0, 0, 0]   # rows, commits, revealed, committed
        self._sampler_tier: str | None = None
        self._first_tokens = {"program": 0, "host": 0}
        self._extra: dict[str, int] = {}
        self._ahead = False
        self._drain: str | None = None
        self._discarded = 0
        self._last_end: float | None = None   # previous step_end, perf
        # the open dispatch windows, oldest first: [phase, t0, issued,
        # seq of the step that opened it] (issued None: its dispatch is
        # still being prepared), and the end of the window closed last
        self._open: deque = deque()
        self._win_closed = float("-inf")
        # the window lane's segment under way (``_lane`` None: nothing
        # in flight, or no step open): its name, begin and annotation
        self._lane: str | None = None
        self._lane_t0 = 0.0
        self._lane_ann = None
        self._seq = 0
        # --- cumulative totals (engine-thread writes; scrapes read the
        # swapped snapshot, never these) ---
        self._host_seconds = {a: 0.0 for a in ACTIVITIES}
        self._steps_total = 0
        self._step_wall_total = 0.0
        self._device_seconds_total = 0.0
        self._issue_seconds_total = 0.0
        self._thread_seconds = {"cpu": 0.0, "device_wait": 0.0,
                                "stalled": 0.0}
        self._sampler_steps: dict[str, int] = {}
        self._steps_ahead = 0
        self._step_drains: dict[str, int] = {}
        self._tokens_discarded = 0
        # rolling fractions over the last `window` steps (cached floats,
        # same convention as DispatchMeter.per_step)
        self._window = window
        self._busy_roll: deque = deque(maxlen=window)  # (wall, device)
        self._snap = self._build_snapshot()

    @property
    def _recording(self) -> bool:
        return self.enabled and self._step_t0 is not None

    # -- engine-thread hooks --------------------------------------------------

    def scope(self, name: str):
        """``with st.scope("admit"):`` — attribute the enclosed wall
        time (minus inner scopes and dispatch windows) to ``name``."""
        if not self._recording:
            return _NOOP_SCOPE
        return self._scopes[name]

    def _uncovered(self, now: float) -> float:
        """The clock host scopes run on: ``now`` less the window lane's
        length so far in this step, so that a scope counts only what no
        unread program covered."""
        covered = self._device_s
        if self._lane is not None:
            covered += now - self._lane_t0
        return now - covered

    def _enter(self, name: str) -> None:
        now = time.perf_counter()
        u = self._uncovered(now)
        if self._stack:
            top = self._stack[-1]
            top[2] += u - top[1]
        self._stack.append([name, u, 0.0, now,
                            TraceAnnotation("engine:" + name)])

    def _exit(self) -> None:
        now = time.perf_counter()
        u = self._uncovered(now)
        name, last, acc, entered, ann = self._stack.pop()
        _close(ann)
        host = max(0.0, acc + (u - last))
        self._acts[name] = self._acts.get(name, 0.0) + host
        if self._stack:
            self._stack[-1][1] = u
        # GROSS span (enter → exit): nesting shows as containment, like
        # any flame chart; the net seconds are in ``activities``
        self._segment(name, entered, now)

    def _segment(self, name: str, t0: float, t1: float) -> None:
        if len(self._segments) < _MAX_SEGMENTS_PER_STEP:
            self._segments.append((name, t0, t1))

    def _lane_to(self, name: str | None, now: float) -> None:
        """End the window lane's segment under way at ``now`` (booked
        into the open record) and begin ``name``'s, if any."""
        if self._lane is not None:
            dt = now - self._lane_t0
            self._device_s += dt
            if self._lane.startswith("issue:"):
                self._issue_s += dt
            self._segment(self._lane, self._lane_t0, now)
            if self._lane_ann is not None:
                _close(self._lane_ann)
                self._lane_ann = None
        self._lane = name if self._recording else None
        if self._lane is not None:
            self._lane_t0 = now
            self._lane_ann = TraceAnnotation("engine:" + name)

    def _lane_now(self) -> str | None:
        """What the window lane reads: the dispatch being prepared,
        else the oldest unread program, else nothing."""
        if not self._open:
            return None
        if self._open[-1][2] is None:
            return "issue:" + self._open[-1][0]
        return "wait:" + self._open[0][0]

    def window_begin(self, phase: str) -> None:
        """Open a dispatch window: the first line that prepares the
        dispatch. Always stamps (the engine needs the durations for its
        own books); records and annotates only inside a recorded step."""
        now = time.perf_counter()
        recording = self._recording
        self._open.append([phase, now, None,
                           self._seq + 1 if recording else None])
        if recording:
            self._dispatches += 1
        self._lane_to(self._lane_now(), now)

    def window_issued(self) -> None:
        """The jitted call(s) of the window opened last have returned:
        it is in flight until :meth:`window_end` reads it. No
        synchronisation here."""
        now = time.perf_counter()
        self._open[-1][2] = now
        self._lane_to(self._lane_now(), now)

    def window_end(self) -> tuple[float, float]:
        """The OLDEST open window's results are on the host. Returns
        ``(window_s, issue_s)`` from the later of the window's begin and
        the previous window's end (windows booked to a request tile)."""
        now = time.perf_counter()
        _, t0, issued, _ = self._open.popleft()
        issued = now if issued is None else issued
        t0 = max(t0, self._win_closed)
        self._win_closed = now
        self._lane_to(self._lane_now(), now)
        return now - t0, max(0.0, issued - t0)

    def fetch(self):
        """``with st.fetch():`` around a call that FORCES a device value
        between a :meth:`window_issued` and the :meth:`window_end` that
        follows. Its wall is the record's ``blocked_s`` (whether or not
        another program is in flight), its span a segment and an
        annotation named after the OLDEST unread window (the program
        being read), and the step that issued that program the record's
        ``read_seq``. Always stamps; records only inside a recorded
        step; the no-op scope with the recorder off."""
        return self._fetch if self.enabled else _NOOP_SCOPE

    def thread_states(self) -> tuple[float, float, float, float] | None:
        """``(wall, cpu, blocked, stalled)`` seconds of the open step so
        far (not inside a fetch), ``cpu + blocked + stalled == wall``:
        what the engine thread did as a thread. None outside a recorded
        step."""
        if not self._recording:
            return None
        return self._thread_states(time.perf_counter(),
                                   time.thread_time())[0]

    def _thread_states(self, now: float, cpu_now: float):
        """The open step's wall so far by state, and the CPU seconds the
        thread's clock has shown that do not fit this step's wall outside
        fetches: that clock may tick far coarser than the wall clock (10
        ms on a TPU v5e host, where a 17 ms step then reads 0 or 10), so
        what does not fit is owed to the next step, not dropped; a
        window's SUM is what to read there."""
        wall = now - self._step_t0
        blocked = min(self._blocked_s, wall)
        seen = self._cpu_owed_s + max(
            0.0, cpu_now - self._step_cpu0 - self._fetch_cpu_s)
        cpu = min(seen, wall - blocked)
        return (wall, cpu, blocked, wall - blocked - cpu), seen - cpu

    def note_chunk_rows(self, rows: int, row_slots: int) -> None:
        """A chunk dispatch of this step advanced ``rows`` prompts and
        computed ``row_slots`` rows for them (the contiguous
        slot plane's idle rows included): the record's ``chunk_rows`` / ``chunk_row_slots``."""
        if self._recording:
            self._chunk_rows += rows
            self._chunk_row_slots += row_slots

    def note_block_pass(self, rows: int, commits: int, revealed: int,
                        committed: int) -> None:
        """A block-diffusion pass of this step (serve/block_step.py):
        ``rows`` rows really advanced, ``commits`` of them committed their
        block, the others revealed ``revealed`` positions, and
        ``committed`` tokens streamed out: the record's ``block_rows`` /
        ``block_commits`` / ``tokens_revealed`` / ``tokens_committed``."""
        if self._recording:
            for i, v in enumerate((rows, commits, revealed, committed)):
                self._block[i] += v

    def note_sampler_tier(self, tier: str) -> None:
        """This step's decode, fused mixed or block program runs the
        sampler's ``tier`` body (``infer/sampling.py::SAMPLER_TIERS``),
        as the host worked out from the flags it hands the program: the
        record's ``sampler_tier``, ``None`` for a step that dispatched
        no such program."""
        if self._recording:
            self._sampler_tier = tier

    def note_extra(self, **counts: int) -> None:
        """Counts only some models' steps have (serve/step_stats.py: a
        latent cache's ``latent_tokens_attended`` / ``latent_view_tokens``, a
        routed model's ``moe_*``): summed over the step and written into
        its record under their own names; a step without them has no
        such fields."""
        if self._recording:
            for k, v in counts.items():
                self._extra[k] = self._extra.get(k, 0) + v

    def note_first_token(self, path: str) -> None:
        """A prompt that finished in a chunk, mixed or suffix program of
        this step got its first token from the ``program`` or from the
        ``host`` fallback: the record's ``first_tokens_program`` /
        ``first_tokens_host``."""
        if self._recording:
            self._first_tokens[path] += 1

    def note_ahead(self) -> None:
        """This step's dispatch was issued while the previous one was
        unread: the record's ``ahead``."""
        if self._recording:
            self._ahead = True

    def note_drain(self, reason: str) -> None:
        """This step did not run ahead, and why (the engine's name for
        the state that forbade it; ``idle``: nothing was in flight): the
        record's ``drain``. The first reason of a step stands."""
        if self._recording and self._drain is None and not self._ahead:
            self._drain = reason

    def note_discarded(self, tokens: int) -> None:
        """Tokens of rows a program ran past their EOS, dropped when it
        was read: the record's ``tokens_discarded``."""
        if self._recording:
            self._discarded += tokens

    def step_begin(self, *, lock_wait_s: float = 0.0) -> None:
        """Open a step record. ``lock_wait_s``: what the caller waited
        for the engine's step lock before this call (a record field, not
        an activity: it lies before the step's wall clock, inside
        ``gap_before_s``)."""
        if not self.enabled:
            return
        self._step_ann = TraceAnnotation("engine_step")
        self._step_t0 = time.perf_counter()
        self._step_cpu0 = time.thread_time()
        self._step_wall0 = time.time()
        self._blocked_s = 0.0
        self._fetch_cpu_s = 0.0
        self._read_seq = None
        self._lock_wait_s = float(lock_wait_s)
        self._gap_before_s = (self._step_t0 - self._last_end
                              if self._last_end is not None else 0.0)
        self._chunk_rows = 0
        self._chunk_row_slots = 0
        self._block = [0, 0, 0, 0]
        self._sampler_tier = None
        self._first_tokens = {"program": 0, "host": 0}
        self._extra = {}
        self._ahead = False
        self._drain = None
        self._discarded = 0
        self._acts = {}
        self._device_s = 0.0
        self._issue_s = 0.0
        self._dispatches = 0
        self._stack = []
        self._segments = []
        # a program the last step left unread covers this one from its
        # first instant
        self._lane_to(self._lane_now(), self._step_t0)

    def _close_open(self) -> None:
        """Close what an exception left open, so that neither a scope's
        time nor an annotation leaks out of the step: a window whose
        dispatch never returned is dropped (one in flight stays open:
        the next step reads it), the lane's segment ends here."""
        if self._open and self._open[-1][2] is None:
            self._open.pop()
        self._lane_to(None, time.perf_counter())
        while self._stack:
            self._exit()

    def step_abort(self) -> None:
        """Discard the open record (idle background-loop polls must not
        decay the fractions to meaninglessness — same rule as
        ``DispatchMeter.note_step``)."""
        if self._step_t0 is None:
            return
        self._close_open()
        _close(self._step_ann)
        self._step_t0 = None

    def step_end(self, tracer=None) -> dict | None:
        """Close the record: derive ``other``, append to the ring,
        refresh the cumulative totals + the scrape snapshot, and (with a
        sink-carrying ``tracer``) emit the dual-lane Chrome events.
        Returns the record dict (bench/test introspection)."""
        if not self._recording:
            return None
        self._close_open()
        end = time.perf_counter()
        (wall, cpu, blocked, stalled), self._cpu_owed_s = \
            self._thread_states(end, time.thread_time())
        _close(self._step_ann)
        attributed = sum(self._acts.values()) + self._device_s
        other = max(0.0, wall - attributed)
        self._acts["other"] = self._acts.get("other", 0.0) + other
        self._seq += 1
        # perf_counter edges → the time.time() axis of start_s
        off = self._step_wall0 - self._step_t0
        rec = {
            "seq": self._seq,
            "start_s": self._step_wall0,
            "wall_s": wall,
            "device_s": self._device_s,
            "issue_s": self._issue_s,
            "wait_s": self._device_s - self._issue_s,
            "cpu_s": cpu,
            "blocked_s": blocked,
            "stalled_s": stalled,
            "read_seq": self._read_seq,
            "lock_wait_s": self._lock_wait_s,
            "gap_before_s": self._gap_before_s,
            "dispatches": self._dispatches,
            "chunk_rows": self._chunk_rows,
            "chunk_row_slots": self._chunk_row_slots,
            "block_rows": self._block[0],
            "block_commits": self._block[1],
            "tokens_revealed": self._block[2],
            "tokens_committed": self._block[3],
            "sampler_tier": self._sampler_tier,
            "first_tokens_program": self._first_tokens["program"],
            "first_tokens_host": self._first_tokens["host"],
            "ahead": self._ahead,
            "drain": self._drain,
            "tokens_discarded": self._discarded,
            **self._extra,
            "activities": dict(self._acts),
            "segments": [(name, t0 + off, t1 + off)
                         for name, t0, t1 in self._segments],
        }
        with self._lock:
            self._ring.append(rec)
        for name, dt in self._acts.items():
            self._host_seconds[name] = self._host_seconds.get(name, 0.0) + dt
        self._steps_total += 1
        self._step_wall_total += wall
        self._device_seconds_total += self._device_s
        self._issue_seconds_total += self._issue_s
        for state, dt in (("cpu", cpu), ("device_wait", blocked),
                          ("stalled", stalled)):
            self._thread_seconds[state] += dt
        if self._sampler_tier is not None:
            self._sampler_steps[self._sampler_tier] = (
                self._sampler_steps.get(self._sampler_tier, 0) + 1)
        self._steps_ahead += self._ahead
        if self._drain is not None:
            self._step_drains[self._drain] = (
                self._step_drains.get(self._drain, 0) + 1)
        self._tokens_discarded += self._discarded
        self._busy_roll.append((wall, self._device_s))
        self._step_t0 = None
        self._last_end = end
        self._snap = self._build_snapshot()
        if tracer is not None:
            self._emit_timeline(tracer, rec["segments"])
        return rec

    # -- scrape-side reads ----------------------------------------------------

    def _build_snapshot(self) -> dict:
        roll_wall = sum(w for w, _ in self._busy_roll)
        roll_dev = sum(d for _, d in self._busy_roll)
        busy = (roll_dev / roll_wall) if roll_wall > 0 else 0.0
        wall = self._step_wall_total
        dev = self._device_seconds_total
        other = self._host_seconds.get("other", 0.0)
        return {
            "enabled": self.enabled,
            "steps": self._steps_total,
            "step_wall_seconds_total": wall,
            "device_seconds_total": dev,
            # the dispatch windows' two parts (issue + wait = device)
            "dispatch_issue_seconds_total": self._issue_seconds_total,
            "dispatch_wait_seconds_total": dev - self._issue_seconds_total,
            # the engine thread's wall by state (cpu + device_wait +
            # stalled = step_wall_seconds_total), whatever covers it
            "thread_seconds": dict(self._thread_seconds),
            "host_seconds": dict(self._host_seconds),
            "sampler_steps": dict(self._sampler_steps),
            # one step of lookahead (serve/engine.py): steps whose
            # dispatch was issued while the previous one was unread,
            # the others by why not, and tokens of rows run past their EOS
            "steps_ahead": self._steps_ahead,
            "step_drains": dict(self._step_drains),
            "tokens_discarded": self._tokens_discarded,
            # rolling over the last `window` steps — the live dial. A
            # recorder that measured nothing (fresh, idle, or disabled)
            # reports 0 host gap, NOT 1 − busy = 1.0: "the chip waits
            # on Python 100%" must never be the default reading
            "device_busy_fraction": busy,
            "host_gap_fraction": (max(0.0, 1.0 - busy)
                                  if roll_wall > 0 else 0.0),
            # lifetime coverage: attributed activities + device over
            # wall (the ≥ 0.95 gate the serve benches assert); 0.0 with
            # no recorded steps so the gate can never pass vacuously
            "coverage": ((wall - other) / wall) if wall > 0 else 0.0,
        }

    def snapshot(self) -> dict:
        """One consistent view for ``/metrics`` callbacks and bench
        artifacts — the dict reference is swapped atomically at step
        end, so a scrape never mixes two steps' totals."""
        return self._snap

    def records(self, limit: int = 256) -> list[dict]:
        """Most recent step records, oldest first (``/debug`` reads)."""
        with self._lock:
            out = list(self._ring)
        return out[-limit:]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    # -- Perfetto dual-lane export --------------------------------------------

    _meta_sink: str | None = None

    def _emit_timeline(self, tracer, segments) -> None:
        write = getattr(tracer, "write_event", None)
        if write is None or not getattr(tracer, "has_file_sink", False):
            return
        pid = os.getpid()
        # lane metadata once PER SINK, not per recorder: a rotated
        # trace file must carry its own thread_name events or the
        # dual-lane view renders as raw synthetic tids
        sink = getattr(tracer, "file_sink_path", None) or "<sink>"
        if sink != self._meta_sink:
            self._meta_sink = sink
            for tid, label in (
                    (HOST_LANE_TID, "engine host lane"),
                    (WINDOW_LANE_TID, "dispatch window lane (host clock)")):
                write({"ph": "M", "pid": pid, "tid": tid,
                       "name": "thread_name", "args": {"name": label}})
        for name, t0, t1 in segments:
            window = name.startswith(("issue:", "wait:"))
            write({
                "ph": "X", "cat": "steptrace", "name": name,
                "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6, "pid": pid,
                "tid": WINDOW_LANE_TID if window else HOST_LANE_TID,
            })
