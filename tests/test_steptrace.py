"""Host-gap flight recorder (obs/steptrace.py) + the request
critical-path plane (ISSUE 11).

Pins:

- recorder mechanics: ring bound, scope nesting/pause semantics, device
  deduction, snapshot consistency, kill switch; the dispatch window's
  two parts (issue + wait = device), segments on the step's clock,
  TraceAnnotations on the same edges (ISSUE 27);
- live engine integration: activity sums ≈ step wall (the partition
  invariant), coverage >= 0.95 on contiguous AND paged paths, the
  /metrics families strict-parse with live values;
- per-request critical path: /debug/requests breakdown sums ≈ request
  wall, warm-vs-cold TTFT labels from the admission outcome; every
  window booked to every slot holder (prefill_stall /
  decode_interleave), overlays outside the residual, the front end's
  api_* overlays (ISSUE 27);
- the engine thread's time by state (PR 41): ``fetch()`` as a span inside
  its window named after the OLDEST unread one, ``cpu_s + blocked_s +
  stalled_s == wall_s`` on an injected clock and on the real ones, the
  four ``engine_*`` overlays of every finished request, the
  ``llm_engine_thread_seconds_total`` family;
- golden-token parity with the recorder OFF (LLM_TPU_STEPTRACE=off),
  and an overhead smoke (recorder primitives bounded + TPOT A/B);
- the kv-pool's kvpool_handoff_wire_seconds server-side cross-check;
- the Perfetto dual-lane export (host + dispatch-window lane events);
- the checked-in BENCH_HOST_GAP artifact's coverage gate.
"""

import json
import os
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from llm_in_practise_tpu.models.gpt import GPT, GPTConfig
from llm_in_practise_tpu.obs import steptrace
from llm_in_practise_tpu.obs.steptrace import (
    ACTIVITIES,
    HOST_LANE_TID,
    WINDOW_LANE_TID,
    StepTrace,
)
from llm_in_practise_tpu.serve.engine import (
    CP_OVERLAYS,
    InferenceEngine,
    Request,
    SamplingParams,
)
from tests.promparse import parse_exposition
from tests.thread_state_checks import check_records, check_requests

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=64, seq_len=192, n_layer=2, n_head=2,
                    embed_dim=32, dropout=0.0, pos_embedding="rope")
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


_ENGINES: list = []


def _engine(model, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("cache_len", 192)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("chunked_prefill", 8)
    eng = InferenceEngine(model, params, **kw)
    _ENGINES.append(eng)
    return eng


@pytest.fixture(autouse=True)
def _stop_engines():
    """An engine returns its HBM-ledger bytes only in ``stop()``; one
    left running would leak them into whatever test file shares this
    worker (``test_hbm_ledger`` reads the process-wide accounts)."""
    yield
    while _ENGINES:
        _ENGINES.pop().stop()


SHORT = ([3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8])
LONG = [(i * 7 + 3) % 64 for i in range(40)]


def _run_mixed_load(eng, max_tokens=24):
    sp = SamplingParams(greedy=True, max_tokens=max_tokens)
    h = [eng.submit(p, sp) for p in SHORT]
    eng.step()
    hl = eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
    while eng.step():
        pass
    return [r.result() for r in (*h, hl)]


# --- recorder unit behavior --------------------------------------------------


def test_ring_bound():
    st = StepTrace(capacity=16, enabled=True)
    for _ in range(50):
        st.step_begin()
        with st.scope("admit"):
            pass
        st.step_end()
    assert len(st) == 16
    assert st.snapshot()["steps"] == 50


def test_scope_nesting_pauses_outer_and_device_deducts():
    st = StepTrace(enabled=True)
    st.step_begin()
    with st.scope("admit"):
        time.sleep(0.02)
        with st.scope("index_build"):
            time.sleep(0.02)
        # a dispatch window inside admit: its wall time is device, not
        # host — the deduction keeps the partition honest
        st.window_begin("decode")
        st.window_issued()
        time.sleep(0.02)
        st.window_end()
    rec = st.step_end()
    acts = rec["activities"]
    # admit ≈ 40ms gross − 20ms device deduction; index_build ≈ 20ms;
    # generous bounds (CI timers)
    assert 0.01 < acts["index_build"] < 0.2
    assert 0.01 < acts["admit"] < 0.2
    assert acts["admit"] + acts["index_build"] < rec["wall_s"]
    assert 0.02 <= rec["device_s"] < 0.2
    # partition: activities (incl other) + device == wall
    assert (sum(acts.values()) + rec["device_s"]
            == pytest.approx(rec["wall_s"], rel=1e-6, abs=1e-6))


def test_disabled_recorder_is_inert():
    st = StepTrace(enabled=False)
    st.step_begin()
    with st.scope("admit"):
        st.window_begin("decode")
        st.window_issued()
        with st.fetch():
            pass
        st.window_end()
    assert st.step_end() is None
    assert len(st) == 0
    assert st.snapshot()["steps"] == 0
    assert st.fetch() is steptrace._NOOP_SCOPE
    assert st.thread_states() is None


def test_snapshot_has_every_activity_from_birth():
    st = StepTrace(enabled=True)
    assert set(st.snapshot()["host_seconds"]) == set(ACTIVITIES)


class _Clock:
    """A clock the test moves by hand: ``perf_counter`` and ``time``
    advance together, ``time`` from another origin; ``thread_time``
    (the thread's CPU clock) advances by ``tick``'s ``cpu`` only."""

    def __init__(self, perf: float = 100.0, wall: float = 5000.0):
        self.perf, self.offset, self.cpu = perf, wall - perf, 7.0

    def tick(self, dt: float, cpu: float = 0.0) -> None:
        self.perf += dt
        self.cpu += cpu

    def perf_counter(self) -> float:
        return self.perf

    def thread_time(self) -> float:
        return self.cpu

    def time(self) -> float:
        return self.perf + self.offset


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(steptrace, "time", c)
    return c


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: the activity
    starts at construction and ends at ``__exit__``."""

    def __init__(self):
        self.opened, self.open_now = [], []

    def __call__(self, name):
        self.opened.append(name)
        self.open_now.append(name)
        outer = self

        class _A:
            def __exit__(self, *exc):
                outer.open_now.remove(name)

        return _A()


@pytest.fixture
def annotations(monkeypatch):
    a = _Annotations()
    monkeypatch.setattr(steptrace, "TraceAnnotation", a)
    return a


def test_window_parts_sum_to_device(clock):
    """issue_s + wait_s == device_s, each where the clock put it; the
    window is deducted from the scope it sits in, so the partition of
    wall_s stands."""
    st = StepTrace(enabled=True)
    st.step_begin(lock_wait_s=0.25)
    with st.scope("dispatch_wait"):
        clock.tick(0.001)                 # scope, before the window
        st.window_begin("decode")
        clock.tick(0.004)                 # issue
        st.window_issued()
        clock.tick(0.040)                 # wait
        assert st.window_end() == pytest.approx((0.044, 0.004))
        clock.tick(0.002)                 # scope, after the window
    with st.scope("sample_commit"):
        clock.tick(0.003)
    clock.tick(0.0005)                    # unscoped: other
    rec = st.step_end()
    assert rec["issue_s"] == pytest.approx(0.004)
    assert rec["wait_s"] == pytest.approx(0.040)
    assert rec["issue_s"] + rec["wait_s"] == pytest.approx(rec["device_s"])
    assert rec["wall_s"] == pytest.approx(0.0505)
    assert rec["lock_wait_s"] == 0.25
    acts = rec["activities"]
    assert acts["dispatch_wait"] == pytest.approx(0.003)
    assert acts["sample_commit"] == pytest.approx(0.003)
    assert acts["other"] == pytest.approx(0.0005)
    assert sum(acts.values()) + rec["device_s"] == pytest.approx(rec["wall_s"])
    snap = st.snapshot()
    assert snap["dispatch_issue_seconds_total"] == pytest.approx(0.004)
    assert snap["dispatch_wait_seconds_total"] == pytest.approx(0.040)
    # a window without an issue part: the whole of it is wait
    st.step_begin()
    st.window_begin("decode")
    st.window_issued()
    clock.tick(0.01)
    st.window_end()
    rec = st.step_end()
    assert (rec["issue_s"], rec["wait_s"]) == pytest.approx((0.0, 0.01))


def test_segments_on_the_steps_clock_and_nested(clock):
    """Every segment lies inside its step on start_s's axis; an inner
    scope lies inside the outer one; a window is an issue and a wait
    segment that meet; gap_before_s is the previous step's end to this
    step's begin."""
    st = StepTrace(enabled=True)
    st.step_begin()
    clock.tick(0.01)
    st.step_end()
    clock.tick(0.5)                       # between steps
    st.step_begin()
    with st.scope("admit"):
        clock.tick(0.001)
        with st.scope("index_build"):
            clock.tick(0.002)
        clock.tick(0.001)
    with st.scope("dispatch_wait"):
        st.window_begin("prefill")
        clock.tick(0.003)
        st.window_issued()
        clock.tick(0.02)
        st.window_end()
    rec = st.step_end()
    assert rec["gap_before_s"] == pytest.approx(0.5)
    assert rec["start_s"] == pytest.approx(5000.51)
    segs = {name: (t0, t1) for name, t0, t1 in rec["segments"]}
    assert set(segs) == {"admit", "index_build", "dispatch_wait",
                         "issue:prefill", "wait:prefill"}
    lo, hi = rec["start_s"], rec["start_s"] + rec["wall_s"]
    for t0, t1 in segs.values():
        assert lo - 1e-9 <= t0 <= t1 <= hi + 1e-9
    assert segs["admit"][0] <= segs["index_build"][0]
    assert segs["index_build"][1] <= segs["admit"][1]
    assert segs["index_build"] == pytest.approx((5000.511, 5000.513))
    assert segs["issue:prefill"][1] == segs["wait:prefill"][0]
    assert segs["wait:prefill"][1] - segs["issue:prefill"][0] \
        == pytest.approx(rec["device_s"])


def test_annotations_follow_the_edges(clock, annotations):
    """Step, scopes and the window's two parts open and close
    TraceAnnotations; a disabled recorder opens none."""
    st = StepTrace(enabled=True)
    st.step_begin()
    with st.scope("plan"):
        pass
    with st.scope("dispatch_wait"):
        st.window_begin("decode")
        assert annotations.open_now[-1] == "engine:issue:decode"
        st.window_issued()
        assert annotations.open_now[-1] == "engine:wait:decode"
        st.window_end()
    st.step_end()
    assert annotations.opened == [
        "engine_step", "engine:plan", "engine:dispatch_wait",
        "engine:issue:decode", "engine:wait:decode"]
    assert annotations.open_now == []
    # an aborted (idle) step closes its annotation too
    st.step_begin()
    st.step_abort()
    assert annotations.open_now == []
    n = len(annotations.opened)
    off = StepTrace(enabled=False)
    off.step_begin()
    with off.scope("plan"):
        off.window_begin("decode")
        off.window_issued()
        dt, issue = off.window_end()      # still timed for the engine
    off.step_end()
    assert len(annotations.opened) == n and dt >= issue >= 0.0


def test_two_programs_in_flight_windows_tile(clock):
    """The engine issues step n+1 before it reads step n (PR 40): a
    window opens before the one before it has closed. The lane tiles
    (issue:<new> while a dispatch is prepared, else wait:<oldest>), a
    program left unread covers the next step from its first instant,
    issue + wait = device <= wall, the scopes count only what no unread
    program covered, and a window comes back from the later of its begin
    and the previous window's end."""
    st = StepTrace(enabled=True)
    # step 1: nothing in flight; program A is issued and left unread
    st.step_begin()
    with st.scope("admit"):
        clock.tick(0.002)
    with st.scope("dispatch_wait"):
        st.window_begin("decode")             # A
        clock.tick(0.003)
        st.window_issued()
        clock.tick(0.001)
    st.note_drain("idle")
    r1 = st.step_end()
    assert (r1["ahead"], r1["drain"]) == (False, "idle")
    assert r1["issue_s"] == pytest.approx(0.003)
    assert r1["wait_s"] == pytest.approx(0.001)   # clipped to the step
    assert r1["device_s"] == pytest.approx(0.004)
    assert r1["device_s"] <= r1["wall_s"]
    assert r1["activities"]["admit"] == pytest.approx(0.002)
    assert r1["activities"]["dispatch_wait"] == pytest.approx(0.0)
    clock.tick(0.0005)                        # between steps: no record's
    # step 2: A unread from the first instant; B issued, then A read
    st.step_begin()
    with st.scope("admit"):
        clock.tick(0.002)                     # hidden by A
    with st.scope("dispatch_wait"):
        st.window_begin("mixed")              # B
        st.note_ahead()
        clock.tick(0.004)
        st.window_issued()
    with st.scope("dispatch_wait"):
        clock.tick(0.010)                     # the fetch of A
        dt_a, issue_a = st.window_end()
    with st.scope("sample_commit"):
        clock.tick(0.003)                     # hidden by B
    st.note_drain("idle")                     # ahead: no reason is kept
    st.note_discarded(2)
    r2 = st.step_end()
    assert (r2["ahead"], r2["drain"]) == (True, None)
    assert r2["tokens_discarded"] == 2 and r2["dispatches"] == 1
    # A's window: begin 0.002 into step 1 .. its read, 0.016 into step 2
    assert dt_a == pytest.approx(0.004 + 0.0005 + 0.016)
    assert issue_a == pytest.approx(0.003)
    assert r2["device_s"] == pytest.approx(r2["wall_s"])
    assert r2["wall_s"] == pytest.approx(0.019)
    assert r2["issue_s"] == pytest.approx(0.004)
    assert r2["issue_s"] + r2["wait_s"] == pytest.approx(r2["device_s"])
    assert sum(r2["activities"].values()) == pytest.approx(0.0)
    lane = [(n, t1 - t0) for n, t0, t1 in r2["segments"]
            if n.startswith(("issue:", "wait:"))]
    assert [n for n, _ in lane] == ["wait:decode", "issue:mixed",
                                    "wait:decode", "wait:mixed"]
    assert [d for _, d in lane] == pytest.approx(
        [0.002, 0.004, 0.010, 0.003])
    spans = sorted((t0, t1) for n, t0, t1 in r2["segments"]
                   if n.startswith(("issue:", "wait:")))
    assert all(b[0] == pytest.approx(a[1]) for a, b in zip(spans, spans[1:]))
    # step 3: nothing to issue; B is read: booked from A's read on, its
    # issue part (all before that) is no part of it
    st.step_begin()
    with st.scope("dispatch_wait"):
        clock.tick(0.006)
        dt_b, issue_b = st.window_end()
    with st.scope("sample_commit"):
        clock.tick(0.002)                     # nothing in flight: host
    st.note_drain("idle")
    r3 = st.step_end()
    assert dt_b == pytest.approx(0.003 + 0.006) and issue_b == 0.0
    assert r3["device_s"] == pytest.approx(0.006)
    assert r3["activities"]["sample_commit"] == pytest.approx(0.002)
    assert r3["dispatches"] == 0 and r3["drain"] == "idle"
    snap = st.snapshot()
    assert snap["steps_ahead"] == 1
    assert snap["step_drains"] == {"idle": 2}
    assert snap["tokens_discarded"] == 2
    assert snap["device_seconds_total"] == pytest.approx(0.029)
    assert snap["device_seconds_total"] <= snap["step_wall_seconds_total"]


def test_in_flight_annotations_follow_the_lane(clock, annotations):
    st = StepTrace(enabled=True)
    st.step_begin()
    st.window_begin("decode")
    st.window_issued()
    st.step_end()
    assert annotations.open_now == []         # the lane ends with the step
    st.step_begin()
    assert annotations.open_now[-1] == "engine:wait:decode"
    st.window_begin("decode")
    assert annotations.open_now[-1] == "engine:issue:decode"
    st.window_issued()
    st.window_end()
    assert annotations.open_now[-1] == "engine:wait:decode"
    st.window_end()
    assert annotations.open_now == ["engine_step"]
    st.step_end()
    assert annotations.open_now == []


def test_fetch_is_a_span_inside_its_window(clock, annotations):
    """One window: the fetch is a segment inside the window's wait: lane
    segment and an annotation, its wall is blocked_s <= wait_s <=
    device_s, and the step read the program it issued itself."""
    st = StepTrace(enabled=True)
    st.step_begin()
    with st.scope("dispatch_wait"):
        st.window_begin("prefill")
        clock.tick(0.004, cpu=0.004)          # issue
        st.window_issued()
        clock.tick(0.002, cpu=0.002)          # host work under the program
        with st.fetch():
            assert annotations.open_now[-1] == "engine:fetch:prefill"
            clock.tick(0.030)                 # blocked
        assert "engine:fetch:prefill" not in annotations.open_now
        clock.tick(0.001, cpu=0.001)
        st.window_end()
    rec = st.step_end()
    assert rec["blocked_s"] == pytest.approx(0.030)
    assert rec["blocked_s"] <= rec["wait_s"] <= rec["device_s"]
    assert rec["wait_s"] == pytest.approx(0.033)
    assert (rec["cpu_s"], rec["stalled_s"]) == pytest.approx((0.007, 0.0))
    assert rec["read_seq"] == rec["seq"] == 1
    segs = {name: (t0, t1) for name, t0, t1 in rec["segments"]}
    assert segs["fetch:prefill"] == pytest.approx((5000.006, 5000.036))
    assert segs["wait:prefill"][0] <= segs["fetch:prefill"][0]
    assert segs["fetch:prefill"][1] <= segs["wait:prefill"][1]
    check_records([rec])
    # a step that reads nothing names no step and blocks nowhere
    st.step_begin()
    clock.tick(0.001, cpu=0.001)
    rec = st.step_end()
    assert rec["read_seq"] is None and rec["blocked_s"] == 0.0


def test_fetch_names_the_oldest_unread_window(clock, annotations):
    """Two windows open: the segment, the annotation and read_seq are the
    OLDEST window's (the program being read, one behind the one issued
    last), and the fetch lies inside that window's wait: segment."""
    st = StepTrace(enabled=True)
    st.step_begin()
    st.window_begin("decode")                 # A, left unread
    clock.tick(0.003, cpu=0.003)
    st.window_issued()
    r1 = st.step_end()
    st.step_begin()
    st.window_begin("mixed")                  # B, issued under A
    clock.tick(0.004, cpu=0.004)
    st.window_issued()
    with st.fetch():                          # reads A
        assert annotations.open_now[-1] == "engine:fetch:decode"
        clock.tick(0.010)
    st.window_end()
    clock.tick(0.002, cpu=0.002)              # commit of A, under B
    r2 = st.step_end()
    assert (r1["read_seq"], r2["read_seq"]) == (None, r1["seq"])
    names = [n for n, _, _ in r2["segments"]]
    assert "fetch:decode" in names and "fetch:mixed" not in names
    assert r2["blocked_s"] == pytest.approx(0.010)
    assert r2["cpu_s"] == pytest.approx(0.006)
    check_records([r1, r2])
    st.step_begin()
    with st.fetch():                          # reads B, issued a step ago
        clock.tick(0.005)
    st.window_end()
    r3 = st.step_end()
    assert r3["read_seq"] == r2["seq"]
    assert [n for n, _, _ in r3["segments"] if n.startswith("fetch:")] \
        == ["fetch:mixed"]
    check_records([r1, r2, r3])
    assert annotations.open_now == []


@pytest.mark.parametrize("where,state", [
    ("scope", "stalled_s"), ("fetch", "blocked_s"), ("busy", "cpu_s"),
    ("nowhere", None)])
def test_thread_states_partition_the_wall(clock, where, state):
    """10 ms asleep in a scope land in stalled_s, inside fetch() in
    blocked_s, of a busy loop in cpu_s; the CPU a fetch itself burns is
    no part of cpu_s; the three sum to wall_s, in the record and in the
    running totals, whatever a running program covers."""
    st = StepTrace(enabled=True)
    st.step_begin()
    st.window_begin("decode")
    st.window_issued()
    with st.scope("sample_commit"):           # covered: activities read 0
        clock.tick(0.002, cpu=0.002)
        if where == "scope":
            clock.tick(0.010)                 # time.sleep: wall, no CPU
        elif where == "busy":
            clock.tick(0.010, cpu=0.010)
        mid = st.thread_states()
    with st.fetch():
        clock.tick(0.003, cpu=0.0005)
        if where == "fetch":
            clock.tick(0.010)
    st.window_end()
    rec = st.step_end()
    want = {"cpu_s": 0.002, "blocked_s": 0.003, "stalled_s": 0.0}
    if state is not None:
        want[state] += 0.010
    for key, value in want.items():
        assert rec[key] == pytest.approx(value, abs=1e-12), key
    assert sum(want.values()) == pytest.approx(rec["wall_s"])
    assert rec["activities"]["sample_commit"] == pytest.approx(0.0)
    # the step so far, as the engine books it to a request that finishes
    stalled = 0.010 if where == "scope" else 0.0
    assert mid == pytest.approx(
        (want["cpu_s"] + stalled, want["cpu_s"], 0.0, stalled), abs=1e-12)
    snap = st.snapshot()
    assert snap["thread_seconds"] == pytest.approx(
        {"cpu": want["cpu_s"], "device_wait": want["blocked_s"],
         "stalled": want["stalled_s"]})
    assert sum(snap["thread_seconds"].values()) == pytest.approx(
        snap["step_wall_seconds_total"])
    check_records([rec])


def test_a_coarse_cpu_clock_keeps_the_partition_and_the_sum(clock):
    """A thread CPU clock that ticks by 10 ms (a TPU v5e host's) under
    steps of 9 ms: 4 of work, 2 asleep, 3 blocked. A tick that does not
    fit a step's wall outside its fetch is owed to the next step, never
    dropped: every record still partitions, and the window's sum is the
    work, less what is still owed (under one tick)."""
    st = StepTrace(enabled=True)
    recs, work = [], 0.0
    for _ in range(10):
        st.step_begin()
        st.window_begin("decode")
        st.window_issued()
        ticks = int((work + 0.004) / 0.010 + 1e-9) - int(work / 0.010 + 1e-9)
        work += 0.004
        clock.tick(0.004, cpu=0.010 * ticks)
        clock.tick(0.002)
        with st.fetch():
            clock.tick(0.003)
        st.window_end()
        recs.append(st.step_end())
    check_records(recs)
    assert {round(r["cpu_s"], 6) for r in recs} == {0.0, 0.004, 0.006}
    assert 0.0 <= st._cpu_owed_s < 0.010
    assert sum(r["cpu_s"] for r in recs) + st._cpu_owed_s \
        == pytest.approx(0.040)
    assert sum(r["stalled_s"] for r in recs) - st._cpu_owed_s \
        == pytest.approx(0.020)
    assert sum(r["blocked_s"] for r in recs) == pytest.approx(0.030)


def test_thread_states_on_the_real_clocks():
    """time.sleep in a scope is a stall, inside fetch() a wait for the
    device, a busy loop CPU (generous bounds: the workers share the
    machine)."""
    st = StepTrace(enabled=True)
    st.step_begin()
    st.window_begin("decode")
    st.window_issued()
    with st.scope("sample_commit"):
        time.sleep(0.03)
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.03:
            pass
    with st.fetch():
        time.sleep(0.03)
    st.window_end()
    rec = st.step_end()
    assert 0.025 <= rec["blocked_s"] < 1.0
    assert 0.025 <= rec["cpu_s"] < 1.0
    assert 0.02 <= rec["stalled_s"]
    check_records([rec])


def test_lookahead_records_and_request_windows_live(model_params):
    """A paged engine under mixed load: steps run ahead, every record
    says ``ahead`` or why not and keeps issue + wait = device <= wall, a
    request's windows tile (host_gap >= 0, segments + residual = wall),
    and the three families strict-parse on /metrics."""
    from llm_in_practise_tpu.serve.api import OpenAIServer
    from llm_in_practise_tpu.serve.engine import DRAIN_REASONS

    model, params = model_params
    eng = _engine(model, params, kv_layout="paged")
    handles = _run_mixed_load(eng)
    recs = eng.steptrace.records()
    assert sum(r["ahead"] for r in recs) >= len(recs) // 2
    for r in recs:
        assert r["ahead"] != (r["drain"] is not None)
        assert r["issue_s"] + r["wait_s"] == pytest.approx(r["device_s"])
        assert r["device_s"] <= r["wall_s"] + 1e-9
        assert sum(r["activities"].values()) + r["device_s"] == \
            pytest.approx(r["wall_s"], abs=1e-6)
    for req in eng.finished:
        wall = req.finish_time - req.submit_time
        parts = sum(v for k, v in req.cp.items() if k not in CP_OVERLAYS)
        assert req.cp["host_gap"] >= 0.0
        assert parts == pytest.approx(wall, abs=1e-6)
    assert handles

    class _Tok:
        def encode(self, t):
            return [b % 64 for b in t.encode()][:32]

        def decode(self, ids):
            return " ".join(map(str, ids))

    fams = parse_exposition(
        OpenAIServer(eng, _Tok(), model_name="ahead").metrics_text())
    snap = eng.steptrace.snapshot()
    assert next(iter(fams["llm_steps_ahead_total"].samples.values())) \
        == snap["steps_ahead"] > 0
    drains = {dict(k[1])["reason"]: v
              for k, v in fams["llm_step_drains_total"].samples.items()}
    assert set(drains) == set(DRAIN_REASONS)
    assert drains["oneshot_prefill"] >= 1
    assert snap["steps_ahead"] + sum(drains.values()) == snap["steps"]
    assert next(iter(
        fams["llm_tokens_discarded_total"].samples.values())) == 0


# --- live engine integration -------------------------------------------------


@pytest.mark.parametrize("kv_layout", ["contiguous", "paged"])
def test_activity_sums_match_step_wall(model_params, kv_layout):
    """Every recorded step is a PARTITION: activities + device == wall,
    and attributed coverage clears the 95 % gate on a live engine."""
    model, params = model_params
    eng = _engine(model, params, kv_layout=kv_layout)
    _run_mixed_load(eng)
    recs = eng.steptrace.records()
    assert recs, "engine steps must record"
    for rec in recs:
        total = sum(rec["activities"].values()) + rec["device_s"]
        assert total == pytest.approx(rec["wall_s"], rel=1e-6, abs=1e-6)
        # the windows' two parts, and every segment inside its step
        assert rec["issue_s"] + rec["wait_s"] == pytest.approx(
            rec["device_s"], abs=1e-9)
        assert rec["issue_s"] >= 0 and rec["wait_s"] >= 0
        assert rec["lock_wait_s"] >= 0 and rec["gap_before_s"] >= 0
        lo, hi = rec["start_s"], rec["start_s"] + rec["wall_s"]
        for _, t0, t1 in rec["segments"]:
            assert lo - 1e-6 <= t0 <= t1 <= hi + 1e-6
        if rec["dispatches"]:
            parts = {name.split(":")[0] for name, _, _ in rec["segments"]}
            assert {"issue", "wait"} <= parts
    # by thread state too, and per request (the contiguous engine reads
    # every program in the step that issued it)
    check_records(recs)
    check_requests(eng.finished)
    if kv_layout == "contiguous":
        assert all(r["read_seq"] in (None, r["seq"]) for r in recs)
    snap = eng.steptrace.snapshot()
    assert sum(snap["thread_seconds"].values()) == pytest.approx(
        snap["step_wall_seconds_total"])
    assert snap["dispatch_issue_seconds_total"] \
        + snap["dispatch_wait_seconds_total"] \
        == pytest.approx(snap["device_seconds_total"])
    assert snap["coverage"] >= 0.95
    assert 0.0 <= snap["host_gap_fraction"] <= 1.0
    assert snap["device_busy_fraction"] + snap["host_gap_fraction"] \
        == pytest.approx(1.0)
    # the load exercised the core activities
    hs = snap["host_seconds"]
    for must in ("admit", "dispatch_wait", "sample_commit", "plan"):
        assert hs[must] > 0.0, f"activity {must} never recorded"


def test_spec_round_records_draft_propose(model_params):
    model, params = model_params
    eng = _engine(model, params, speculative_k=3, chunked_prefill=None)
    sp = SamplingParams(greedy=True, max_tokens=24)
    req = eng.submit([5, 9, 2, 6, 5, 9, 2, 6, 5, 9, 2, 6], sp)
    while eng.step():
        pass
    req.result()
    assert eng.spec_rounds > 0
    assert eng.steptrace.snapshot()["host_seconds"]["draft_propose"] > 0


def test_metrics_families_strict_parse_live(model_params):
    """The new families render live values through the strict
    exposition parser on the model server."""
    from llm_in_practise_tpu.serve.api import OpenAIServer

    model, params = model_params
    eng = _engine(model, params)
    _run_mixed_load(eng)

    class _Tok:
        def encode(self, t):
            return [b % 64 for b in t.encode()][:32]

        def decode(self, ids):
            return " ".join(map(str, ids))

    srv = OpenAIServer(eng, _Tok(), model_name="steptrace-test")
    fams = parse_exposition(srv.metrics_text())
    gap = fams["llm_host_gap_seconds_total"]
    acts = {dict(k[1])["activity"] for k in gap.samples}
    assert acts == set(ACTIVITIES)
    assert sum(gap.samples.values()) > 0
    wall = fams["llm_step_wall_seconds_total"]
    assert next(iter(wall.samples.values())) > 0
    steps = fams["llm_engine_steps_total"]
    assert next(iter(steps.samples.values())) > 0
    issue = next(iter(
        fams["llm_dispatch_issue_seconds_total"].samples.values()))
    wait = next(iter(
        fams["llm_dispatch_wait_seconds_total"].samples.values()))
    assert issue > 0 and wait > 0
    assert issue + wait == pytest.approx(
        eng.steptrace.snapshot()["device_seconds_total"])
    states = {dict(k[1])["state"]: v for k, v in
              fams["llm_engine_thread_seconds_total"].samples.items()}
    assert set(states) == {"cpu", "device_wait", "stalled"}
    assert states["cpu"] > 0 and states["device_wait"] > 0
    assert sum(states.values()) == pytest.approx(
        next(iter(wall.samples.values())))
    frac = fams["llm_host_gap_fraction"]
    busy = fams["llm_device_busy_fraction"]
    fv = next(iter(frac.samples.values()))
    bv = next(iter(busy.samples.values()))
    assert 0.0 <= fv <= 1.0 and 0.0 <= bv <= 1.0
    assert fv + bv == pytest.approx(1.0)
    cp = fams["llm_request_critical_path_seconds_total"]
    segs = {dict(k[1])["segment"]: v for k, v in cp.samples.items()}
    assert segs["decode_dispatch"] > 0
    assert segs["prefill_dispatch"] > 0
    # ttft cache labels: this load is all cold prompts (first time) —
    # at least the cold child must carry the observations
    ttft = fams["llm_ttft_seconds"]
    cold_count = ttft.samples[
        ("llm_ttft_seconds_count", frozenset({("cache", "cold")}.union()))]
    assert cold_count >= 1


def test_ttft_cache_labels_hit_and_cold(model_params):
    model, params = model_params
    eng = _engine(model, params, prefix_cache=True,
                  chunked_prefill=None)
    sp = SamplingParams(greedy=True, max_tokens=4)
    prompt = [7] * 24
    r1 = eng.submit(prompt, sp)
    while eng.step():
        pass
    r1.result()
    r2 = eng.submit(prompt, sp)
    while eng.step():
        pass
    r2.result()
    assert r1.cache_outcome == "cold"
    assert r2.cache_outcome == "hit"
    stats = eng.stats
    assert stats.ttft_by_cache["cold"].count >= 1
    assert stats.ttft_by_cache["hit"].count >= 1


class _ByteTok:
    def encode(self, t):
        return [b % 64 for b in t.encode()][:32]

    def decode(self, ids):
        return " ".join(map(str, ids))


def _chat_then_debug_requests(eng, *, stream: bool) -> dict:
    """One chat completion over HTTP, then GET /debug/requests."""
    from llm_in_practise_tpu.serve.api import OpenAIServer

    srv = OpenAIServer(eng, _ByteTok(), model_name="steptrace-test")
    port = srv.serve(host="127.0.0.1", port=0, background=True)
    try:
        body = json.dumps({
            "model": "steptrace-test",
            "messages": [{"role": "user", "content": "hello host gap"}],
            "max_tokens": 12, "temperature": 0.0, "stream": stream,
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/chat/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            resp.read()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/requests",
                timeout=30) as resp:
            return json.loads(resp.read().decode())
    finally:
        srv.shutdown()


def test_debug_requests_breakdown_sums_to_wall(model_params):
    """HTTP GET /debug/requests: every finished request's engine
    segments (incl. the derived host_gap residual) partition its wall
    clock; the overlays are excluded (concurrent with, inside, or
    before the partitioned time)."""
    model, params = model_params
    payload = _chat_then_debug_requests(_engine(model, params), stream=True)
    assert payload["capacity"] == 128
    assert payload["finished"], "the finished ring must hold the request"
    for rec in payload["finished"]:
        segs = rec["segments"]
        engine_sum = sum(v for k, v in segs.items()
                        if k not in CP_OVERLAYS)
        assert engine_sum == pytest.approx(rec["wall_s"], abs=2e-3)
        assert all(v >= 0 for v in segs.values())
        assert rec["cache"] in ("hit", "partial", "cold")
    # the streamed request carries the API-side tail
    assert any("stream_flush" in r["segments"]
               for r in payload["finished"])
    agg = payload["critical_path_seconds_total"]
    assert agg["decode_dispatch"] > 0
    assert agg["stream_flush"] >= 0
    # the engine thread's time by state: per request and in the aggregate
    for rec in payload["finished"]:
        segs = rec["segments"]
        assert segs["engine_cpu"] + segs["engine_blocked"] \
            + segs["engine_stalled"] == pytest.approx(segs["engine_wall"])
    assert agg["engine_wall"] > 0
    assert agg["engine_cpu"] + agg["engine_blocked"] + agg["engine_stalled"] \
        == pytest.approx(agg["engine_wall"])


@pytest.mark.parametrize("stream", [True, False])
def test_http_request_carries_api_overlays(model_params, stream):
    """The handler's two instants become overlays at the finish funnel:
    a streamed request has both, a non-stream one api_pre_submit only;
    the dispatch_issue overlay is within the windows it is part of."""
    model, params = model_params
    payload = _chat_then_debug_requests(_engine(model, params),
                                        stream=stream)
    (rec,) = payload["finished"]
    segs = rec["segments"]
    assert segs["api_pre_submit"] > 0
    assert ("api_first_flush" in segs) == stream
    if stream:
        assert segs["api_first_flush"] >= 0
    windows = sum(segs.get(k, 0.0) for k in (
        "prefill_dispatch", "decode_dispatch", "prefill_stall",
        "decode_interleave"))
    assert 0 < segs["dispatch_issue"] <= windows + 1e-6
    assert set(segs) <= set(payload["segments"])


@pytest.mark.parametrize("mixed_step", [True, False],
                         ids=["fused", "no-mixed-step"])
def test_every_window_booked_to_every_slot_holder(model_params, mixed_step):
    """One long prompt admitted while two requests decode: the decoding
    requests book the windows that advanced its prompt as prefill_stall
    (the fused mixed step included: not decode_dispatch), the prefilling
    one books plain decode windows between its chunks as
    decode_interleave, and host_gap is what is left: segments + residual
    = wall for all three."""
    model, params = model_params
    eng = _engine(model, params, mixed_step=mixed_step)
    sp = SamplingParams(greedy=True, max_tokens=24)
    short = [eng.submit(p, sp) for p in SHORT]
    eng.step()
    long = eng.submit(LONG, SamplingParams(greedy=True, max_tokens=8))
    while eng.step():
        pass
    for r in (*short, long):
        r.result()
        wall = r.finish_time - r.submit_time
        parts = sum(v for k, v in r.cp.items() if k not in CP_OVERLAYS)
        assert parts == pytest.approx(wall, abs=1e-6)
        assert r.cp["dispatch_issue"] > 0
    for r in short:
        # five chunks of the long prompt, each a window they sat through
        assert r.cp["prefill_stall"] > 0
        assert r.cp["decode_dispatch"] > 0
        assert "decode_interleave" not in r.cp
    assert long.cp["prefill_dispatch"] > 0
    assert long.cp.get("decode_interleave", 0.0) >= 0
    if not mixed_step:
        # sequential: a decode window follows every chunk window
        assert long.cp["decode_interleave"] > 0
    assert long.cp["decode_dispatch"] > 0
    if mixed_step:
        assert eng.mixed_blocks > 0
    # the one-shot prefill of the two short prompts was ONE window both
    # waited for: own prompt, so prefill_dispatch for both
    assert all(r.cp["prefill_dispatch"] > 0 for r in short)


def test_overlays_never_enter_the_residual(model_params):
    """host_gap = wall − Σ non-overlay segments, whatever the overlays
    hold; the api_* overlays come from the handler's instants."""
    model, params = model_params
    eng = _engine(model, params)
    req = Request(uid=10_000, prompt_ids=[1, 2, 3],
                  params=SamplingParams(), submit_time=100.0)
    req.first_token_time, req.finish_time = 100.5, 102.0
    req.api_body_time, req.api_first_flush_time = 99.75, 100.625
    req.cp.update(queue_wait=0.25, prefill_dispatch=0.25,
                  decode_dispatch=1.0, prefill_stall=0.125,
                  stream_flush=50.0, dispatch_issue=60.0,
                  engine_wall=70.0, engine_cpu=40.0, engine_blocked=20.0,
                  engine_stalled=10.0)
    eng._record_finished(req)
    assert req.cp["host_gap"] == pytest.approx(0.375)
    assert req.cp["api_pre_submit"] == pytest.approx(0.25)
    assert req.cp["api_first_flush"] == pytest.approx(0.125)
    assert CP_OVERLAYS == {"stream_flush", "dispatch_issue",
                           "api_pre_submit", "api_first_flush",
                           "engine_wall", "engine_cpu", "engine_blocked",
                           "engine_stalled"}


def test_recorder_off_golden_parity(model_params, monkeypatch,
                                    annotations):
    """LLM_TPU_STEPTRACE=off: zero records, no annotation, identical
    greedy tokens — and the requests' critical paths are still booked
    (the windows are timed either way)."""
    model, params = model_params
    on = _engine(model, params)
    out_on = _run_mixed_load(on)
    live = set(annotations.opened)
    assert {"engine_step", "engine:admit", "engine:sample_commit",
            "engine:issue:prefill", "engine:wait:prefill",
            "engine:issue:decode", "engine:wait:decode"} <= live
    assert annotations.open_now == []
    n = len(annotations.opened)
    monkeypatch.setenv("LLM_TPU_STEPTRACE", "off")
    off = _engine(model, params)
    out_off = _run_mixed_load(off)
    assert not off.steptrace.enabled
    assert len(off.steptrace) == 0
    assert off.steptrace.snapshot()["steps"] == 0
    assert len(annotations.opened) == n
    assert out_on == out_off
    assert off.steptrace.fetch() is steptrace._NOOP_SCOPE
    for r in off.finished:
        assert r.cp["decode_dispatch"] > 0 and r.cp["dispatch_issue"] > 0
        assert not any(k.startswith("engine_") for k in r.cp)
    check_requests(on.finished)
    assert {"engine:fetch:prefill", "engine:fetch:decode",
            "engine:fetch:mixed"} <= live


def test_recorder_overhead_bounded(model_params, monkeypatch):
    """Overhead smoke. (a) The primitives themselves are cheap: a full
    scope enter/exit around a whole window (begin, issued, a fetch, end)
    costs < 50 µs on average. (b) An
    on-vs-off engine A/B stays within a loose TPOT factor (best of two
    runs per config — CI timing is noisy; the deterministic guard is
    (a), this is the end-to-end sanity)."""
    st = StepTrace(enabled=True)
    st.step_begin()
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with st.scope("admit"):
            st.window_begin("decode")
            st.window_issued()
            with st.fetch():
                pass
            st.window_end()
    per = (time.perf_counter() - t0) / n
    st.step_end()
    assert per < 50e-6, f"recorder primitives cost {per * 1e6:.1f} µs"

    model, params = model_params

    def tpot(eng):
        sp = SamplingParams(greedy=True, max_tokens=40)
        req = eng.submit([3, 1, 4, 1, 5, 9], sp)
        while eng.step():
            pass
        req.result()
        return req.tpot_s

    def best(make):
        vals = []
        for _ in range(2):
            eng = make()
            tpot(eng)          # warm the compile caches
            vals.append(tpot(eng))
        return min(vals)

    t_on = best(lambda: _engine(model, params, chunked_prefill=None))
    monkeypatch.setenv("LLM_TPU_STEPTRACE", "off")
    t_off = best(lambda: _engine(model, params, chunked_prefill=None))
    assert t_on < t_off * 3 + 5e-3, (
        f"recorder-on TPOT {t_on * 1e3:.2f} ms vs off "
        f"{t_off * 1e3:.2f} ms")


# --- kv-pool wire histogram --------------------------------------------------


def test_kvpool_handoff_wire_seconds():
    import numpy as np

    from llm_in_practise_tpu.serve.kv_pool import (
        HostEntry,
        KVPoolServer,
        RemoteKVClient,
    )

    server = KVPoolServer(port=0, handoff_ttl_s=30.0).start()
    try:
        client = RemoteKVClient(server.address, namespace="ns")
        entry = HostEntry(
            length=8, bucket=8,
            rows=[{"k": np.zeros((1, 8, 2, 4), np.float32),
                   "v": np.zeros((1, 8, 2, 4), np.float32)}],
            last_logits=np.zeros((1, 64), np.float32))
        client.handoff_put("hg-1", entry)
        got = client.handoff_claim("hg-1")
        assert got is not None
        # the sidecar books an op AFTER its reply is sent, so the
        # client can be back before ``hclaim`` is counted: wait for it
        deadline = time.monotonic() + 10.0
        while True:
            fams = parse_exposition(server.metrics_text())
            wire = fams["kvpool_handoff_wire_seconds"]
            counts = {dict(k[1])["op"]: v for k, v in wire.samples.items()
                      if k[0] == "kvpool_handoff_wire_seconds_count"}
            if counts["hclaim"] >= 1 or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert counts["hput"] >= 1
        assert counts["hclaim"] >= 1
        sums = {dict(k[1])["op"]: v for k, v in wire.samples.items()
                if k[0] == "kvpool_handoff_wire_seconds_sum"}
        assert sums["hput"] > 0
    finally:
        server.stop()


# --- Perfetto dual-lane export ----------------------------------------------


def test_perfetto_dual_lane(model_params, tmp_path):
    from llm_in_practise_tpu.obs.trace import Tracer

    model, params = model_params
    path = tmp_path / "steptrace.jsonl"
    tracer = Tracer(trace_file=str(path))
    eng = _engine(model, params, tracer=tracer)
    _run_mixed_load(eng)
    tracer.set_trace_file(None)
    lanes = {HOST_LANE_TID: [], WINDOW_LANE_TID: []}
    meta = set()
    with open(path, encoding="utf-8") as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("ph") == "M":
                meta.add(ev["args"]["name"])
            if ev.get("cat") != "steptrace" or ev.get("ph") != "X":
                continue
            lanes[ev["tid"]].append(ev)
    assert {"engine host lane",
            "dispatch window lane (host clock)"} <= meta
    assert not any("device" in m for m in meta)
    host = {ev["name"] for ev in lanes[HOST_LANE_TID]}
    assert "admit" in host and "dispatch_wait" in host
    assert not any(n.startswith(("issue:", "wait:")) for n in host)
    # each window is an issue slice and the wait slice that follows it
    windows = sorted(lanes[WINDOW_LANE_TID], key=lambda ev: ev["ts"])
    assert {ev["name"] for ev in windows} >= {
        "issue:prefill", "wait:prefill", "issue:decode", "wait:decode"}
    assert len(windows) % 2 == 0
    for a, b in zip(windows[::2], windows[1::2]):
        assert a["name"].startswith("issue:")
        assert b["name"] == "wait:" + a["name"][len("issue:"):]
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1.0)
    # the same segments the records carry
    recs = eng.steptrace.records()
    assert sum(len(r["segments"]) for r in recs) \
        == len(windows) + len(lanes[HOST_LANE_TID])


# --- bench artifact + smoke --------------------------------------------------


def test_bench_host_gap_artifact_coverage():
    """The checked-in BENCH_HOST_GAP artifact meets the acceptance
    gate: per-activity totals present, coverage >= 0.95 on every engine
    path, live /metrics fraction captured, both Perfetto lanes seen."""
    path = os.path.join(REPO, "BENCH_HOST_GAP_r09.json")
    with open(path) as f:
        artifact = json.load(f)
    legs = {leg["leg"] for leg in artifact["legs"]}
    assert {"contiguous", "paged", "paged_spec"} <= legs
    for leg in artifact["legs"]:
        block = leg["host_gap"]
        assert block["coverage"] >= 0.95, leg["leg"]
        assert block["coverage_ok"] is True
        assert set(block["host_seconds"]) == set(ACTIVITIES)
        assert 0.0 <= leg["live_host_gap_fraction"] <= 1.0
        assert leg["perfetto"]["host_events"] > 0
        assert leg["perfetto"]["window_events"] > 0
    spec_leg = next(leg for leg in artifact["legs"]
                    if leg["leg"] == "paged_spec")
    assert spec_leg["spec_rounds"] > 0


@pytest.mark.slow
def test_host_gap_bench_smoke(tmp_path):
    """End-to-end smoke of the bench harness itself (tiny counts)."""
    from tools.host_gap_bench import main

    artifact = main(quick=True, out=str(tmp_path / "hg.json"),
                    workdir=str(tmp_path))
    assert len(artifact["legs"]) == 3


# --- host_gap_report CLI -----------------------------------------------------


def test_host_gap_report_parses_live_scrape(model_params):
    from llm_in_practise_tpu.serve.api import OpenAIServer
    from tools.host_gap_report import format_table, host_gap_from_metrics

    model, params = model_params
    eng = _engine(model, params)
    _run_mixed_load(eng)

    class _Tok:
        def encode(self, t):
            return [b % 64 for b in t.encode()][:32]

        def decode(self, ids):
            return ""

    srv = OpenAIServer(eng, _Tok(), model_name="report-test")
    block = host_gap_from_metrics(srv.metrics_text())
    assert block is not None
    assert block["coverage"] >= 0.95
    assert set(block["host_seconds"]) == set(ACTIVITIES)
    table = format_table(block)
    assert "dispatch_wait" in table and "device (busy)" in table
    # absent families → None (old server / recorder off)
    assert host_gap_from_metrics("llm_requests_total 3\n") is None
