"""What every step record and every finished request of a live engine
must hold of the engine thread's time by state (PR 41): shared by
``test_steptrace``, ``test_engine_lookahead`` and ``test_sdar_block``."""

import pytest

from llm_in_practise_tpu.serve.engine import CP_OVERLAYS, CP_THREAD_STATES

# the overlays before the thread states joined them: host_gap's rule
PARENT_OVERLAYS = {"stream_flush", "dispatch_issue", "api_pre_submit",
                   "api_first_flush"}


def spy_window_closes(eng) -> list[int]:
    """Wrap ``eng._window_close``; the list returned gains, a call, the
    ``seq`` of the step record open at that instant."""
    closed: list[int] = []
    inner = eng._window_close

    def spy(*args, **kw):
        closed.append(eng.steptrace._seq + 1)
        return inner(*args, **kw)

    eng._window_close = spy
    return closed


def check_records(records, closed=None) -> None:
    """``cpu_s + blocked_s + stalled_s == wall_s``, each >= 0,
    ``blocked_s <= wait_s``; a fetch lies inside a ``wait:`` lane segment
    of its own phase; ``read_seq`` names a step that issued that phase;
    and (``closed``: :func:`spy_window_closes`' list) every window closed
    holds one ``fetch:`` segment in the record that read it."""
    by_seq = {r["seq"]: r for r in records}
    for r in records:
        states = (r["cpu_s"], r["blocked_s"], r["stalled_s"])
        assert all(v >= 0.0 for v in states), r
        assert sum(states) == pytest.approx(r["wall_s"], abs=1e-6)
        assert r["blocked_s"] <= r["wait_s"] + 1e-9
        fetches = [s for s in r["segments"] if s[0].startswith("fetch:")]
        assert sum(t1 - t0 for _, t0, t1 in fetches) == pytest.approx(
            r["blocked_s"], abs=1e-6)
        for name, t0, t1 in fetches:
            lane = "wait:" + name.split(":", 1)[1]
            assert any(n == lane and a - 1e-9 <= t0 and t1 <= b + 1e-9
                       for n, a, b in r["segments"]), (name, r["segments"])
        if not fetches:
            assert r["read_seq"] is None and r["blocked_s"] == 0.0
            continue
        assert r["read_seq"] in (r["seq"], r["seq"] - 1)
        assert r["ahead"] <= (r["read_seq"] == r["seq"] - 1)
        issuer = by_seq.get(r["read_seq"])
        if issuer is not None:
            phase = fetches[0][0].split(":", 1)[1]
            assert any(n == "issue:" + phase
                       for n, _, _ in issuer["segments"])
    if closed is not None:
        for r in records:
            n = sum(s[0].startswith("fetch:") for s in r["segments"])
            assert n == closed.count(r["seq"]), r


def check_requests(finished) -> None:
    """Every finished request that held a slot carries the four overlays,
    ``engine_cpu + engine_blocked + engine_stalled == engine_wall`` within
    its own wall, and ``host_gap`` is what the parent's rule gives: the
    overlays stay out of the residual."""
    assert finished
    assert set(CP_THREAD_STATES) <= CP_OVERLAYS
    for req in finished:
        cp = req.cp
        assert set(CP_THREAD_STATES) <= set(cp), cp
        wall, cpu, blocked, stalled = (cp[k] for k in CP_THREAD_STATES)
        assert min(wall, cpu, blocked, stalled) >= 0.0
        assert cpu + blocked + stalled == pytest.approx(wall, abs=1e-6)
        life = req.finish_time - req.submit_time
        assert 0.0 < wall <= life + 1e-3
        attributed = sum(v for k, v in cp.items()
                         if k not in PARENT_OVERLAYS
                         and k not in CP_THREAD_STATES and k != "host_gap")
        assert cp["host_gap"] == pytest.approx(
            max(0.0, life - attributed), abs=1e-9)
