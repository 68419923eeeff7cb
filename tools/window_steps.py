#!/usr/bin/env python3
"""Where a serving window's time went, step by step: a run whose tokens a
second read low names its cause here. Reads the files a benchmark run
leaves under ``chiprun_out/benchmark/<cell>/`` (``benchmark/run.py``: the
run's notes and the engine's step records) and prints, for each, the
end-to-end numbers and ``process_s``, then by kind of step (decode-only,
fused mixed, chunk alone, other) the count, the median wall, and the steps
over 1.5 x that median: how many, what they cost in all, and the four
worst with their issue / wait / cpu / blocked / stalled parts and drain
reason; last the largest gaps between steps. A step record's wall under
lookahead belongs to the program READ in it, so a decode record that reads
a mixed program is slow by design, as is a two-dispatch mixed step: a
machine stall is a step whose ``stalled_s``, or a gap, is hundreds of
milliseconds. Steps up to the last one that built a program (``issue_s`` >
0.5 s: the warm-up) are left out.

    python tools/window_steps.py chiprun_out/benchmark/<cell>/seed*.json
"""

from __future__ import annotations

import json
import statistics
import sys


def kind(rec: dict) -> str:
    chunk, plane = rec.get("chunk_rows"), rec.get("ssm_state_rows_held",
                                                  rec.get("dispatches"))
    if chunk:
        return "mixed" if rec.get("dispatches", 1) == 1 and plane else "chunk"
    return "decode" if plane else "other"


def ms(seconds: float) -> float:
    return round(seconds * 1e3, 1)


def report(path: str) -> None:
    run = json.load(open(path))
    notes, e2e = run["notes"], run["end_to_end"]
    print("==", path.rsplit("/", 1)[-1],
          {k: round(v, 2) for k, v in e2e.items() if v is not None},
          "process_s", round(notes.get("process_s", 0.0), 1),
          "check_s", round(notes.get("check_s", 0.0), 1),
          "correct", notes.get("check", {}).get("ok"),
          "failed", notes.get("failed"))
    steps = run["steps"]
    built = max([r["seq"] for r in steps if r["issue_s"] > 0.5] + [0])
    steps = [r for r in steps if r["seq"] > built]
    by_kind: dict = {}
    for rec in steps:
        by_kind.setdefault(kind(rec), []).append(rec)
    for name, recs in sorted(by_kind.items()):
        median = statistics.median(r["wall_s"] for r in recs)
        slow = [r for r in recs if r["wall_s"] > 1.5 * median + 0.005]
        print(f"   {name}: {len(recs)} steps, median wall {ms(median)} ms, "
              f"{len(slow)} over 1.5 x it, their excess "
              f"{sum(r['wall_s'] - median for r in slow):.3f} s")
        for r in sorted(slow, key=lambda r: -r["wall_s"])[:4]:
            print("      seq", r["seq"], "wall", ms(r["wall_s"]),
                  "issue", ms(r["issue_s"]), "wait", ms(r["wait_s"]),
                  "cpu", ms(r["cpu_s"]), "blocked", ms(r["blocked_s"]),
                  "stalled", ms(r["stalled_s"]), "drain", r.get("drain"))
    gaps = sorted(((r.get("gap_before_s") or 0.0, r["seq"]) for r in steps),
                  reverse=True)
    print("   largest gaps before a step (ms, seq):",
          [(ms(g), s) for g, s in gaps[:3]],
          f"all gaps {sum(g for g, _ in gaps):.3f} s")


if __name__ == "__main__":
    for arg in sys.argv[1:]:
        report(arg)
