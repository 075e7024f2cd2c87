"""Readings that the limits of ``correct`` are set from.

    python bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 30]

For each seed, one run of the cell (set-up, warm-up traffic, window), in
one process, and three readings of the numbers compared:

- ``program``: the daemon's answers against the reference (what every
  benchmark run reports);
- ``dropped_write``: the control, the daemon's answers against a
  reference that leaves out one acknowledged write, which a later read
  on the writer's own connection must show (a broken guarantee);
- ``dropped_write_foreign``: the same, for a write that a later read
  from another connection must show.

The configuration states no precision (its columns are integers and
text), so the control breaks one of its guarantees. Prints one JSON line
per seed. The benchmark's own runs do not run the controls.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import check as CHK  # noqa: E402
from bench import harness  # noqa: E402


def readings(d: dict) -> dict:
    """The three readings of one driven run (see the module doc)."""
    stmts, recs = d["stmts"], d["recs"]
    route = harness._route_col(d["mix"])

    def nums(res):
        return {k: res[k] for k in ("wrong", "unanswered")}

    out = {"program": nums(CHK.check(stmts, recs, harness.reference(d),
                                     route_col=route))}
    for name, foreign in (("dropped_write", False),
                          ("dropped_write_foreign", True)):
        pair = CHK.dropped_write(stmts, recs, foreign=foreign)
        res = None if pair is None else CHK.check(
            stmts, recs, harness.reference(d), route_col=route,
            skip=frozenset([pair[0]]))
        out[name] = None if res is None else {
            **nums(res), "read_caught": pair[1] in res["wrong_ids"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float,
                    default=harness.spec()["run_seconds"])
    a = ap.parse_args()
    start = PROCESS_START
    for seed in (int(s) for s in a.seeds.split(",")):
        d = harness.drive(a.workload, seed, a.seconds, False,
                          process_start=start)
        lm = harness.latency_metrics(d["stmts"], d["recs"], 0.0, a.seconds)
        print(json.dumps({"seed": seed, **readings(d),
                          "p50_ms": lm["p50_ms"], "p99_ms": lm["p99_ms"],
                          "setup_s": d["setup_s"],
                          "compiles": d["delta"]["executors"].get(
                              "compiles", 0)}), flush=True)
        start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
