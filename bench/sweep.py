"""Find a cell's knee: one table, the cell's mix at rising fixed rates.

    python bench/sweep.py --workload <cell> --seed <n> --rates 250,500,... \
        [--seconds 8]

Loads and warms the cell's table once, then for each rate runs the
cell's warm-up traffic, the gap and a window of ``--seconds``, and
prints one JSON line: offered rate, answered rate, p50 and p99 from due
time, generator lateness, and the p99 of the first and the second half
of the window (a backlog that grows shows as a second half far above
the first). Answers are not checked here; the benchmark's runs check
them. The knee is the highest rate whose p99 meets the cell's latency
limit with no growing backlog.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    a = ap.parse_args()
    c = harness.cell(a.workload, harness.spec())
    cfg, mix = c["config"], c["mix"]
    harness.os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  str(harness.ROOT / ".jax_cache"))
    import jax
    dev = harness.device_info(jax)
    if dev["platform"] != "tpu":
        raise SystemExit("sweep: no TPU")
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.core.daemon import SQLCached
    from repro.core.execache import use_persistent_cache
    from repro.core.protocol import ThreadedServer
    from bench import traffic as TR
    use_persistent_cache()
    table = cfg["table"]
    db = SQLCached()
    load_s = harness.load_table(db, cfg, TR.make_rows(cfg, a.seed), table)
    w = harness.warm(db, cfg, mix, table)
    harness.log(f"load {load_s:.3f} s, warm {w['warmup_s']:.3f} s")
    warm_s, gap = mix["warm_seconds"], mix["gap_seconds"]
    with ThreadedServer(db=db) as srv:
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            stmts = TR.schedule(cfg, mix, a.seed + i, table, rate=rate,
                                seconds=warm_s, start=-(warm_s + gap),
                                stream=1)
            stmts += TR.schedule(cfg, mix, a.seed + i, table, rate=rate,
                                 seconds=a.seconds, stream=2,
                                 first_id=len(stmts))
            got = harness.serve(srv.addr, table, stmts, mix["connections"],
                                gap=gap, warm_s=warm_s, seconds=a.seconds,
                                trace_dir=None, process_start=PROCESS_START)
            recs = got["recs"]
            lm = harness.latency_metrics(stmts, recs, 0.0, a.seconds)
            half = a.seconds / 2
            first = harness.latency_metrics(stmts, recs, 0.0, half)
            second = harness.latency_metrics(stmts, recs, half, a.seconds)
            d = got["delta"]["scheduler"]
            print(json.dumps({
                "rate": rate, "answered_per_s": lm["per_s"],
                "p50_ms": lm["p50_ms"], "p99_ms": lm["p99_ms"],
                "p99_first_half_ms": first["p99_ms"],
                "p99_second_half_ms": second["p99_ms"],
                "late_p99_ms": lm["late_p99_ms"],
                "errors": sum(1 for r in recs.values() if r.get("e")),
                "unanswered": sum(1 for r in recs.values() if r["r"] is None),
                "stmts_per_dispatch": (d.get("singles", 0) +
                                       d.get("grouped_statements", 0)) /
                max(1, d.get("batches", 0)),
                "compiles": got["delta"]["executors"].get("compiles", 0)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
