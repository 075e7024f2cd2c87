"""Record the small chip trace that ``bench/tests`` reduce.

Drives the benchmark's fragment table (``cms_fragments``, CAPACITY
131072) straight through the library on the chip: single-statement page
reads (the hash-index probe kernel), user scans (the relscan kernels)
and INSERTs (the index upkeep), traced by the JAX profiler. Copies the
``.xplane.pb`` to ``--out`` and prints the device planes' lines, the
kernel events with their metadata and the upkeep operations.

    python bench/record_trace.py --out bench/tests/data/trace_small.xplane.pb
"""
from __future__ import annotations

import argparse
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=3)
    a = ap.parse_args()
    import jax
    from bench import devtrace, traffic
    from bench.harness import load_json, load_table
    from repro.core.daemon import SQLCached
    cfg = load_json("configs", "cms_fragments")
    db = SQLCached()
    load_table(db, cfg, traffic.make_rows(cfg, a.seed), "t")
    text = traffic.fragment(7, cfg["fragment_bytes"])
    stmts = [("SELECT * FROM t WHERE page_id = ?", [17]),
             ("SELECT page_id FROM t WHERE user_id = ?", [5]),
             ("SELECT COUNT(*) FROM t WHERE user_id = ?", [9]),
             ("INSERT INTO t (page_id, user_id, data) VALUES (?, ?, ?)",
              [17, 5, text])]
    for sql, p in stmts:          # compile outside the trace
        db.execute(sql, p).count
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for i in range(5):
        for sql, p in stmts:
            db.execute(sql, p).count
    jax.profiler.stop_trace()
    src = devtrace.find_xplane(tmp)
    out = pathlib.Path(a.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    print(f"trace {out} {out.stat().st_size} bytes")
    planes = devtrace.load(out)
    for p in planes:
        print("plane", p["name"], [(ln["name"], len(ln["events"]))
                                   for ln in p["lines"]])
    for p in planes:
        if not p["name"].startswith(devtrace.DEVICE_PREFIX):
            continue
        for ln in p["lines"]:
            names = sorted({e[0] for e in ln["events"]})
            print("line", ln["name"], names[:60])
            for e in ln["events"]:
                if "kernel" in e[0]:
                    print("event", e[0], e[2], e[3])
                    break
    red = devtrace.reduce(planes, 1.0)
    print("busy_s", red["busy_s"], "top", devtrace.top_ops(red))
    print("upkeep", [(n[:300], len(evs)) for n, evs in red["events"].items()
                     if devtrace.upkeep_op(n)])
    print("gaps", red["idle_gaps"][:5])
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
