"""Cluster failover benchmark: kill a replica mid-run, survive it.

Three daemon PROCESSES (tests/_chaos.DaemonProc — real SIGKILL, not a
mock), one spread table with ``REPLICAS 2``, one ClusterClient. Four
phases:

- **healthy**: per-op latency of pruned single-group reads (p50/p99 µs)
  with all three nodes up — the baseline.
- **kill window**: a mixed write+read workload is in flight when one
  node takes ``kill -9``. Every write ack is recorded; errors and the
  worst latency in the window are reported (the failover detection +
  backoff cost lands here, and only here).
- **post-kill**: the same read loop as `healthy`, now served by the
  promoted survivors — steady-state degraded latency.
- **audit**: every acknowledged write is read back; the headline
  invariant ``lost_acked_writes == 0`` means the ack contract held
  through the kill (mirrored tags: the surviving replica's response
  stood in for the dead node's).

Headline gated metric: ``failover_p99_ratio`` = post-kill p99 / healthy
p99. Steady state after promotion does the same work as healthy (one
node fewer shares it), so the ratio sits near 1 and is a stable
SAME-RUN ratio — host speed cancels. The kill-window spike is reported
but NOT gated (its magnitude is one backoff schedule, not a trend).

The daemons run on the CPU backend: on a host with a TPU the bench
stops at once, since three daemons cannot share one chip.

``--json`` writes BENCH_cluster.json at the repo root (checked in per
PR); ``--quick`` trims op counts but keeps every phase and the kill.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT / "tests"))  # the chaos harness

from repro.core.cluster import ClusterClient  # noqa: E402
from repro.launch.mesh import refuse_fleet_on_accelerator  # noqa: E402

from _chaos import spawn_fleet  # noqa: E402

N_READS = 600
N_KILL_OPS = 300
N_READS_QUICK = 150
N_KILL_OPS_QUICK = 120

CREATE = ("CREATE TABLE c (id INT, score FLOAT, INDEX (id)) "
          "CAPACITY 8192 MAX_SELECT 4096 SHARDS 2 PARTITION BY id "
          "REPLICAS 2")


def _pcts(us: list[float]) -> dict:
    s = sorted(us)
    return {"p50_us": round(s[len(s) // 2], 1),
            "p99_us": round(s[min(len(s) - 1, int(len(s) * 0.99))], 1),
            "ops": len(s)}


def _read_phase(cc: ClusterClient, n: int, rows: int) -> dict:
    lat: list[float] = []
    for i in range(n):
        t0 = time.perf_counter()
        r = cc.execute("SELECT * FROM c WHERE id = ?", (i % rows,))
        lat.append((time.perf_counter() - t0) * 1e6)
        assert r["rows"], f"row {i % rows} unreadable"
    return _pcts(lat)


def run(quick: bool = False) -> dict:
    n_reads = N_READS_QUICK if quick else N_READS
    n_kill = N_KILL_OPS_QUICK if quick else N_KILL_OPS
    seed_rows = 200
    refuse_fleet_on_accelerator("cluster bench")
    fleet = spawn_fleet(3)
    cc = None
    try:
        cc = ClusterClient([d.name for d in fleet], statement_retries=4,
                           retry_base=0.02, retry_cap=0.2)
        cc.execute(CREATE)
        with cc.pipeline() as pl:
            for i in range(seed_rows):
                pl.execute("INSERT INTO c (id, score) VALUES (?, ?)",
                           (i, float(i)))
        assert all(isinstance(r, dict) for r in pl.results)
        acked = list(range(seed_rows))

        # warm-up (unmeasured): WARMUP on every node pre-plans the read
        # executors (the eq-SELECT on the partition/index column is in
        # the canonical set), then a short read phase settles the batch
        # buckets + host caches. The gated ratio must compare steady
        # states, not compile time.
        cc.warmup("c")
        _read_phase(cc, 24, seed_rows)

        healthy = _read_phase(cc, n_reads, seed_rows)

        # ---- kill window: mixed workload, SIGKILL a third of the way in
        victim = fleet[0]
        kill_at = n_kill // 3
        errors = 0
        window: list[float] = []
        next_id = seed_rows
        for op in range(n_kill):
            if op == kill_at:
                victim.kill9()
            t0 = time.perf_counter()
            try:
                if op % 3 == 0:  # writes keep the ack contract honest
                    r = cc.execute(
                        "INSERT INTO c (id, score) VALUES (?, ?)",
                        (next_id, 1.0))
                    if r["count"] == 1:
                        acked.append(next_id)
                    next_id += 1
                else:
                    cc.execute("SELECT * FROM c WHERE id = ?",
                               (op % seed_rows,))
            except Exception:  # noqa: BLE001 — an unacked op, counted
                errors += 1
                if op % 3 == 0:
                    next_id += 1
            window.append((time.perf_counter() - t0) * 1e6)
        kill_window = dict(_pcts(window), errors=errors,
                           max_us=round(max(window), 1))

        post_kill = _read_phase(cc, n_reads, seed_rows)

        # ---- audit: every ack must still be readable (zero lost writes)
        lost = [i for i in acked
                if not cc.execute("SELECT * FROM c WHERE id = ?",
                                  (i,))["rows"]]
        return {
            "nodes": 3, "replicas": 2, "killed": 1,
            "healthy": healthy,
            "kill_window": kill_window,
            "post_kill": post_kill,
            "acked_writes": len(acked),
            "lost_acked_writes": len(lost),
            "failover_p99_ratio": round(
                post_kill["p99_us"] / healthy["p99_us"], 3),
        }
    finally:
        if cc is not None:
            cc.close()
        for d in fleet:
            d.kill9()


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    doc = run(quick="--quick" in argv)
    assert doc["lost_acked_writes"] == 0, doc
    if "--json" in argv:
        path = REPO_ROOT / "BENCH_cluster.json"
        path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
        print(f"wrote {path}")
    h, k, p = doc["healthy"], doc["kill_window"], doc["post_kill"]
    print(f"healthy    p50={h['p50_us']:>8.1f}us p99={h['p99_us']:>8.1f}us")
    print(f"kill win   p50={k['p50_us']:>8.1f}us max={k['max_us']:>8.1f}us "
          f"errors={k['errors']}")
    print(f"post-kill  p50={p['p50_us']:>8.1f}us p99={p['p99_us']:>8.1f}us")
    print(f"acked={doc['acked_writes']} lost={doc['lost_acked_writes']} "
          f"failover_p99_ratio={doc['failover_p99_ratio']}")


if __name__ == "__main__":
    main()
