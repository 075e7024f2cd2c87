"""Mesh placement: 1 vs N devices at fixed per-device capacity.

PR 7 makes ``SHARDS n`` a *physical* partition — one execution lane
per device (core/shards.py mesh section, launch/mesh.py placement
policy). This bench answers the two questions that placement raises:

1. **Pruned routes must not pay for the mesh.** A partition-eq SELECT
   dispatches to exactly one lane on one device (zero cross-device
   traffic); its p50 through the production ``execute()`` path must
   stay within ~1.2x of the same table executed UNPLACED (all lanes on
   one device, the pre-PR-7 shape). That ratio is
   ``pruned_mesh_over_single_p50`` in BENCH_mesh.json.

2. **Fan-out overhead is bounded.** A non-partition-eq SELECT visits
   every device under one shard_map program and merges via the
   id-only gather. ``fanout_over_pruned_p50`` (N-device fan-out p50 /
   pruned p50, same run, same table) is the curated ``--check``
   metric: it is a SAME-RUN ratio, so host speed and background load
   cancel to first order, and a regression means the cross-device
   fan-out path itself got slower relative to single-device dispatch.

On an accelerator host the measurement runs in this process over the
devices the backend has (it needs at least two; 8 shards spread over
them): a child process would contend for the chips this one holds.
On the CPU backend (e.g. ``JAX_PLATFORMS=cpu``) it runs in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` unless this
process already has 8 devices: the parent (benchmarks/run.py) has
already initialized jax with however many devices the host exposes —
typically one — and XLA device count is fixed at first use. The
worker builds one mesh-placed and one
unplaced ``SQLCached`` over IDENTICAL 8-shard schemas (fixed per-shard
capacity, ~90% full, unique partition keys) and samples all four
(placement, route) timers ROUND-ROBIN in a single loop — paired
sampling, same convention as shard_bench — so a load spike moves every
configuration together and the checked-in ratios stay stable.

``--json`` writes BENCH_mesh.json at the repo root (checked in per
PR); ``--quick`` trims per-shard rows and reps but keeps both ratio
metrics ``--check`` compares.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

N_DEVICES = 8               # forced host device count in the worker
N_SHARDS = 8                # one lane per forced device
SHARD_ROWS = 8192           # per-shard capacity (FIXED per device)
QUICK_SHARD_ROWS = 2048
REPS = 120
REPS_QUICK = 60
FILL = 0.9
INSERT_CHUNK = 4096
WORKER_TIMEOUT_S = 1200


def _pcts(us):
    us = np.asarray(us)
    return (round(float(np.percentile(us, 50)), 2),
            round(float(np.percentile(us, 99)), 2))


# ----------------------------------------------------------------- worker

class _ExecTimer:
    """Times one (db, statement) pair through the production
    ``execute()`` path — parse cache, shard routing, dispatch, result
    realization to host — the latency a web client actually sees."""

    def __init__(self, db, sql, qkeys):
        self._db = db
        self._sql = sql
        self._ks = [int(k) for k in qkeys]
        self.lats: list = []

    def warm(self) -> None:
        """Pre-plan this statement's executor on every device a pruned
        route can land on — one WARMUP LIKE statement (core/execache.py
        compiles per placed lane device from abstract avals; no real
        traffic needed)."""
        self._db.execute(
            "WARMUP mt LIKE '" + self._sql.replace("'", "''") + "'")

    def step(self, i: int) -> None:
        k = self._ks[i % len(self._ks)]
        t0 = time.perf_counter()
        self._db.execute(self._sql, (k,))
        self.lats.append((time.perf_counter() - t0) * 1e6)


def _build(shard_rows: int):
    """Two daemons over identical 8-shard tables: mesh-placed (one lane
    per device) and unplaced (all lanes on one device, pre-PR-7)."""
    import jax

    from repro.core import shards as SH
    from repro.core.daemon import SQLCached

    assert jax.device_count() > 1, (
        f"the mesh bench needs several devices, got {jax.device_count()}"
        " — XLA_FLAGS not applied before jax init?")
    create = (f"CREATE TABLE mt (k INT, w INT) "
              f"CAPACITY {shard_rows * N_SHARDS} MAX_SELECT 8 "
              f"SHARDS {N_SHARDS} PARTITION BY k")
    db_mesh = SQLCached(mesh_exec=True)
    db_single = SQLCached(mesh_exec=False)
    for db in (db_mesh, db_single):
        db.execute(create)
    assert db_mesh.tables["mt"].mesh is not None
    assert db_single.tables["mt"].mesh is None

    total = int(shard_rows * N_SHARDS * FILL)
    rng = np.random.default_rng(shard_rows)
    keys = rng.permutation(shard_rows * N_SHARDS).astype(np.int64)[:total]
    ws = rng.integers(0, 1024, total)
    rows = [(int(k), int(w)) for k, w in zip(keys, ws)]
    for db in (db_mesh, db_single):
        for lo in range(0, total, INSERT_CHUNK):
            db.executemany("INSERT INTO mt (k, w) VALUES (?, ?)",
                           rows[lo:lo + INSERT_CHUNK])

    # query keys: 8 live partition keys PER SHARD (deliberate coverage,
    # so warm-up compiles the pruned executor on every device) + 64
    # fan-out values drawn from the live w range
    by_shard: dict = {}
    for k in keys:
        by_shard.setdefault(SH.shard_of_host(int(k), N_SHARDS), []).append(k)
    assert len(by_shard) == N_SHARDS
    qk_pruned = [int(ks[i]) for i in range(8) for ks in by_shard.values()]
    qk_fanout = [int(w) for w in ws[rng.integers(0, total, 64)]]
    return db_mesh, db_single, qk_pruned, qk_fanout


def worker(shard_rows: int, reps: int) -> dict:
    import jax

    db_mesh, db_single, qk_pruned, qk_fanout = _build(shard_rows)
    pruned_sql = "SELECT w FROM mt WHERE k = ?"
    fanout_sql = "SELECT k FROM mt WHERE w = ?"
    timers = {
        ("mesh", "pruned"): _ExecTimer(db_mesh, pruned_sql, qk_pruned),
        ("mesh", "fanout"): _ExecTimer(db_mesh, fanout_sql, qk_fanout),
        ("single", "pruned"): _ExecTimer(db_single, pruned_sql, qk_pruned),
        ("single", "fanout"): _ExecTimer(db_single, fanout_sql, qk_fanout),
    }
    for t in timers.values():
        t.warm()
    for i in range(reps):            # paired: round-robin, one loop
        for t in timers.values():
            t.step(i)

    mesh = db_mesh.tables["mt"].mesh
    out = {
        "bench": "mesh_placement",
        "latency_basis": "daemon execute() wall-clock per statement, "
                         "all four (placement, route) timers sampled "
                         "round-robin (paired)",
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "devices_used": int(np.prod(mesh.devices.shape)),
        "shards": N_SHARDS,
        "per_shard_rows": shard_rows,
        "fill": FILL,
    }
    for name in ("mesh", "single"):
        entry = {}
        for route in ("pruned", "fanout"):
            p50, p99 = _pcts(timers[(name, route)].lats)
            entry[f"{route}_p50_us"] = p50
            entry[f"{route}_p99_us"] = p99
        out[name] = entry
    out["fanout_over_pruned_p50"] = round(
        out["mesh"]["fanout_p50_us"] / out["mesh"]["pruned_p50_us"], 2)
    out["pruned_mesh_over_single_p50"] = round(
        out["mesh"]["pruned_p50_us"] / out["single"]["pruned_p50_us"], 2)
    out["fanout_mesh_over_single_p50"] = round(
        out["mesh"]["fanout_p50_us"] / out["single"]["fanout_p50_us"], 2)
    return out


# ----------------------------------------------------------------- parent

def run(quick: bool = False) -> dict:
    """Measure in this process when the backend already has the devices,
    else (CPU only) spawn the forced-8-device worker subprocess and
    collect its JSON. The current process's jax device topology is
    already fixed, so XLA_FLAGS must be set before the worker's first
    jax import."""
    import jax
    shard_rows = QUICK_SHARD_ROWS if quick else SHARD_ROWS
    reps = REPS_QUICK if quick else REPS
    n, backend = jax.device_count(), jax.default_backend()
    if n >= N_DEVICES or (n > 1 and backend != "cpu"):
        return worker(shard_rows, reps)
    if backend != "cpu":
        raise RuntimeError(
            f"mesh bench: the {backend} backend has one device; run it on "
            "a multi-chip host, or under JAX_PLATFORMS=cpu (forced host "
            "devices)")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEVICES}"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("REPRO_MESH", None)       # the worker builds both placements
    env["PYTHONPATH"] = (str(REPO_ROOT / "src")
                         + (os.pathsep + env["PYTHONPATH"]
                            if env.get("PYTHONPATH") else ""))
    with tempfile.TemporaryDirectory() as td:
        out_path = pathlib.Path(td) / "mesh.json"
        cmd = [sys.executable, "-m", "benchmarks.mesh_bench",
               "--worker", "--out", str(out_path)]
        if quick:
            cmd.append("--quick")
        proc = subprocess.run(cmd, cwd=REPO_ROOT, env=env,
                              capture_output=True, text=True,
                              timeout=WORKER_TIMEOUT_S)
        if proc.returncode != 0:
            raise RuntimeError(
                f"mesh bench worker failed (rc={proc.returncode}):\n"
                f"{proc.stdout}\n{proc.stderr}")
        return json.loads(out_path.read_text())


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    quick = "--quick" in argv
    if "--worker" in argv:
        res = worker(QUICK_SHARD_ROWS if quick else SHARD_ROWS,
                     REPS_QUICK if quick else REPS)
        out = pathlib.Path(argv[argv.index("--out") + 1])
        out.write_text(json.dumps(res, indent=2) + "\n")
        return res
    res = run(quick=quick)
    if "--json" in argv:
        path = REPO_ROOT / "BENCH_mesh.json"
        path.write_text(json.dumps(res, indent=2) + "\n")
        print(json.dumps(res, indent=2))
        print(f"# wrote {path}")
        return res
    print(f"# {res['devices_used']}-device mesh vs unplaced, "
          f"{res['shards']} shards x {res['per_shard_rows']} rows "
          f"(execute() wall-clock, p50 us)")
    print("placement,pruned_us,fanout_us")
    for name in ("mesh", "single"):
        e = res[name]
        print(f"{name},{e['pruned_p50_us']},{e['fanout_p50_us']}")
    print(f"# fan-out / pruned p50 on the mesh: "
          f"{res['fanout_over_pruned_p50']}x")
    print(f"# pruned p50, mesh vs single-device: "
          f"{res['pruned_mesh_over_single_p50']}x")
    return res


if __name__ == "__main__":
    main()
