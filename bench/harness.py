"""One run of one benchmark cell: the daemon served over loopback TCP to an
open-loop web tier, every answer checked against the plain reference.

The process holds the chip. It builds the cell's table from the seed,
warms every executor the traffic can use, starts an in-process
``ThreadedServer``, and hands a seeded schedule to the load generator,
a child process that never imports JAX (``bench/loadgen.py``). The
schedule has three parts, all checked:

    warm-up traffic   the cell's own mix at its rate, ``warm_seconds`` long
    gap               ``gap_seconds`` with nothing due: window counters
                      are read here, over the wire
    window            ``--seconds`` of the mix at the cell's rate

Everything before the window is set-up (``setup_s``). Latency is timed
from each statement's due time to its answer; statements due in the
window form the sample.

Whatever belongs to a configuration, a mix, a cell or a per-layer
metric is found by name: ``configs/<config>.json``, ``mixes/<traffic>.json``,
``cells/<workload>.json``, ``metrics/<metric>.py``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "_out"
# every grouped batch size up to the server's default group size, 64
BUCKETS = tuple(2 ** i for i in range(1, 7))


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ discovery

def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def mixes() -> list[str]:
    """Every traffic mix the harness can run, by name."""
    return sorted(p.stem for p in (BENCH / "mixes").glob("*.json"))


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def cell(workload: str, bench_spec: dict) -> dict:
    """The workload entry with its configuration, mix and cell files."""
    for w in bench_spec["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise KeyError(f"no workload named {workload!r} in BENCHMARK.json")
    return {"workload": w, "config": load_json("configs", w["config"]),
            "mix": load_json("mixes", w["traffic"]),
            "cell": load_json("cells", w["name"])}


# ---------------------------------------------------------------- set-up

def load_table(db, cfg: dict, rows: dict, table: str) -> float:
    """CREATE the table and bulk-load ``rows`` through ``executemany``.
    Returns the load time in seconds."""
    from bench.traffic import fragment
    db.execute(cfg["ddl"].format(table=table, capacity=cfg["capacity"],
                                 max_select=cfg["max_select"]))
    cols = cfg["columns"]
    sql = (f"INSERT INTO {table} ({', '.join(cols)}) "
           f"VALUES ({', '.join('?' * len(cols))})")
    n, batch = len(rows["page_id"]), cfg["load_batch"]
    t0 = time.perf_counter()
    res = None
    for lo in range(0, n, batch):
        hi = min(n, lo + batch)
        data = [fragment(x, cfg["fragment_bytes"])
                for x in rows["data"][lo:hi].tolist()]
        res = db.executemany(sql, list(zip(
            rows["page_id"][lo:hi].tolist(), rows["user_id"][lo:hi].tolist(),
            data)))
    if res is not None:
        res.count  # the last batch's answer ends the load
    return time.perf_counter() - t0


def _sentinel(cfg: dict, mix: dict, sp: dict, i: int) -> list:
    """Parameters of a statement shaped like ``sp`` that touch only
    sentinel rows (page ids no statement draws, user -1): warming it
    changes no row that the traffic reads."""
    from bench.traffic import fragment
    out = []
    for b in sp["bind"]:
        dr = mix["draws"][b]
        if "recent" in dr:
            dr = mix["draws"][dr["fallback"]]
        space = dr.get("of") or dr.get("uniform_key")
        if space == "pages":
            out.append(cfg["pages"] + 1_000_000 + i)
        elif space == "users":
            out.append(-1)
        else:
            out.append(fragment(i, cfg["fragment_bytes"]))
    return out


def warm(db, cfg: dict, mix: dict, table: str) -> dict:
    """Plan every executor the mix can reach before any traffic: the
    single-statement shapes (``WARMUP ... LIKE``) and, for each shape,
    every grouped batch size the scheduler can form. Warm statements
    touch sentinel rows only, and those are deleted at the end."""
    t0 = time.perf_counter()
    db.drain_warmup(table)
    compiled = db.execute(f"WARMUP {table}").count
    sqls = [sp["sql"].format(table=table) for sp in mix["statements"]]
    cleanup = f"DELETE FROM {table} WHERE user_id = ?"
    for sql in dict.fromkeys(sqls + [cleanup]):
        compiled += db.execute(
            f"WARMUP {table} LIKE '{sql}'").count
    t_single = time.perf_counter() - t0
    order = sorted(mix["statements"],
                   key=lambda sp: ("insert", "select", "count", "update",
                                   "delete").index(sp["kind"]))
    per_kind: dict[str, float] = {}
    for b in BUCKETS:
        for sp in order:
            t1 = time.perf_counter()
            sql = sp["sql"].format(table=table)
            params = [_sentinel(cfg, mix, sp, i) for i in range(b)]
            res = db.executemany(sql, params, per_statement=True)
            (res[-1] if isinstance(res, list) else res).count
            per_kind[sp["kind"]] = (per_kind.get(sp["kind"], 0.0)
                                    + time.perf_counter() - t1)
    db.execute(cleanup, [-1]).count
    log(f"warm-up: single-statement shapes {t_single:.3f} s; grouped "
        "sizes by kind " + ", ".join(f"{k} {v:.3f} s"
                                     for k, v in per_kind.items()))
    for sql in ("SHOW STATS", f"SHOW METRICS {table}"):  # what the gap reads
        db.execute(sql).value
    stats = json.loads(db.execute(f"SHOW STATS {table}").value)
    errs = stats["executors"]["warmup_errors"]
    if errs:
        raise RuntimeError(f"background warm-up failed: {errs[0]}")
    live = sum(s["live_rows"] for s in stats["per_shard"])
    return {"warmup_s": time.perf_counter() - t0, "warm_compiled": compiled,
            "executors": stats["executors"]["compiles"], "live_rows": live}


# ------------------------------------------------------------ the window

def _snapshot(client, table: str) -> dict:
    m = client.execute(f"SHOW METRICS {table}")["value"]
    s = client.execute("SHOW STATS")["value"]
    stages: dict[str, list] = {}
    for shape in m["shapes"].values():
        for stage, ent in shape["stages"].items():
            acc = stages.setdefault(stage, [0.0, 0])
            acc[0] += ent.get("total_us", 0.0)
            acc[1] += ent.get("count", 0)
    return {"stages": stages, "scheduler": dict(s.get("scheduler", {})),
            "server": dict(s.get("server", {})),
            "executors": dict(s["executors"])}


def _delta(before: dict, after: dict) -> dict:
    out = {"stages": {k: [v[0] - before["stages"].get(k, [0.0, 0])[0],
                          v[1] - before["stages"].get(k, [0.0, 0])[1]]
                      for k, v in after["stages"].items()}}
    for part in ("scheduler", "server", "executors"):
        out[part] = {k: v - before[part].get(k, 0)
                     for k, v in after[part].items()
                     if isinstance(v, (int, float))}
    return out


def _wire_statements(stmts: list) -> tuple[list, list]:
    sqls = list(dict.fromkeys(st["sql"] for st in stmts))
    qi = {q: i for i, q in enumerate(sqls)}
    return sqls, [[st["id"], st["conn"], st["due"], qi[st["sql"]],
                   st["params"]] for st in stmts]


def serve(addr, table: str, stmts: list, connections: int, *, gap: float,
          warm_s: float, seconds: float, trace_dir: pathlib.Path | None,
          process_start: float) -> dict:
    """Run the schedule through the generator child; read the window's
    counters in the gap, trace part of the window when asked. Returns
    the generator's records and what was measured around them."""
    from bench import wire
    sqls, lines = _wire_statements(stmts)
    header = {"host": addr[0], "port": addr[1], "connections": connections,
              "sqls": sqls}
    # the child must not inherit a JAX platform choice or the chip
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "TPU_", "XLA_"))}
    proc = subprocess.Popen(
        [sys.executable, str(BENCH / "loadgen.py")], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True, env=env, cwd=str(ROOT))
    try:
        proc.stdin.write(json.dumps(header) + "\n")
        for ln in lines:
            proc.stdin.write(json.dumps(ln) + "\n")
        proc.stdin.write("END\n")
        proc.stdin.flush()
        ready = proc.stdout.readline().strip()
        if ready != "READY":
            raise RuntimeError(f"load generator did not start: {ready!r}")
        t0 = time.monotonic() + warm_s + gap + 0.2
        proc.stdin.write(f"START {t0!r}\n")
        proc.stdin.flush()
        with wire.Client(*addr) as ctl:
            _sleep_until(t0 - gap / 2)
            before = _snapshot(ctl, table)
            late = time.monotonic() - t0
            if late > 0:
                log(f"WARNING window counters read {late:.3f} s into the "
                    "window")
            traced = None
            if trace_dir is not None:
                traced = _trace(trace_dir, t0, seconds)
            out, _ = proc.communicate(timeout=seconds + 120)
            if proc.returncode != 0:
                raise RuntimeError(f"load generator exited {proc.returncode}")
            after = _snapshot(ctl, table)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    recs = {}
    for line in out.splitlines():
        r = json.loads(line)
        recs[r["i"]] = r
    return {"recs": recs, "t0": t0, "setup_s": t0 - process_start,
            "delta": _delta(before, after), "totals": after,
            "traced": traced}


def _sleep_until(t: float) -> None:
    while (d := t - time.monotonic()) > 0:
        time.sleep(min(d, 0.5))


def _trace(trace_dir: pathlib.Path, t0: float, seconds: float) -> dict:
    """Profile a steady stretch in the middle of the window (from now,
    if the middle has begun)."""
    import jax
    length = min(4.0, seconds / 3)
    start = t0 + (seconds - length) / 2
    shutil.rmtree(trace_dir, ignore_errors=True)
    _sleep_until(start)
    jax.profiler.start_trace(str(trace_dir))
    a = time.monotonic()
    _sleep_until(a + length)
    b = time.monotonic()
    jax.profiler.stop_trace()
    return {"window_s": b - a, "start": a - t0}


# -------------------------------------------------------------- metrics

def latency_metrics(stmts: list, recs: dict, lo: float, hi: float) -> dict:
    """Latency quantiles over the statements due in [lo, hi) (a
    failure or a statement never answered misses every limit, so it
    sorts last), and stmts_per_s answered without error in [lo, hi)."""
    lat, late, done = [], [], 0
    for st in stmts:
        if not lo <= st["due"] < hi:
            continue
        r = recs[st["id"]]
        if r["s"] is not None:
            late.append(r["s"] - st["due"])
        ok = r["r"] is not None and r.get("e") is None
        lat.append((r["r"] - st["due"]) if ok else math.inf)
    for r in recs.values():
        if r["r"] is not None and r.get("e") is None and lo <= r["r"] < hi:
            done += 1
    lat.sort()
    n = len(lat)

    def pct(q):
        v = lat[max(0, math.ceil(q * n) - 1)]
        return v * 1e3 if math.isfinite(v) else math.inf

    return {"n": n, "p50_ms": pct(0.50), "p90_ms": pct(0.90),
            "p95_ms": pct(0.95), "p99_ms": pct(0.99), "p999_ms": pct(0.999),
            "beyond_p99": n - math.ceil(0.99 * n),
            "per_s": done / (hi - lo),
            "late_p50_ms": float(np.percentile(late, 50)) * 1e3,
            "late_p99_ms": float(np.percentile(late, 99)) * 1e3,
            "late_max_ms": float(np.max(late)) * 1e3}


def per_layer(bench_spec: dict, workload: str, ctx: dict) -> dict:
    out = {}
    for m in bench_spec["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int | None:
    peaks = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ------------------------------------------------------------------ run

def drive(workload: str, seed: int, seconds: float, trace: bool, *,
          process_start: float, require_chip: bool = True,
          config_override: dict | None = None,
          cell_override: dict | None = None,
          mix_override: dict | None = None, fault=None) -> dict:
    """Set up ``workload`` from ``seed``, serve its schedule and return
    everything the result is made from: the schedule, the generator's
    records, the rows, the window's counter deltas, the device. The
    overrides shrink the cell for tests; ``fault(server)``
    breaks the timed path for the tests that must see ``correct`` go
    false."""
    c = cell(workload, spec())
    cfg = {**c["config"], **(config_override or {})}
    mix = {**c["mix"], **(mix_override or {})}
    cl = {**c["cell"], **(cell_override or {})}
    chips = c["workload"]["chips"]

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax
    dev = device_info(jax)
    if require_chip and (dev["platform"] != "tpu" or dev["count"] < chips):
        raise SystemExit(f"bench: cell {workload} needs {chips} TPU chip(s); "
                         f"JAX found {dev['count']} {dev['platform']} "
                         "device(s)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.daemon import SQLCached
    from repro.core.execache import use_persistent_cache
    from repro.core.protocol import ThreadedServer
    from bench import traffic as TR
    log(f"device {dev['platform']} {dev['kind']} x{dev['count']}; "
        f"compile cache {use_persistent_cache()}")

    table = cfg["table"]
    data = TR.make_rows(cfg, seed)
    db = SQLCached()
    load_s = load_table(db, cfg, data, table)
    w = warm(db, cfg, mix, table)
    log(f"loaded {len(data['page_id'])} rows in {load_s:.3f} s; warm-up "
        f"{w['warmup_s']:.3f} s ({w['warm_compiled']} WARMUP executables, "
        f"{w['executors']} executors in all); live rows {w['live_rows']}")
    if w["live_rows"] != len(data["page_id"]):
        raise RuntimeError("warm-up left sentinel rows behind")

    rate = cl["rate_per_s"]
    warm_s, gap = mix["warm_seconds"], mix["gap_seconds"]
    stmts = TR.schedule(cfg, mix, seed, table, rate=rate, seconds=warm_s,
                        start=-(warm_s + gap), stream=1)
    stmts += TR.schedule(cfg, mix, seed, table, rate=rate, seconds=seconds,
                         stream=2, first_id=len(stmts))
    trace_dir = OUT / f"trace-{workload}" if trace else None
    compiles: list[float] = []      # end times of every XLA compile

    def on_compile(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.monotonic())
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        with ThreadedServer(db=db) as srv:
            if fault is not None:
                fault(srv.server)
            got = serve(srv.addr, table, stmts, mix["connections"], gap=gap,
                        warm_s=warm_s, seconds=seconds, trace_dir=trace_dir,
                        process_start=process_start)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    t0 = got["t0"]
    log(f"XLA compiles: {sum(1 for t in compiles if t < t0)} in the warm-up "
        f"traffic, {sum(1 for t in compiles if t0 <= t < t0 + seconds)} in "
        "the window")
    dev["memory_peak_bytes"] = memory_peak(jax)
    # the program's state goes before the reference runs
    db.execute(f"DROP TABLE {table}")
    del db, srv
    gc.collect()
    return {"workload": workload, "seconds": seconds, "cfg": cfg, "mix": mix,
            "cell": cl, "stmts": stmts, "recs": got["recs"], "data": data,
            "delta": got["delta"], "totals": got["totals"],
            "setup_s": got["setup_s"],
            "traced": got["traced"], "trace_dir": trace_dir, "device": dev,
            "load_s": load_s, "warmup_s": w["warmup_s"]}


def reference(d: dict):
    from bench.reference import Reference
    cfg = d["cfg"]
    return Reference(d["data"], cfg["capacity"], cfg["max_select"],
                     cfg["fragment_bytes"])


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        process_start: float, require_chip: bool = True, **kw) -> dict:
    """One run of ``workload``: the result object (the contract's last
    line), with ``checks`` last. Keywords go to :func:`drive`."""
    from bench import check as CHK
    d = drive(workload, seed, seconds, trace, process_start=process_start,
              require_chip=require_chip, **kw)
    stmts, recs, cl, dev = d["stmts"], d["recs"], d["cell"], d["device"]
    lm = latency_metrics(stmts, recs, 0.0, seconds)
    log(f"window {seconds} s at {cl['rate_per_s']} stmts/s offered: "
        f"{lm['n']} statements due, {lm['beyond_p99']} beyond p99; "
        f"generator lateness p50 {lm['late_p50_ms']:.3f} ms, p99 "
        f"{lm['late_p99_ms']:.3f} ms, max {lm['late_max_ms']:.3f} ms; "
        f"latency p50 {lm['p50_ms']:.3f} ms, p90 {lm['p90_ms']:.3f} ms, p95 "
        f"{lm['p95_ms']:.3f} ms, p99 {lm['p99_ms']:.3f} ms, p99.9 "
        f"{lm['p999_ms']:.3f} ms")
    sched, execs = d["delta"]["scheduler"], d["delta"]["executors"]
    log(f"window dispatch: {sched.get('singles', 0)} single statements, "
        f"{sched.get('grouped_statements', 0)} grouped in "
        f"{sched.get('batches', 0) - sched.get('singles', 0)} groups; "
        f"executor compiles {execs.get('compiles', 0)}; mean rows per "
        f"SELECT {CHK.mean_rows(stmts, recs):.2f}")

    t_check = time.monotonic()
    res = CHK.check(stmts, recs, reference(d), route_col=_route_col(d["mix"]))
    log(f"check: {res['compared']} answers compared ({res['foreign_compared']}"
        f" foreign reads) in {time.monotonic() - t_check:.1f} s")
    for ex in res["examples"]:
        log(f"MISMATCH {ex}")

    failed = sum(1 for st in stmts
                 if recs[st["id"]]["r"] is None or recs[st["id"]].get("e"))
    result = {"correct": None, "attempted": len(stmts), "failed": failed}
    if trace:
        from bench import devtrace, peaks
        planes = devtrace.load(devtrace.find_xplane(d["trace_dir"]))
        red = devtrace.reduce(planes, d["traced"]["window_s"],
                              ignore=("$harness.py",))
        for name in devtrace.KERNELS:
            calls = devtrace.kernel_calls(red, name)
            if calls:
                log(f"trace {name}: {len(calls)} calls, "
                    f"{sum(c[0] for c in calls) * 1e-9:.6f} s, {calls[0][1]}")
        ctx = {"workload": workload, "config": d["cfg"], "mix": d["mix"],
               "cell": cl, "seconds": seconds, "delta": d["delta"],
               "device": dev, "latency": lm, "trace": red,
               "traced": d["traced"], "stmts": stmts, "recs": recs,
               "peaks": peaks.for_device(dev["kind"]) if require_chip
               else None}
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        result["metrics"] = per_layer(spec(), workload, ctx)
        result["breakdown"] = {"device_ops": devtrace.top_ops(red),
                               "idle_gaps": red["idle_gaps"]}
    else:
        values = {"stmt_p50_ms": _finite(lm["p50_ms"], seconds),
                  "stmt_p99_ms": _finite(lm["p99_ms"], seconds),
                  "stmts_per_s": lm["per_s"], "setup_s": d["setup_s"]}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec()["end_to_end"]
            if workload in m.get("workloads", [workload])}
    result["device"] = dev
    limits = cl["limits"]
    checks = {"wrong": {"value": res["wrong"], "limit": limits["wrong"]},
              "unanswered": {"value": res["unanswered"],
                             "limit": limits["unanswered"]}}
    result["correct"] = all(v["value"] <= v["limit"] for v in checks.values())
    result["checks"] = checks
    return result


def _finite(ms: float, seconds: float) -> float:
    """A failed statement counts as missing every limit: in a percentile
    it stands at the longest the run waits for an answer."""
    return ms if math.isfinite(ms) else (seconds + 60.0) * 1e3


def _route_col(mix: dict) -> str:
    """The column of the key that routes the mix's statements."""
    for sp in mix["statements"]:
        if "route" in sp:
            dr = mix["draws"][sp["bind"][sp["route"]]]
            if "recent" in dr:
                dr = mix["draws"][dr["fallback"]]
            space = dr.get("of") or dr.get("uniform_key")
            return {"pages": "page_id", "users": "user_id"}[space]
    raise ValueError("the mix has no routed statement")
