"""Chip smoke: drive SQLcached's served path once on a TPU and check every
answer against a plain numpy model of the same table.

The workload is the paper's CMS fragment cache (§4-5): page fragments
keyed by page and user, read by page (hash-index probe), by user and
time (fused relscan), expired per user (fine-grained DELETE), rewritten
(UPDATE) and summarised (COUNT / AVG). The path is the one a web
application uses:

    SQLCached library -> in-process ThreadedServer (port 0)
      -> SQLCachedClient over TCP -> scheduler -> planner -> executors

Phases (one process, so one process holds the chip):

1. a monolithic table, ``CAPACITY 4194304``, loaded with 3,000,000 seeded
   rows through ``executemany``, warmed with synchronous ``WARMUP`` and
   then served a seeded statement mix over the wire;
2. the same rows and statements against a ``SHARDS 4 PARTITION BY
   page_id`` twin on the same chip (the lane and stacked paths).

``--chips 4`` runs only the path across chips instead: the ``SHARDS 4``
table placed on four chips (mesh mode), compared with the same
statements on a one-device twin.

The reference (:class:`Reference`) uses numpy alone, never ``repro``.
Any mismatch, any failed phase, no TPU, or a forced non-kernel
``REPRO_KERNELS`` exits non-zero without the result line. On success
the last line of stdout is one JSON object naming the device.

Run: ``python chip_smoke.py [--seed N] [--chips 4]`` on a TPU host.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro.core.daemon import SQLCached  # noqa: E402
from repro.core.execache import use_persistent_cache  # noqa: E402
from repro.core.protocol import SQLCachedClient, ThreadedServer  # noqa: E402
from repro.kernels import ops as OPS  # noqa: E402

CAPACITY = 4_194_304
N_ROWS = 3_000_000
BATCH = 65_536
MAX_SELECT = 256
KINDS = ("header", "nav", "body", "sidebar", "footer", "comment", "ad",
         "meta")
COLS = ("page_id", "user_id", "kind", "weight", "ts")
TS0 = 1_600_000_000
AVG_RTOL = 1e-4  # float32 sums in a different order than numpy's


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ----------------------------------------------------------------- data

def make_rows(seed: int, n: int) -> dict:
    """The seeded fragment rows: ~15 fragments per page, ~30 per user,
    timestamps spread so that a 100-second window holds ~30 rows."""
    rng = np.random.default_rng(seed)
    return {
        "page_id": rng.integers(0, max(1, n // 15), n).astype(np.int32),
        "user_id": rng.integers(0, max(1, n // 30), n).astype(np.int32),
        "kind": rng.integers(0, len(KINDS), n).astype(np.int32),
        "weight": rng.random(n, dtype=np.float32),
        "ts": (TS0 + rng.integers(0, max(1, n * 10 // 3), n)).astype(
            np.int32),
    }


class Reference:
    """Plain numpy model of one table: the same rows, a validity mask,
    and the semantics of each statement the traffic uses."""

    def __init__(self, rows: dict, capacity: int):
        n = len(rows["page_id"])
        self.cols = {c: np.zeros(capacity, rows[c].dtype) for c in COLS}
        for c in COLS:
            self.cols[c][:n] = rows[c]
        self.valid = np.zeros(capacity, bool)
        self.valid[:n] = True
        self.n = n

    def where(self, terms) -> np.ndarray:
        m = self.valid.copy()
        for col, op, v in terms:
            a = self.cols[col]
            if col == "kind":
                v = KINDS.index(v)
            m &= {"=": a == v, ">=": a >= v, "<=": a <= v}[op]
        return np.flatnonzero(m)

    def row(self, i: int, cols) -> tuple:
        out = []
        for c in cols:
            v = self.cols[c][i]
            out.append(KINDS[v] if c == "kind" else
                       float(v) if c == "weight" else int(v))
        return tuple(out)

    def apply(self, st: dict) -> dict:
        """The expected answer of statement ``st`` (and its effect)."""
        idx = self.where(st["terms"])
        kind = st["kind"]
        if kind == "select":
            return {"count": len(idx),
                    "rows": collections.Counter(
                        self.row(i, st["cols"]) for i in idx)}
        if kind == "count":
            return {"value": len(idx)}
        if kind == "avg":
            w = self.cols["weight"][idx].astype(np.float64)
            return {"value": float(w.mean()) if len(idx) else 0.0}
        if kind == "delete":
            self.valid[idx] = False
            return {"count": len(idx)}
        if kind == "update":
            col, v = st["set"]
            self.cols[col][idx] = np.asarray(v, self.cols[col].dtype)
            return {"count": len(idx)}
        if kind == "insert":
            i = self.n
            for c, v in zip(COLS, st["params"]):
                self.cols[c][i] = KINDS.index(v) if c == "kind" else v
            self.valid[i] = True
            self.n += 1
            return {"count": 1}
        if kind == "explain":
            return {"plan": st["plan"]}
        raise ValueError(kind)


def make_traffic(seed: int, rows: dict, table: str, n_stmts: int) -> list:
    """A seeded statement mix over a small pool of hot pages and users,
    so that DELETEs and UPDATEs change what later reads must see."""
    rng = np.random.default_rng(seed + 1)
    n = len(rows["page_id"])
    pages = rng.choice(rows["page_id"], 24).tolist() + [-7]  # -7: absent
    users = rng.choice(rows["user_id"], 24).tolist()
    span = max(1, n * 10 // 3)
    next_page = int(rows["page_id"].max()) + 1
    out = []

    def add(kind, sql, params, terms, **kw):
        out.append({"kind": kind, "sql": sql, "params": tuple(params),
                    "terms": terms, **kw})

    def pick(pool):
        return int(pool[rng.integers(len(pool))])

    for _ in range(n_stmts):
        r = rng.random()
        if r < 0.30:
            p = pick(pages)
            add("select", f"SELECT * FROM {table} WHERE page_id = ?", [p],
                [("page_id", "=", p)], cols=COLS)
        elif r < 0.42:
            u, t = pick(users), TS0 + int(rng.integers(0, span))
            add("select",
                f"SELECT * FROM {table} WHERE user_id = ? AND ts >= ?",
                [u, t], [("user_id", "=", u), ("ts", ">=", t)], cols=COLS)
        elif r < 0.47:
            u, k = pick(users), KINDS[int(rng.integers(len(KINDS)))]
            add("select",
                f"SELECT page_id, weight FROM {table} "
                "WHERE kind = ? AND user_id = ?", [k, u],
                [("kind", "=", k), ("user_id", "=", u)],
                cols=("page_id", "weight"))
        elif r < 0.55:
            lo = TS0 + int(rng.integers(0, span))
            hi = lo + int(rng.choice([100, 2000]))  # ~30 or ~600 rows
            add("select",
                f"SELECT page_id, user_id, ts FROM {table} "
                "WHERE ts BETWEEN ? AND ?", [lo, hi],
                [("ts", ">=", lo), ("ts", "<=", hi)],
                cols=("page_id", "user_id", "ts"))
        elif r < 0.62:
            u = pick(users)
            add("count", f"SELECT COUNT(*) FROM {table} WHERE user_id = ?",
                [u], [("user_id", "=", u)])
        elif r < 0.69:
            lo = TS0 + int(rng.integers(0, span))
            add("avg", f"SELECT AVG(weight) FROM {table} "
                "WHERE ts BETWEEN ? AND ?", [lo, lo + 1000],
                [("ts", ">=", lo), ("ts", "<=", lo + 1000)])
        elif r < 0.78:
            u = pick(users)
            add("delete", f"DELETE FROM {table} WHERE user_id = ?", [u],
                [("user_id", "=", u)])
        elif r < 0.86:
            p, w = pick(pages), float(np.float32(rng.random()))
            add("update", f"UPDATE {table} SET weight = ? WHERE page_id = ?",
                [w, p], [("page_id", "=", p)], set=("weight", w))
        elif r < 0.90:
            u, t = pick(users), TS0 + int(rng.integers(0, span))
            add("update", f"UPDATE {table} SET ts = ? WHERE user_id = ?",
                [t, u], [("user_id", "=", u)], set=("ts", t))
        else:
            p = next_page
            next_page += 1
            pages.append(p)
            params = [p, pick(users), KINDS[int(rng.integers(len(KINDS)))],
                      float(np.float32(rng.random())),
                      TS0 + int(rng.integers(0, span))]
            add("insert", f"INSERT INTO {table} ({', '.join(COLS)}) "
                "VALUES (?, ?, ?, ?, ?)", params, [])
    for sql, plan in (
            (f"SELECT * FROM {table} WHERE page_id = ?", "index-probe"),
            (f"SELECT * FROM {table} WHERE user_id = ? AND ts >= ?",
             "fused-scan"),
            (f"DELETE FROM {table} WHERE user_id = ?", "fused-scan"),
            (f"UPDATE {table} SET weight = ? WHERE page_id = ?",
             "index-probe")):
        add("explain", "EXPLAIN " + sql, [0] * sql.count("?"), [], plan=plan)
    return out


# ------------------------------------------------------------- checking

def mismatch(st: dict, got: dict, want: dict, limit: int) -> str | None:
    """None when the daemon's answer agrees with the reference, else why.
    Rows are compared as multisets: a sharded table returns them in
    shard order. Past ``limit`` matches only the first ``limit`` rows
    come back, and each must be one of the matches."""
    kind = st["kind"]
    if kind == "select":
        if got["count"] != want["count"]:
            return f"count {got['count']} != {want['count']}"
        cols = st["cols"]
        rows = collections.Counter(
            tuple(float(np.float32(r[c])) if c == "weight" else r[c]
                  for c in cols) for r in got["rows"])
        if sum(rows.values()) != min(want["count"], limit):
            return f"{sum(rows.values())} rows for count {want['count']}"
        if rows - want["rows"]:
            return f"rows not in the table: {list(rows - want['rows'])[:3]}"
        return None
    if kind == "count":
        return (None if got["value"] == want["value"]
                else f"value {got['value']} != {want['value']}")
    if kind == "avg":
        g, w = float(got["value"]), want["value"]
        return (None if abs(g - w) <= AVG_RTOL * max(abs(w), 1e-6)
                else f"avg {g} != {w}")
    if kind in ("delete", "update", "insert"):
        return (None if got["count"] == want["count"]
                else f"count {got['count']} != {want['count']}")
    if kind == "explain":
        plan = (got["value"] or {}).get("plan")
        return None if plan == want["plan"] else f"plan {plan}"
    raise ValueError(kind)


# --------------------------------------------------------------- phases

def create_and_load(db: SQLCached, table: str, rows: dict, *,
                    capacity: int, batch: int, shards: int = 1) -> dict:
    """CREATE the fragment table, bulk-load ``rows`` through the library's
    ``executemany``, then synchronous WARMUP. Returns the timings."""
    opts = f" SHARDS {shards} PARTITION BY page_id" if shards > 1 else ""
    db.execute(f"CREATE TABLE {table} (page_id INT, user_id INT, "
               "kind TEXT, weight FLOAT, ts INT, INDEX(page_id)) "
               f"CAPACITY {capacity} MAX_SELECT {MAX_SELECT}{opts}")
    sql = (f"INSERT INTO {table} ({', '.join(COLS)}) "
           "VALUES (?, ?, ?, ?, ?)")
    n = len(rows["page_id"])
    kinds = np.asarray(KINDS, dtype=object)[rows["kind"]]
    t0 = time.perf_counter()
    res = None
    for lo in range(0, n, batch):
        hi = min(n, lo + batch)
        res = db.executemany(sql, list(zip(
            rows["page_id"][lo:hi].tolist(), rows["user_id"][lo:hi].tolist(),
            kinds[lo:hi].tolist(), rows["weight"][lo:hi].tolist(),
            rows["ts"][lo:hi].tolist())))
    if res is not None:
        res.count  # the last batch's sync ends the load
    load_s = time.perf_counter() - t0
    db.drain_warmup(table)
    t0 = time.perf_counter()
    compiled = db.execute(f"WARMUP {table}").count
    warm_s = time.perf_counter() - t0
    errs = json.loads(db.execute(f"SHOW STATS {table}").value)[
        "executors"]["warmup_errors"]
    if errs:
        raise RuntimeError(f"background warm-up failed: {errs[0]}")
    return {"rows": n, "load_s": load_s, "warmup_s": warm_s,
            "compiled": compiled}


def serve(addr, traffic: list, ref: Reference, limit: int) -> dict:
    """Send every statement over the wire, one round trip each, and
    compare each answer with the reference. Returns counts, the
    mismatches and the client-side latencies."""
    client = SQLCachedClient(*addr, timeout=600.0)
    lat, bad = [], []
    try:
        for st in traffic:
            t0 = time.perf_counter()
            got = client.execute(st["sql"], st["params"])
            lat.append(time.perf_counter() - t0)
            why = mismatch(st, got, ref.apply(st), limit)
            if why is not None:
                bad.append(f"{st['sql']} {st['params']}: {why}")
    finally:
        client.close()
    return {"answered": len(lat), "matched": len(lat) - len(bad),
            "mismatches": bad, "latencies": lat}


def run_table(db: SQLCached, table: str, rows: dict, traffic_seed: int, *,
              capacity: int, batch: int, n_stmts: int,
              shards: int = 1) -> dict:
    """One phase: create + load + warm ``table``, serve the seeded mix
    over TCP from an in-process server, check every answer."""
    info = create_and_load(db, table, rows, capacity=capacity, batch=batch,
                           shards=shards)
    ref = Reference(rows, capacity)
    traffic = make_traffic(traffic_seed, rows, table, n_stmts)
    for sql in dict.fromkeys(st["sql"] for st in traffic
                             if st["kind"] != "explain"):
        info["compiled"] += db.execute(
            f"WARMUP {table} LIKE '{sql}'").count
    with ThreadedServer(db=db) as srv:
        info.update(serve(srv.addr, traffic, ref, MAX_SELECT))
    info["stats"] = json.loads(db.execute(f"SHOW STATS {table}").value)
    return info


def report(name: str, info: dict) -> None:
    log(f"[{name}] rows loaded {info['rows']} in {info['load_s']:.3f} s; "
        f"warm-up {info['warmup_s']:.3f} s; executables compiled "
        f"{info['compiled']}")
    log(f"[{name}] statements answered {info['answered']}, matched "
        f"{info['matched']}")
    for m in info["mismatches"][:10]:
        log(f"[{name}] MISMATCH {m}")


def single_chip(seed: int, capacity: int = CAPACITY, n_rows: int = N_ROWS,
                batch: int = BATCH, n_stmts: int = 200) -> list:
    """The default run: the monolithic table, then its SHARDS 4 twin on
    the same device. Returns one report per phase."""
    rows = make_rows(seed, n_rows)
    db = SQLCached()
    out = [("fragments", run_table(db, "fragments", rows, seed,
                                   capacity=capacity, batch=batch,
                                   n_stmts=n_stmts))]
    out.append(("fragments_s4", run_table(db, "fragments_s4", rows, seed,
                                          capacity=capacity, batch=batch,
                                          n_stmts=n_stmts, shards=4)))
    return out


def four_chips(seed: int, capacity: int = CAPACITY, n_rows: int = N_ROWS,
               batch: int = BATCH, n_stmts: int = 200) -> list:
    """The path across chips: a SHARDS 4 table placed one lane per chip
    (mesh mode), and the same statements on an unplaced twin on one
    device. Both are checked against the reference, and the placed table
    must report four distinct devices."""
    rows = make_rows(seed, n_rows)
    placed = run_table(SQLCached(), "fragments", rows, seed,
                       capacity=capacity, batch=batch, n_stmts=n_stmts,
                       shards=4)
    devs = {s["device"] for s in placed["stats"]["per_shard"]}
    if len(devs) != 4:
        placed["mismatches"].append(f"lanes on devices {sorted(devs)}")
    twin = run_table(SQLCached(mesh_exec=False), "fragments", rows, seed,
                     capacity=capacity, batch=batch, n_stmts=n_stmts,
                     shards=4)
    return [("fragments_s4_mesh", placed), ("fragments_s4_one_device", twin)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    a = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    mode = OPS.kernel_mode()
    if mode != "kernel":
        print(f"chip_smoke: REPRO_KERNELS={mode!r} forces a non-kernel "
              "path; unset it", file=sys.stderr)
        return 2
    if len(devices) < a.chips:
        print(f"chip_smoke: --chips {a.chips} needs {a.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"kernel mode {mode}")
    log(f"compile cache {use_persistent_cache()}")
    t0 = time.perf_counter()
    phases = (four_chips if a.chips == 4 else single_chip)(a.seed)
    lat = []
    ok = True
    for name, info in phases:
        report(name, info)
        lat += info["latencies"]
        ok &= not info["mismatches"] and info["answered"] > 0
    log(f"smoke median statement latency {np.median(lat) * 1e3:.3f} ms "
        f"over {len(lat)} statements (one client, one round trip each; "
        "a smoke figure, not a benchmark)")
    log(f"total {time.perf_counter() - t0:.1f} s")
    if not ok:
        log("FAILED: answers disagree with the reference")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
