"""Observability tests (PR 9): atomic host counters, log2 latency
histograms + exact merge, per-stage trace spans over the wire, SHOW
METRICS / SHOW SLOW / SHOW STATS roll-up, EXPLAIN ANALYZE stage
accounting vs wall-clock, the slow-statement log, the REPRO_TELEMETRY
kill switch, mesh exec-mode attribution, and ClusterClient.metrics()
histogram-merge exactness (no percentile-of-percentile)."""
import json
import math
import threading
import time

import jax
import pytest

from repro.core import telemetry as TEL
from repro.core.cluster import ClusterClient
from repro.core.daemon import SQLCached
from repro.core.protocol import SQLCachedClient, ThreadedServer

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")


# ------------------------------------------------ host-side primitives

def test_counters_exact_under_8_threads():
    """Satellite: one shared helper, exact totals under 8 concurrent
    writers (the GIL alone does not make `d[k] += 1` atomic)."""
    c = TEL.Counters({"n": 0})
    N = 20_000

    def hammer(i):
        for j in range(N):
            c.add("n")
            c.add(f"t{i % 2}", 2)
            c.max("peak", j)

    ts = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c["n"] == 8 * N
    assert c["t0"] == c["t1"] == 4 * N * 2
    assert c["peak"] == N - 1
    # mapping-read protocol (existing tests/benches read stats this way)
    snap = dict(c)
    assert snap["n"] == 8 * N and "peak" in c and len(c) == 4
    assert c == snap


def test_histogram_buckets_and_percentiles():
    assert TEL.bucket_of(0) == 0 and TEL.bucket_of(1) == 0
    assert TEL.bucket_of(2) == 1 and TEL.bucket_of(3) == 1
    assert TEL.bucket_of(1024) == 10 and TEL.bucket_of(1 << 60) \
        == TEL.N_BUCKETS - 1
    lo, hi = TEL.bucket_bounds(10)
    assert lo == 1024 and hi == 2048
    h = TEL.Histogram()
    assert h.percentile(0.5) is None  # empty histogram has no rank
    for us in (100, 100, 100, 100, 100, 100, 100, 100, 100, 100_000):
        h.record(us)
    assert h.n == 10
    # p50 lands in the [64, 128) bucket; geometric midpoint stays inside
    p50 = h.percentile(0.5)
    assert 64 <= p50 <= 128
    # p999 must land in the tail bucket, not be dragged down by the mass
    assert h.percentile(0.999) > 50_000


def test_histogram_merge_is_exact():
    """Merging = summing bucket counts; percentiles recomputed from the
    merged histogram equal those of the combined population (no
    percentile-of-percentile averaging)."""
    a, b, whole = TEL.Histogram(), TEL.Histogram(), TEL.Histogram()
    vals_a = [3, 17, 900, 900, 4096]
    vals_b = [1, 2, 250_000, 900]
    for v in vals_a:
        a.record(v)
        whole.record(v)
    for v in vals_b:
        b.record(v)
        whole.record(v)
    m = TEL.Histogram()
    m.merge(a.sparse())
    m.merge(b.sparse())
    assert m.counts == whole.counts
    for q in (0.5, 0.9, 0.99, 0.999):
        assert m.percentile(q) == whole.percentile(q)


def test_trace_spans_are_monotonic_deltas():
    tr = TEL.Trace()
    tr.mark("wire")
    time.sleep(0.002)
    tr.mark("parse")
    d = tr.to_dict()
    stages = dict(tr.spans)
    assert set(stages) == {"wire", "parse"}
    assert stages["parse"] >= 1_000  # the 2 ms sleep, in µs
    assert d["total_us"] >= stages["parse"]
    assert all(v >= 0 for _, v in tr.spans)


def test_merge_reports_sums_buckets_and_counts():
    db = None
    r1 = {"shapes": {"t.select": {
        "count": 3, "buckets": {"5": 2, "9": 1},
        "stages": {"execute": {"total_us": 30.0, "count": 3}},
        "modes": {"lane": 3}, "cache": {"hit": 3}}}}
    r2 = {"shapes": {"t.select": {
        "count": 2, "buckets": {"5": 1, "20": 1},
        "stages": {"execute": {"total_us": 70.0, "count": 2}},
        "modes": {"mesh": 2}, "cache": {"compile": 1}}}}
    merged = TEL.merge_reports([r1, r2])
    assert db is None and merged["nodes"] == 2
    sh = merged["shapes"]["t.select"]
    assert sh["count"] == 5
    assert sh["buckets"] == {"5": 3, "9": 1, "20": 1}
    assert sh["stages"]["execute"]["total_us"] == 100.0
    assert sh["modes"] == {"lane": 3, "mesh": 2}
    assert sh["cache"] == {"hit": 3, "compile": 1}
    # percentile recomputed from merged buckets: rank 3 of 5 → bucket 5
    lo, hi = TEL.bucket_bounds(5)
    assert lo <= sh["p50_us"] <= hi


# ------------------------------------------------------- wire surface

@pytest.fixture()
def server():
    with ThreadedServer() as s:
        yield s


@pytest.fixture()
def client(server):
    c = SQLCachedClient(*server.addr)
    yield c
    c.close()


def _traffic(client, n=16):
    client.execute("CREATE TABLE t (k INT, w FLOAT, INDEX (k)) CAPACITY 128")
    p = client.pipeline()
    for i in range(n):
        p.execute("INSERT INTO t (k, w) VALUES (?, ?)", [i, float(i)])
    for i in range(n):
        p.execute("SELECT w FROM t WHERE k = ? LIMIT 1", [i])
    p.collect()


def test_show_metrics_shapes_stages_and_filter(server, client):
    _traffic(client, n=16)
    rep = client.execute("SHOW METRICS")["value"]
    assert rep["enabled"] is True and rep["bucket_base"] == 2
    shapes = rep["shapes"]
    assert shapes["t.insert"]["count"] == 16
    assert shapes["t.select"]["count"] == 16
    sel = shapes["t.select"]
    # every serving stage is attributed, and bucket counts are exact
    assert {"wire", "parse", "queue", "lock", "execute", "render"} \
        <= set(sel["stages"])
    assert sel["stages"]["execute"]["count"] == 16
    assert sum(sel["buckets"].values()) == 16
    assert sel["p50_us"] > 0 and sel["p999_us"] >= sel["p50_us"]
    # exec-mode + executor-cache attribution rides on the same shape
    assert sum(sel["modes"].values()) == 16
    assert sel["cache"].get("compile", 0) >= 1  # cold first hit compiled
    # every select is attributed exactly one cache outcome (a grouped
    # dispatch fans its single compile/hit event out to all members)
    ev = sum(n for k, n in sel["cache"].items() if k != "compile_ms")
    assert ev == 16
    # warm sequential re-runs are hits
    for i in range(4):
        client.execute("SELECT w FROM t WHERE k = ? LIMIT 1", [i])
    sel = client.execute("SHOW METRICS t")["value"]["shapes"]["t.select"]
    assert sel["cache"].get("hit", 0) >= 3
    # table filter drops foreign shapes
    r2 = client.execute("SHOW METRICS t")
    assert set(r2["value"]["shapes"]) == {"t.insert", "t.select", "t.admin"}
    with pytest.raises(RuntimeError):
        client.execute("SHOW METRICS nope")


def test_show_metrics_percentile_vs_measured_latency(server, client):
    """Acceptance: server-side p50 agrees with the client-measured
    steady-state median within bucket resolution (log2 buckets +
    client-side socket overhead ⇒ compare within a 4x band)."""
    _traffic(client, n=8)
    lats = []
    for i in range(32):
        t0 = time.perf_counter()
        client.execute("SELECT w FROM t WHERE k = ? LIMIT 1", [i % 8])
        lats.append((time.perf_counter() - t0) * 1e6)
    lats.sort()
    client_p50 = lats[len(lats) // 2]
    rep = client.execute("SHOW METRICS t")["value"]
    sel = rep["shapes"]["t.select"]
    # drop the cold-compile outlier's influence by using p50 only
    assert sel["p50_us"] <= client_p50 * 4
    assert sel["p50_us"] >= client_p50 / 4


def test_show_metrics_prom_format(server, client):
    _traffic(client, n=4)
    text = client.execute("SHOW METRICS t FORMAT 'prom'")["value"]
    assert isinstance(text, str)
    assert "sqlcached_uptime_seconds" in text
    assert 'sqlcached_statement_latency_us_bucket{shape="t.select"' in text
    assert 'le="+Inf"' in text
    assert "sqlcached_statement_latency_us_count" in text
    assert "sqlcached_stage_us_total" in text
    # cumulative buckets: +Inf count equals the _count sample
    inf = [ln for ln in text.splitlines()
           if ln.startswith("sqlcached_statement_latency_us_bucket")
           and 'shape="t.select"' in ln and 'le="+Inf"' in ln]
    cnt = [ln for ln in text.splitlines()
           if ln.startswith("sqlcached_statement_latency_us_count")
           and 'shape="t.select"' in ln]
    assert len(inf) == 1 and len(cnt) == 1
    assert inf[0].rsplit(" ", 1)[1] == cnt[0].rsplit(" ", 1)[1]
    with pytest.raises(RuntimeError):
        client.execute("SHOW METRICS t FORMAT 'xml'")


def test_explain_analyze_stages_sum_to_wall_clock(server, client):
    """Acceptance: EXPLAIN ANALYZE's per-stage spans account for the
    statement's wall-clock wire latency within 10% — measured on a cold
    (compile-dominated) statement so the comparison is meaningful."""
    client.execute(
        "CREATE TABLE ea (k INT, w FLOAT, INDEX (k)) CAPACITY 64")
    client.execute("INSERT INTO ea (k, w) VALUES (?, ?)", [1, 2.5])
    t0 = time.perf_counter()
    r = client.execute("EXPLAIN ANALYZE SELECT w FROM ea WHERE k = ?", [1])
    wall_us = (time.perf_counter() - t0) * 1e6
    info = r["value"]
    assert info["analyze"] is True
    assert info["plan"]["table"] == "ea"
    assert {"execute", "render"} <= set(info["stages"])
    span_sum = sum(info["stages"].values())
    assert span_sum <= info["total_us"] * 1.001
    # cold first hit: compile dominates, so spans ≈ wall-clock
    assert info["cache"] in ("compile", "hit", "fallback")
    assert span_sum >= 0.9 * (wall_us - 5_000) or wall_us < 20_000
    assert info["total_us"] <= wall_us * 1.10
    # warm re-run still carries the full span tree and the exec mode
    r2 = client.execute("EXPLAIN ANALYZE SELECT w FROM ea WHERE k = ?", [1])
    assert r2["value"]["exec_mode"] in ("lane", "stacked", "mesh", "mono")
    assert r2["value"]["cache"] == "hit"


def _served_mix(server, conns=4, n=24):
    """Pipelined INSERT / point SELECT / COUNT / DELETE from ``conns``
    connections at once, so groups, waves and in-order waits all occur."""
    boot = SQLCachedClient(*server.addr)
    boot.execute("CREATE TABLE cw (k INT, u INT, INDEX (k)) CAPACITY 512")
    boot.close()

    def worker(i):
        c = SQLCachedClient(*server.addr)
        p = c.pipeline()
        for j in range(n):
            k = i * n + j
            p.execute("INSERT INTO cw (k, u) VALUES (?, ?)", [k, k % 5])
            p.execute("SELECT u FROM cw WHERE k = ?", [k])
            p.execute("SELECT COUNT(*) FROM cw WHERE u = ?", [j % 5])
            if j % 4 == 3:
                p.execute("DELETE FROM cw WHERE k = ?", [k - 1])
        p.collect()
        c.close()

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(conns)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()


def test_child_spans_lie_inside_their_parents(server):
    """The four child spans split ``queue`` and ``render`` without moving
    them: per statement the six stages still run end to end from wire
    receipt to the render flush, ``queue`` is exactly ``cut_wait`` +
    ``wave_wait``, and ``respond_wait`` + ``device_wait`` fit inside
    ``render``; SHOW METRICS counts each child once per statement of
    its parent, so the means are per statement."""
    server.server.db.telemetry.slow_ms = 0.0   # keep every span tree
    _served_mix(server)
    c = SQLCachedClient(*server.addr)
    shapes = c.execute("SHOW METRICS cw")["value"]["shapes"]
    slow = c.execute("SHOW SLOW")["rows"]
    c.close()
    for kind in ("insert", "select", "delete"):
        st = shapes[f"cw.{kind}"]["stages"]
        for child, parent in TEL.CHILDREN.items():
            assert st[child]["count"] == st[parent]["count"] > 0
        assert (st["cut_wait"]["total_us"] + st["wave_wait"]["total_us"]
                <= st["queue"]["total_us"] + 1.0)
        assert (st["respond_wait"]["total_us"]
                + st["device_wait"].get("total_us", 0.0)
                <= st["render"]["total_us"] + 1.0)
    served = [e for e in slow if e["table"] == "cw"]
    assert len(served) >= TEL.Telemetry.SLOW_SIZE - 4   # the newest
    for e in served:
        stages, ch = e["stages"], e["children"]
        assert set(stages) == set(TEL.STAGES)
        assert set(ch) == set(TEL.CHILDREN)
        # the parents partition the statement's life, as before children
        assert sum(stages.values()) == pytest.approx(e["total_us"], abs=1.0)
        assert ch["cut_wait"] + ch["wave_wait"] == pytest.approx(
            stages["queue"], abs=0.3)
        assert ch["respond_wait"] + ch["device_wait"] <= \
            stages["render"] + 0.3
        assert min(ch.values()) >= 0


def test_explain_analyze_reports_children_apart(server, client):
    """EXPLAIN ANALYZE lists the child spans beside the six stages and
    never adds them into the stages, which still sum to the total."""
    client.execute(
        "CREATE TABLE ec (k INT, w FLOAT, INDEX (k)) CAPACITY 64")
    client.execute("INSERT INTO ec (k, w) VALUES (?, ?)", [1, 2.5])
    info = client.execute(
        "EXPLAIN ANALYZE SELECT w FROM ec WHERE k = ?", [1])["value"]
    assert set(info["stages"]) <= set(TEL.STAGES)
    assert set(info["children"]) == set(TEL.CHILDREN)
    assert sum(info["stages"].values()) <= info["total_us"] * 1.001
    ch = info["children"]
    assert ch["cut_wait"] + ch["wave_wait"] <= info["stages"]["queue"] + 0.3
    assert ch["respond_wait"] + ch["device_wait"] <= \
        info["stages"]["render"] + 0.3


def _annotations(trace_dir) -> list:
    """(name, start_ns, end_ns, stats) of every ``sqlcached.*`` event."""
    import pathlib
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).glob(
        "plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sqlcached."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def test_profiler_sees_the_spans_of_each_statement(tmp_path):
    """Inside a profiler session every stage and child of a served
    statement is a ``sqlcached.<stage>`` annotation carrying its trace id
    and (once parsed) kind, children inside their parents; each dispatch
    is one ``sqlcached.dispatch`` span naming its statements and group
    size."""
    with ThreadedServer() as s:
        s.server.db.telemetry.slow_ms = 0.0
        c = SQLCachedClient(*s.addr)
        c.execute("CREATE TABLE pa (k INT, u INT, INDEX (k)) CAPACITY 128")
        jax.profiler.start_trace(str(tmp_path))
        try:
            p = c.pipeline()
            for i in range(8):
                p.execute("INSERT INTO pa (k, u) VALUES (?, ?)", [i, i])
                p.execute("SELECT u FROM pa WHERE k = ?", [i])
            p.collect()
            slow = c.execute("SHOW SLOW")["rows"]
        finally:
            jax.profiler.stop_trace()
        c.close()
    served = {e["id"]: e for e in slow
              if e["table"] == "pa" and e["kind"] != "admin"}
    assert len(served) == 16
    spans: dict = {}
    dispatched = set()
    for name, a, b, stats in _annotations(tmp_path):
        if name == "sqlcached.dispatch":
            ids = {int(x) for x in str(stats["ids"]).split(";")}
            assert stats["group"] == len(ids)
            dispatched |= ids
            continue
        tid = stats["id"]
        if tid in served:
            # spans opened once the statement is parsed carry its kind
            if name not in ("sqlcached.wire", "sqlcached.parse"):
                assert stats["kind"] == served[tid]["kind"]
            spans.setdefault(tid, {})[name[len("sqlcached."):]] = (a, b)
    assert set(spans) == set(served) and set(served) <= dispatched
    for tid, sp in spans.items():
        assert set(TEL.STAGES) <= set(sp)
        assert {"cut_wait", "wave_wait", "respond_wait"} <= set(sp)
        for child, parent in TEL.CHILDREN.items():
            if child in sp:
                assert sp[parent][0] <= sp[child][0] <= sp[child][1] \
                    <= sp[parent][1], (tid, child)
        order = [sp[st] for st in TEL.STAGES]
        assert all(x[1] <= y[0] for x, y in zip(order, order[1:]))


def test_no_annotation_outside_a_profiler_session(server, client,
                                                  monkeypatch):
    """Without a session a statement only reads the clock: no
    annotation object is built."""
    built = []

    class Counting(TEL.TraceAnnotation):
        def __init__(self, *a, **kw):
            built.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(TEL, "TraceAnnotation", Counting)
    _traffic(client, n=8)
    assert not TEL.Trace().prof
    assert built == []


def test_show_slow_log(server, client):
    server.server.db.telemetry.slow_ms = 0.0  # everything is "slow"
    _traffic(client, n=4)
    r = client.execute("SHOW SLOW")
    assert r["count"] == len(r["rows"]) > 0
    entry = r["rows"][-1]
    assert "sql" in entry and "stages" in entry and "total_us" in entry
    assert entry["total_us"] >= 0
    # bounded ring: never more than SLOW_SIZE entries
    p = client.pipeline()
    for i in range(200):
        p.execute("SELECT w FROM t WHERE k = ? LIMIT 1", [i % 4])
    p.collect()
    r = client.execute("SHOW SLOW")
    assert r["count"] <= TEL.Telemetry.SLOW_SIZE


def test_show_stats_rollup_no_table(server, client):
    _traffic(client, n=4)
    st = client.execute("SHOW STATS")["value"]
    assert st["telemetry"] is True and st["uptime_s"] >= 0
    assert set(st["tables"]) == {"t"}
    assert st["tables"]["t"]["live_rows"] == 4
    assert st["executors"]["compiles"] >= 1
    assert st["scheduler"]["admitted"] >= 9
    assert st["server"]["statements"] >= 9
    # per-table SHOW STATS still answers (back-compat)
    st_t = client.execute("SHOW STATS t")["value"]
    assert sum(p["live_rows"] for p in st_t["per_shard"]) == 4


def test_mixed_good_bad_8_connections_exact_totals(server):
    """Satellite regression: 8 concurrent connections issuing interleaved
    good and bad statements — counters land on exact totals."""
    boot = SQLCachedClient(*server.addr)
    boot.execute("CREATE TABLE h (a INT) CAPACITY 512")
    boot.close()
    GOOD, BAD = 25, 25

    def worker(i):
        c = SQLCachedClient(*server.addr)
        p = c.pipeline()
        for j in range(GOOD):
            p.execute("INSERT INTO h (a) VALUES (?)", [i * GOOD + j])
            p.execute("SELECT a FROM nope_%d WHERE a = 1" % i)
        out = p.collect(return_exceptions=True)
        c.close()
        assert sum(isinstance(r, dict) for r in out) == GOOD
        assert sum(isinstance(r, RuntimeError) for r in out) == BAD

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stats = server.server.stats
    assert stats["errors"] == 8 * BAD
    assert stats["statements"] == 8 * GOOD + 1  # + the CREATE
    assert server.server.scheduler.stats["admitted"] == 8 * (GOOD + BAD) + 1
    assert server.server.db.live_rows("h") == 8 * GOOD
    # failed statements are histogrammed too, under their parsed shape
    rep = SQLCachedClient(*server.addr)
    shapes = rep.execute("SHOW METRICS")["value"]["shapes"]
    rep.close()
    err_total = sum(s["count"] for k, s in shapes.items()
                    if k.startswith("nope_"))
    assert err_total == 8 * BAD


@pytest.mark.parametrize("conc", ["0", "4"])
def test_metrics_under_both_scheduler_regimes(monkeypatch, conc):
    """SHOW METRICS / EXPLAIN ANALYZE / SHOW SLOW behave identically
    under serialized (REPRO_SCHED_CONCURRENCY=0) and concurrent lanes."""
    monkeypatch.setenv("REPRO_SCHED_CONCURRENCY", conc)
    with ThreadedServer() as s:
        c = SQLCachedClient(*s.addr)
        s.server.db.telemetry.slow_ms = 0.0
        _traffic(c, n=8)
        rep = c.execute("SHOW METRICS t")["value"]
        assert rep["shapes"]["t.select"]["count"] == 8
        assert rep["shapes"]["t.select"]["stages"]["lock"]["count"] == 8
        ea = c.execute(
            "EXPLAIN ANALYZE SELECT w FROM t WHERE k = ?", [3])["value"]
        assert ea["analyze"] and ea["stages"]["execute"] > 0
        assert c.execute("SHOW SLOW")["count"] > 0
        c.close()


def test_telemetry_kill_switch(monkeypatch):
    """REPRO_TELEMETRY=0: no traces, no histograms, wire still serves,
    SHOW METRICS answers with enabled=false and empty shapes."""
    monkeypatch.setenv("REPRO_TELEMETRY", "0")
    with ThreadedServer() as s:
        c = SQLCachedClient(*s.addr)
        _traffic(c, n=4)
        rep = c.execute("SHOW METRICS")["value"]
        assert rep["enabled"] is False and rep["shapes"] == {}
        assert c.execute("SHOW SLOW")["count"] == 0
        # EXPLAIN ANALYZE still works (it times its own dispatch)
        ea = c.execute(
            "EXPLAIN ANALYZE SELECT w FROM t WHERE k = ?", [1])["value"]
        assert ea["analyze"] and ea["total_us"] > 0
        assert s.server.stats["statements"] >= 9
        c.close()


@pytest.mark.skipif(jax.device_count() <= 1,
                    reason="needs >1 device for mesh execution")
def test_mesh_exec_mode_attribution():
    """Fan-out statements on a sharded table run on the mesh; SHOW
    METRICS attributes them to exec_mode 'mesh', pruned ones to 'lane'."""
    db = SQLCached(warmup=False)
    with ThreadedServer(db=db) as s:
        c = SQLCachedClient(*s.addr)
        c.execute("CREATE TABLE mt (k INT, w FLOAT, INDEX (k)) "
                  "CAPACITY 256 SHARDS %d PARTITION BY k"
                  % min(4, jax.device_count()))
        p = c.pipeline()
        for i in range(8):
            p.execute("INSERT INTO mt (k, w) VALUES (?, ?)", [i, float(i)])
        p.collect()
        for _ in range(3):
            c.execute("SELECT COUNT(*) FROM mt WHERE w < ?", [100.0])
        for i in range(3):
            c.execute("SELECT w FROM mt WHERE k = ? LIMIT 1", [i])
        modes = c.execute(
            "SHOW METRICS mt")["value"]["shapes"]["mt.select"]["modes"]
        assert modes.get("mesh", 0) >= 3
        assert modes.get("lane", 0) + modes.get("stacked", 0) >= 3
        c.close()


def test_show_metrics_is_nonblocking_snapshot():
    """Same contract as SHOW STATS: reading metrics must not replace or
    sync lane handles a concurrent dispatch is about to use."""
    db = SQLCached(warmup=False, slow_ms=1e9)
    db.execute("CREATE TABLE nb (k INT, w FLOAT, INDEX (k)) "
               "CAPACITY 128 SHARDS 2 PARTITION BY k")
    for i in range(16):
        db.execute("INSERT INTO nb (k, w) VALUES (?, ?)", (i, float(i)))
    t = db.tables["nb"]
    pending = db.execute("SELECT COUNT(*) FROM nb WHERE w < ?", (999.0,))
    before = [id(lane) for lane in t.lanes]
    rep = db.execute("SHOW METRICS nb").value
    assert json.loads(rep)["enabled"] in (True, False)
    assert [id(lane) for lane in t.lanes] == before
    assert pending.value == 16


# ----------------------------------------------------- cluster fan-out

@pytest.fixture()
def fleet():
    servers = [ThreadedServer() for _ in range(3)]
    yield servers
    for s in servers:
        s.stop()


@pytest.fixture()
def cc(fleet):
    c = ClusterClient([f"{s.addr[0]}:{s.addr[1]}" for s in fleet],
                      statement_retries=3, retry_base=0.01, retry_cap=0.05)
    yield c
    c.close()


def test_cluster_metrics_merge_exact(fleet, cc):
    """ClusterClient.metrics(): bucket counts merge by exact summation
    across nodes and percentiles are recomputed from the merged
    histogram — never averaged per-node percentiles."""
    cc.execute("CREATE TABLE m (id INT, score FLOAT, INDEX (id)) "
               "CAPACITY 512 SHARDS 2 PARTITION BY id REPLICAS 2")
    with cc.pipeline() as pl:
        for i in range(24):
            pl.execute("INSERT INTO m (id, score) VALUES (?, ?)",
                       (i, float(i)))
    for i in range(12):
        cc.execute("SELECT * FROM m WHERE id = ?", (i,))
    merged = cc.metrics("m")
    assert merged["nodes"] >= 2
    # collect the per-node ground truth directly
    per_node = []
    for s in fleet:
        c = SQLCachedClient(*s.addr)
        try:
            per_node.append(c.execute("SHOW METRICS m")["value"])
        except RuntimeError:
            pass  # table not placed on this node
        finally:
            c.close()
    for shape in ("m.insert", "m.select"):
        want_count = sum(r["shapes"][shape]["count"]
                         for r in per_node if shape in r["shapes"])
        got = merged["shapes"][shape]
        assert got["count"] == want_count
        want_buckets: dict = {}
        for r in per_node:
            for b, n in r["shapes"].get(shape, {}).get(
                    "buckets", {}).items():
                want_buckets[b] = want_buckets.get(b, 0) + n
        assert got["buckets"] == want_buckets
        assert sum(got["buckets"].values()) == want_count
        # recomputed percentile lies inside a populated bucket's span
        hist = TEL.Histogram()
        hist.merge(got["buckets"])
        assert math.isclose(hist.percentile(0.5), got["p50_us"],
                            rel_tol=1e-3)  # report rounds to 0.1 µs
    # daemon-wide (no table) fan-out asks every live ring node
    whole = cc.metrics()
    assert whole["nodes"] == 3
