"""Pre-planned statement serving (core/execache.py): AOT executor cache,
WARMUP / CREATE-time warm-up, epoch invalidation, and the scheduler's
cold-solo admission.

The load-bearing properties:

* **zero recompiles at steady state** — after WARMUP, repeat dispatches
  of every warmed shape replay compiled executables (``compiles`` stops
  moving, ``fallbacks`` stays 0);
* **never a stale executable** — RESHARD n→m, REINDEX, RESTORE and mesh
  re-placement bump the schema epoch, which retires every entry by
  construction (the epoch is part of the entry key); FLUSH changes
  contents, not shapes, so it must NOT bump (benchmarks warm, then
  FLUSH, then measure);
* results after any invalidation match a never-cached daemon (parity).

Multi-device coverage (one lane per device) runs when >1 device is
visible — scripts/ci.sh forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import asyncio
import json

import pytest

import jax

from repro.core.daemon import SQLCached
from repro.core.execache import ExecutorCache
from repro.core.scheduler import BatchScheduler

multidev = pytest.mark.skipif(
    jax.device_count() <= 1,
    reason="needs >1 device "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _stats(db, table):
    return json.loads(db.execute(f"SHOW STATS {table}").value)["executors"]


def _mkdb(shards=4, cap=256, warmup=False):
    db = SQLCached(warmup=warmup)
    opts = f"SHARDS {shards} PARTITION BY k" if shards > 1 else ""
    db.execute(f"CREATE TABLE t (k INT, v INT, INDEX(k)) "
               f"CAPACITY {cap} {opts}")
    return db


# ------------------------------------------------------- cache unit tests

def test_cache_get_memoizes_and_bump_retires():
    c = ExecutorCache()
    built = []

    def builder():
        built.append(1)
        return lambda *a: a

    e1 = c.get(("select", "shape"), builder)
    e2 = c.get(("select", "shape"), builder)
    assert e1 is e2 and len(built) == 1
    c.note_sig(("select", "shape", None, "mono", ("dev", 0)))
    assert c.has_sig(("select", "shape", None, "mono", ("dev", 0)))
    old_epoch = c.epoch
    assert c.bump() == old_epoch + 1
    # same key, new epoch -> rebuilt entry; sigs gone with it
    e3 = c.get(("select", "shape"), builder)
    assert e3 is not e1 and len(built) == 2
    assert not c.has_sig(("select", "shape", None, "mono", ("dev", 0)))


def test_cache_stats_shape():
    c = ExecutorCache()
    s = c.stats_dict()
    assert set(s) == {"cached", "entries", "epoch", "hits", "misses",
                      "compiles", "fallbacks", "compile_ms_total",
                      "warmup_errors"}
    assert s["cached"] == 0 and s["epoch"] == 0 and s["warmup_errors"] == []


# ----------------------------------------------------- WARMUP + zero-recompile

def test_warmup_counts_then_idempotent():
    db = _mkdb()
    r = db.execute("WARMUP t")
    assert r.count > 0
    assert db.execute("WARMUP t").count == 0
    assert db.execute(
        "WARMUP t LIKE 'SELECT COUNT(*) FROM t WHERE k = ?'").count > 0
    assert db.execute(
        "WARMUP t LIKE 'SELECT COUNT(*) FROM t WHERE k = ?'").count == 0


def test_zero_recompiles_after_warmup():
    """The tentpole acceptance property: 3 repeat dispatches of every
    warmed shape never compile (hits only, zero fallbacks)."""
    db = _mkdb()
    db.execute("WARMUP t")
    st0 = _stats(db, "t")
    assert st0["cached"] > 0 and st0["hits"] == 0
    for rep in range(3):
        db.execute("INSERT INTO t (k, v) VALUES (?, ?)", (rep, rep * 10))
        db.execute("SELECT * FROM t WHERE k = ?", (rep,))
        db.execute("DELETE FROM t WHERE k = ?", (rep,))
    st1 = _stats(db, "t")
    assert st1["compiles"] == st0["compiles"]
    assert st1["misses"] == 0 and st1["fallbacks"] == 0
    assert st1["hits"] == 9


def test_zero_recompiles_mono():
    db = _mkdb(shards=1)
    db.execute("WARMUP t")
    st0 = _stats(db, "t")
    for rep in range(3):
        db.execute("INSERT INTO t (k, v) VALUES (?, ?)", (rep, rep))
        db.execute("SELECT * FROM t WHERE k = ?", (rep,))
        db.execute("DELETE FROM t WHERE k = ?", (rep,))
    st1 = _stats(db, "t")
    assert st1["compiles"] == st0["compiles"]
    assert st1["misses"] == 0 and st1["fallbacks"] == 0


def test_create_time_background_warmup():
    db = _mkdb(warmup=True)
    db.drain_warmup("t")
    st = _stats(db, "t")
    assert st["cached"] > 0 and st["warmup_errors"] == []
    # everything the canonical set covers is already planned
    assert db.execute("WARMUP t").count == 0


def test_background_warmup_failure_is_reported(monkeypatch):
    """A compile the backend refuses during CREATE-time warm-up must show
    in SHOW STATS, not vanish until the first live statement."""
    def refuse(self, t, stmt):
        raise RuntimeError("compile refused")

    monkeypatch.setattr(SQLCached, "_warm_statement", refuse)
    db = _mkdb(warmup=True)
    db.drain_warmup("t")
    errs = _stats(db, "t")["warmup_errors"]
    assert len(errs) == 1 and "RuntimeError: compile refused" in errs[0]


def test_explain_reports_preplanned():
    db = _mkdb()
    e = json.loads(db.execute(
        "EXPLAIN SELECT * FROM t WHERE k = ?").value)
    assert e["preplanned"] is False
    db.execute("WARMUP t")
    e = json.loads(db.execute(
        "EXPLAIN SELECT * FROM t WHERE k = ?").value)
    assert e["preplanned"] is True
    # a shape outside the canonical set stays unplanned
    e = json.loads(db.execute(
        "EXPLAIN SELECT * FROM t WHERE v = ?").value)
    assert e["preplanned"] is False
    ei = json.loads(db.execute(
        "EXPLAIN INSERT INTO t (k, v) VALUES (?, ?)").value)
    assert ei["preplanned"] is True


def test_warmup_unknown_table_errors():
    from repro.core.sqlparse import SQLError
    db = SQLCached(warmup=False)
    with pytest.raises(SQLError):
        db.execute("WARMUP nope")


# ------------------------------------------------------------ invalidation

def _fill(db, n=24):
    db.executemany("INSERT INTO t (k, v) VALUES (?, ?)",
                   [(i % 12, i) for i in range(n)])


def _snapshot(db):
    rows = db.execute("SELECT k, v FROM t").rows
    return sorted((r["k"], r["v"]) for r in rows)


def test_reshard_never_serves_stale():
    db = _mkdb(shards=4)
    db.execute("WARMUP t")
    _fill(db)
    before = _snapshot(db)
    st0 = _stats(db, "t")
    db.execute("ALTER TABLE t RESHARD 2")
    st1 = _stats(db, "t")
    assert st1["epoch"] == st0["epoch"] + 1
    assert st1["cached"] == 0 and st1["entries"] == 0
    # post-reshard traffic runs against 2-shard avals — parity with a
    # never-cached daemon proves no 4-shard executable survived
    assert _snapshot(db) == before
    db.execute("INSERT INTO t (k, v) VALUES (?, ?)", (99, 990))
    assert db.execute("SELECT v FROM t WHERE k = ?", (99,)).rows == [
        {"v": 990}]


def test_reindex_bumps_epoch():
    db = _mkdb()
    db.execute("WARMUP t")
    _fill(db)
    st0 = _stats(db, "t")
    db.execute("REINDEX t")
    st1 = _stats(db, "t")
    assert st1["epoch"] == st0["epoch"] + 1
    assert db.execute("SELECT COUNT(*) FROM t WHERE k = ?", (3,)).value == 2


def test_flush_keeps_epoch_and_executables():
    """FLUSH drops rows, not shapes: benchmarks warm, FLUSH, then
    measure — invalidating here would throw the warm-up away."""
    db = _mkdb()
    db.execute("WARMUP t")
    _fill(db)
    st0 = _stats(db, "t")
    db.execute("FLUSH t")
    assert db.execute("SELECT COUNT(*) FROM t").value == 0
    st1 = _stats(db, "t")
    assert st1["epoch"] == st0["epoch"]
    # FLUSH's own executor joins the cache; nothing is retired
    assert st1["cached"] >= st0["cached"]
    # warmed executables still replay, still no recompiles
    db.execute("INSERT INTO t (k, v) VALUES (?, ?)", (1, 2))
    db.execute("SELECT * FROM t WHERE k = ?", (1,))
    st2 = _stats(db, "t")
    assert st2["compiles"] == st1["compiles"] and st2["fallbacks"] == 0


def test_restore_bumps_epoch(tmp_path):
    db = _mkdb()
    db.execute("WARMUP t")
    _fill(db)
    before = _snapshot(db)
    db.execute(f"CHECKPOINT t TO '{tmp_path}/snap'")
    db.execute("FLUSH t")
    st0 = _stats(db, "t")
    db.execute(f"RESTORE t FROM '{tmp_path}/snap'")
    st1 = _stats(db, "t")
    assert st1["epoch"] == st0["epoch"] + 1
    assert _snapshot(db) == before


def test_drop_create_gets_fresh_cache():
    db = _mkdb()
    db.execute("WARMUP t")
    assert _stats(db, "t")["cached"] > 0
    db.execute("DROP TABLE t")
    db.execute("CREATE TABLE t (k INT, v INT, INDEX(k)) CAPACITY 64")
    assert _stats(db, "t")["cached"] == 0


# ----------------------------------------------------------- multi-device

@multidev
def test_warmup_covers_every_lane_device():
    """Per-device warm-up at CREATE closes the PR 7 follow-up: the FIRST
    pruned hit on EVERY lane device replays, never compiles."""
    n = jax.device_count()
    db = SQLCached(warmup=False)
    db.execute(f"CREATE TABLE t (k INT, v INT, INDEX(k)) CAPACITY 1024 "
               f"SHARDS {n} PARTITION BY k")
    db.execute("WARMUP t")
    st0 = _stats(db, "t")
    # canonical set: INSERT + eq-SELECT + eq-DELETE, each per device
    assert st0["cached"] >= 3 * n
    for rep in range(3):
        for k in range(n):  # k routes shard k -> lane k -> device k
            db.execute("INSERT INTO t (k, v) VALUES (?, ?)", (k, rep))
            db.execute("SELECT * FROM t WHERE k = ?", (k,))
            db.execute("DELETE FROM t WHERE k = ?", (k,))
    st1 = _stats(db, "t")
    assert st1["compiles"] == st0["compiles"]
    assert st1["misses"] == 0 and st1["fallbacks"] == 0
    assert st1["hits"] == 9 * n


@multidev
def test_mesh_replacement_invalidates():
    """RESHARD across device counts re-places lanes on a new mesh — the
    old mesh's executables must be unreachable afterwards."""
    n = jax.device_count()
    db = SQLCached(warmup=False)
    db.execute(f"CREATE TABLE t (k INT, v INT, INDEX(k)) CAPACITY 1024 "
               f"SHARDS {n} PARTITION BY k")
    db.execute("WARMUP t")
    _fill(db)
    before = _snapshot(db)
    st0 = _stats(db, "t")
    db.execute(f"ALTER TABLE t RESHARD {max(1, n // 2)}")
    st1 = _stats(db, "t")
    assert st1["epoch"] == st0["epoch"] + 1 and st1["cached"] == 0
    assert _snapshot(db) == before
    assert _stats(db, "t")["fallbacks"] == 0


# ------------------------------------------------------ scheduler admission

def test_scheduler_solos_cold_groups():
    async def main():
        db = _mkdb(shards=1)
        sched = BatchScheduler(db)
        await sched.start()
        # nothing warmed: the two differently-shaped groups are cold and
        # must be kept out of warm waves even though they would commute
        futs = [sched.submit("INSERT INTO t (k, v) VALUES (?, ?)", (1, 1)),
                sched.submit("SELECT v FROM t WHERE k = ?", (1,))]
        await asyncio.gather(*futs)
        assert sched.stats["cold_solo"] >= 2
        base = sched.stats["cold_solo"]
        db.execute("WARMUP t")
        futs = [sched.submit("INSERT INTO t (k, v) VALUES (?, ?)", (2, 2)),
                sched.submit("SELECT v FROM t WHERE k = ?", (2,))]
        await asyncio.gather(*futs)
        # warmed shapes are admitted into waves again
        assert sched.stats["cold_solo"] == base
        await sched.stop()

    asyncio.run(main())


def test_group_warm_tolerates_unknown():
    db = _mkdb(shards=1)
    # admin / unknown shapes must never be reported cold
    assert db.group_warm(None, []) is True
    assert db.group_warm(db.shape_key("FLUSH t"), []) is True
    sh = db.shape_key("SELECT * FROM t WHERE k = ?")
    assert db.group_warm(sh, [(1,)]) is False
    db.execute("WARMUP t")
    assert db.group_warm(sh, [(1,)]) is True
