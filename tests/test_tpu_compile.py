"""Compile the daemon's Pallas kernels for a TPU v5e that is described,
not attached, at a table size a cache deployment holds (2^22 rows), and
a ``SHARDS 4`` table's executors for the four chips of a v5e host.

What interpret mode cannot show, the TPU compiler refuses here: blocks
off the (8, 128) tiling, ops Mosaic cannot lower, programs that do not
fit the chip. Each test asserts that the kernel really is in the
program (``tpu_custom_call``), so a silent fall-back to jnp fails too.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file. JAX's persistent compilation cache stays off
around these compiles (an entry written without a chip cannot be read
back)."""
import functools
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import daemon as D
from repro.kernels import hashidx as HX
from repro.kernels import relscan as RS

CAP = 1 << 22
LIMIT = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("want_ids", [True, False])
@pytest.mark.parametrize("ops", [("==",), ("==", ">=", "<=", "!=")])
def test_relscan_compiles(spec, want_ids, ops):
    col = spec((CAP,), jnp.int32)

    def fn(cols, valid, vals):
        return RS.relscan(cols, valid, vals, ops=ops, limit=LIMIT,
                          want_ids=want_ids)

    compiled, hlo = _compile(fn, (col,) * len(ops), spec((CAP,), jnp.bool_),
                             spec((len(ops),), jnp.int32))
    assert hlo.count("tpu_custom_call") >= (2 if want_ids else 1)
    # inputs in, bitmap + ids out: no capacity-sized scratch beyond that
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_hash_probe_compiles(spec):
    nb = HX.n_buckets_for(CAP)
    idx = spec((nb, HX.BUCKET_CAP), jnp.int32)
    compiled, hlo = _compile(HX.probe, idx, idx, spec((1,), jnp.int32))
    assert "tpu_custom_call" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _elements(dims: str) -> int:
    """Element count of an MLIR shape prefix such as ``4096x128x``."""
    return math.prod(int(d) for d in dims.split("x") if d)


@pytest.mark.parametrize("n", [1, 8])
def test_insert_upkeep_reads_its_buckets_not_every_lane(n):
    """The narrow-INSERT index upkeep at a 131,072-row table (4,096 x
    128 index lanes) works on the batch's own bucket rows: no gather
    yields one element per index lane, and no op takes or makes a mask
    over every lane. Lowered only, so it needs no TPU."""
    cap = 131072
    nb = HX.n_buckets_for(cap)
    lanes = nb * HX.BUCKET_CAP
    sds = jax.ShapeDtypeStruct
    table = sds((nb, HX.BUCKET_CAP), jnp.int32)
    idx = {"rid": table, "key": table, "stale": sds((), jnp.int32)}
    vec = sds((n,), jnp.int32)
    text = jax.jit(HX.insert_update_batched).lower(
        idx, vec, vec, vec, sds((n,), jnp.bool_),
        sds((cap,), jnp.bool_)).as_text()
    gathers = re.findall(r'"stablehlo\.gather".*-> tensor<((?:\d+x)*)\w+>',
                         text)
    assert gathers
    assert max(_elements(d) for d in gathers) < lanes
    masks = re.findall(r"tensor<((?:\d+x)+)i1>", text)
    assert lanes not in {_elements(d) for d in masks}


def test_vmapped_compaction_fits(spec):
    """The micro-batch executors vmap the jnp compaction over statements:
    its working set must grow with capacity, not capacity x limit."""
    fn = jax.vmap(lambda m: RS.compact(m, limit=LIMIT))
    compiled, _ = _compile(fn, spec((16, CAP), jnp.bool_))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_sharded_table_executors_compile_for_four_chips(topo, monkeypatch):
    """A SHARDS 4 table placed one lane per chip: its fan-out (mesh)
    executors and its per-lane executors compile for the 2x2 host. The
    table is created unplaced here (no chip to hold it) and then given
    the described mesh, so WARMUP lowers from abstract state carrying
    the mesh shardings."""
    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]), ("lane",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    db = D.SQLCached(warmup=False)
    db.execute("CREATE TABLE t (k INT, u INT, ts INT, INDEX(k)) "
               "CAPACITY 65536 MAX_SELECT 256 SHARDS 4 PARTITION BY k")
    t = db.tables["t"]
    t.mesh = mesh
    monkeypatch.setattr(D, "lane_mesh_for", lambda n, d=None: mesh)
    for sql in ("SELECT * FROM t WHERE u = ? AND ts >= ?",  # fan-out
                "DELETE FROM t WHERE u = ?",
                "SELECT * FROM t WHERE k = ?"):              # one lane
        assert db.execute(f"WARMUP t LIKE '{sql}'").count > 0, sql
    placements = {p for e in t.execs._entries.values() for p in e.compiled}
    assert ("mesh", (0, 1, 2, 3)) in placements
    assert {("dev", i) for i in range(4)} <= placements


def test_executors_and_kernels_carry_their_names(topo, monkeypatch):
    """On a profiler's ``XLA Modules`` line a program is known by its jit
    name, and a kernel by its ``pallas_call`` name: a lane SELECT by its
    index probe, a lane scan, and an INSERT batch lower for the v5e as
    ``jit_sqlcached_<kind>_<path>`` with their kernels named, and the
    INSERT's index upkeep carries the ``hashidx_upkeep`` scope. Lowered,
    not compiled: ``ExecEntry.warm`` keeps the text."""
    from repro.core import execache as E
    texts: dict[str, str] = {}

    def lower_only(self, placement, args):
        if placement in self.compiled:
            return False
        low = self.jitted.lower(*args)
        texts.setdefault(self.jitted.__name__, low.as_text(debug_info=True))
        self.compiled[placement] = None
        return True

    monkeypatch.setenv("REPRO_KERNELS", "kernel")
    monkeypatch.setattr(E.ExecEntry, "warm", lower_only)
    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]), ("lane",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    db = D.SQLCached(warmup=False)
    db.execute("CREATE TABLE t (k INT, u INT, INDEX(k)) CAPACITY 65536 "
               "MAX_SELECT 256 SHARDS 4 PARTITION BY u")
    db.tables["t"].mesh = mesh
    monkeypatch.setattr(D, "lane_mesh_for", lambda n, d=None: mesh)
    for sql in ("SELECT * FROM t WHERE k = ? AND u = ?",
                "SELECT k FROM t WHERE u = ?",
                "INSERT INTO t (k, u) VALUES (?, ?)"):
        assert db.execute(f"WARMUP t LIKE '{sql}'").count > 0, sql
    want = {"sqlcached_select_probe": ["hashidx_probe"],
            "sqlcached_select_scan": ["relscan_scan", "relscan_compact"],
            "sqlcached_insert_batch": []}
    assert want.keys() <= texts.keys()
    for name, kernels in want.items():
        assert f"module @jit_{name}" in texts[name]
        for k in kernels:
            assert f'kernel_name = "{k}"' in texts[name], (name, k)
    assert "hashidx_upkeep" in texts["sqlcached_insert_batch"]
    assert "hashidx_upkeep" not in texts["sqlcached_select_scan"]
