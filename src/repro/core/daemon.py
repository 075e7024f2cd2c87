"""SQLCached: the cache daemon object (host-facing management plane).

Faithful structure of the paper's daemon, re-hosted on an accelerator:

- clients speak a subset of SQL (``execute``/``executemany``; optionally
  over TCP via core/protocol.py — "web-enabling");
- statements are parsed once and compiled once into jitted executors
  (the prepared-statement cache ≙ jax's compilation cache);
- TEXT values are interned host-side to int64 ids (the TPU has no strings;
  DESIGN.md §2) and re-materialized in results;
- a single mutation stream per table (functional state threading) mirrors
  the paper's single-threaded request execution — and is exactly what makes
  the pool safely usable inside pjit'd serving steps;
- the paper's third automatic expiry condition (every N cache operations)
  is fused INTO each statement executor (a device-side ``lax.cond`` on a
  host-computed flag), so auto-expiry costs zero extra dispatches.

Sync-free execution contract
----------------------------

``execute``/``executemany`` never block on the device. Every dispatch
returns a **lazy** :class:`Result`: ``count``, ``rows``, ``arrays``,
``row_ids`` and ``value`` hold device handles that materialize (one
device→host sync) on *first attribute access*; ``payloads`` and the
``*_device`` accessors are zero-copy device arrays and never sync.
Back-to-back statements therefore enqueue device work in a pipeline —
the serving engine issues several statements per tick without a single
round trip. ``execute_async`` is the same entry point under its
intent-revealing name; ``drain()`` blocks until all enqueued work for a
table (or every table) has retired. ``executemany`` additionally
micro-batches same-statement DELETE/UPDATE parameter lists into ONE
dispatch (a ``lax.scan`` over the parameter rows).

Plan-based execution
--------------------

Every WHERE is lowered ONCE by ``core/planner.plan_where`` into a plan —
IndexProbe (O(1) bucket probe of a device-resident hash index,
kernels/hashidx), FusedScan (the grid-tiled Pallas relscan) or
GenericScan (jnp masked scan) — and the table-level executors in
``core/table.py`` run that plan. The planner memoizes per statement
shape (schema x WHERE AST — the same granularity as the compiled
executor cache), and the daemon's executors, its batched probe routing
and ``EXPLAIN <stmt>`` all read through that one cache; EXPLAIN reports
the plan as a ``VALUE`` row so selection is observable from a socket
client. ``executemany`` routes
micro-batched SELECT/aggregate statements through *vmapped* index probes
(one ``lax.cond`` on index freshness hoisted outside the vmap), so W
indexed lookups cost O(W x bucket_cap) instead of O(W x capacity). The
env var ``REPRO_KERNELS`` selects ``kernel`` (TPU), ``interpret`` (kernel
body on CPU) or ``ref`` (pure-jnp oracle, the non-TPU default) — see
kernels/ops.py.

Sharded tables
--------------

``CREATE TABLE t (...) SHARDS n [PARTITION BY col]`` hash-partitions the
table across ``n`` independent shard states (``core/shards.py``), each
with its own validity mask, relscan tiles and hash indexes. The daemon
stays shape-agnostic: every ``_Table`` carries an ``eng`` module —
``core.table`` or ``core.shards`` — exposing one executor surface, and
every path below (singleton executors, the micro-batched ``executemany``
family, EXPLAIN, REINDEX, FLUSH, expiry) calls through it. Routing is
value-directed and happens inside the jitted executors: an equality on
the partition column executes on exactly ONE shard (flat latency however
many shards exist — under the vmapped batch executors each statement
routes to its own shard within one dispatch), INSERT splits its batch by
shard device-side (``kernels/ops.shard_split``), everything else fans
out via ``vmap`` over the stacked shard states and merges partials.
``EXPLAIN`` reports the shard route (``pruned [-> shard k]`` /
``fan-out x n`` / ``split x n``) next to the plan; wire examples live in
``core/protocol.py``. The partition column cannot be UPDATEd in place
(rows would land in the wrong shard — DELETE + INSERT moves them), and
LRU eviction / MAX_ROWS act per shard.

Execution lanes (PR 5)
----------------------

A sharded ``_Table`` stores its state as per-shard LANES — one
independent device handle per shard — instead of one stacked pytree.
Every dispatch picks a shape (``_exec_mode``): a statement (group)
whose shard route is provable host-side and lands on ONE shard runs
the ordinary monolithic executors against that lane only (``lane``
mode: O(shard) buffers, own donation, row ids globalized in-dispatch);
everything else stacks the lanes inside the jitted call and runs the
vmapped ``core/shards`` executors (``stacked`` mode). Lane mode is what
lets the batch scheduler overlap same-table statement groups with
disjoint shard routes — and it executes single-shard eq-DELETE
one-passes and single-shard INSERT batches on one shard's rows instead
of all of them (benchmarks/lane_bench.py: ~2.5x mixed-write throughput
over the PR-4 single-lock stacked regime). Clocks stay in LOGICAL
lockstep via lazy catch-up deltas, and a lane that missed a table-wide
op-count expiry replays it at the recorded firing time on its next
dispatch — TTL observables match the unsharded engine statement for
statement (tests/test_shard_parity.py). ``SQLCached(lane_exec=False)``
disables lane routing (every sharded statement takes the stacked
path — the PR-4 regime, kept as the bench baseline).

Mesh placement (PR 7)
---------------------

When more than one accelerator device is visible, a sharded table's
lanes are PLACED: ``launch.mesh.lane_mesh_for`` picks the largest
divisor of the shard count that fits the local device count, builds a
1-D ``("lane",)`` mesh, and each lane's state pytree is committed to
its block's device (``shards.place_lanes``). Dispatch shapes follow the
placement: a pruned (single-lane) route runs the monolithic executors
directly on that lane's device — zero cross-chip traffic, and the
device-AWARE twin of the scheduler's lane locks means disjoint-device
groups overlap; fan-out becomes a real all-device map (``mesh`` mode —
a 4th ``_exec_mode`` shape): the lanes are assembled zero-copy into one
device-sharded global array (``shards.assemble_lanes``), the vmapped
``core/shards`` executors run under ``shard_map`` (``shards._fanout``
routes every per-shard map through the placement mesh), partial results
merge via the O(n·limit) id-only wire shape as a cross-device gather,
and the output state is pinned back to the mesh and disassembled into
per-device lanes. ``ALTER TABLE .. RESHARD n`` re-splits through one
common device then RE-places on the new shard count's mesh (device
counts may differ); CHECKPOINT saves the gathered stacked layout, and
RESTORE reads the snapshot's own shard count from its meta, re-splits
through the RESHARD machinery, and places onto THIS process's mesh —
so a checkpoint round-trips across mesh sizes. ``SHOW STATS`` /
``EXPLAIN`` report per-lane device ids from host-side placement
metadata (no device sync). ``SQLCached(mesh_exec=False)`` or
``REPRO_MESH=0`` disables placement (lanes stay on the default device
— the PR-5/6 regime and the mesh bench's paired baseline).

Pre-planned executors (PR 8)
----------------------------

Every statement executor lives in a per-table :class:`ExecutorCache`
(``core/execache.py``) instead of a daemon-global dict. An entry wraps
the lazy jitted callable together with **AOT-compiled** executables
(``jitted.lower(...).compile()``) keyed by device placement, and the
serving path replays the compiled executable directly — the live jit
cache does not reuse AOT output, so a pre-planned shape never traces or
compiles at dispatch. Lifecycle:

* **key**: the old executor key (statement shape x exec mode x bucket)
  plus the cache's *schema epoch*; RESHARD / REINDEX / RESTORE (mesh
  re-placement) bump the epoch under the table lock, atomically retiring
  every compiled executable — a stale executable is unreachable by
  construction. FLUSH keeps the epoch: it changes contents, not shapes.
* **warm-up**: ``CREATE TABLE`` spawns a background thread that
  pre-compiles the canonical hot shapes (pruned eq-SELECT / INSERT /
  DELETE on the partition + index columns) for every placed lane device,
  from avals derived off the schema — no real state, no clock ticks, no
  lock traffic. ``WARMUP t [LIKE '<stmt>']`` does the same synchronously
  for operator-chosen shapes (the cluster tier issues it after
  ``add_node`` bootstrap); ``drain_warmup()`` joins the background pass.
* **observability**: ``SHOW STATS t`` reports the ``executors`` block
  (cached/compiles/compile_ms_total/hits/misses), ``EXPLAIN <stmt>``
  reports ``preplanned`` from the host-side signature set (never a
  device sync), and the batch scheduler's admission hook
  (:meth:`SQLCached.group_warm`) keeps groups whose executors are
  still cold out of warm waves, so a compile can never stall commuting
  groupmates.

Observability (PR 9)
--------------------

Host-side serving telemetry (``core/telemetry.py``) threads a per-
statement trace context through the whole serving path: stamped at wire
receipt, span-marked at every stage boundary (wire → parse → queue →
lane-lock wait → execute → render) and aggregated at render time into
per-(table, kind) log2-bucketed latency histograms with exec-mode
(lane/stacked/mesh/mono) and executor-cache (hit/compile/fallback)
attribution. Everything is monotonic-clock + host counters — recording
a span or reading a report never syncs a device handle. Wire surface:

* ``SHOW METRICS [t] [FORMAT 'prom']`` — histogram / percentile /
  stage-breakdown report as one JSON VALUE row (prom text exposition is
  JSON-string-encoded to stay a single wire line);
* ``EXPLAIN ANALYZE <stmt>`` — executes the statement and returns its
  measured per-stage spans next to the plan (this one DOES materialize
  the result — it is a diagnostic, not a serving path);
* ``SHOW SLOW`` — bounded ring of span trees for statements crossing
  ``SQLCached(slow_ms=...)`` / ``REPRO_SLOW_MS``;
* ``SHOW STATS`` (no table) — daemon-wide roll-up: tables, scheduler
  stats, executor-cache totals, uptime.

``REPRO_TELEMETRY=0`` disables tracing entirely (the serving path pays
one None check); ``ClusterClient.metrics()`` fans ``SHOW METRICS`` to
every live node and merges raw histogram buckets — sums are exact,
percentiles recompute from merged buckets, never averaged.

Skew + live re-partitioning
---------------------------

``SHOW STATS t`` (equivalently ``EXPLAIN t``) returns one JSON VALUE
row with per-shard live rows plus host-side routed-statement counters
(``statements``/``writes``/``inserted_rows`` — pruned traffic
attributes to its shard, fan-out to all), so a hot shard is observable
from any socket client. ``ALTER TABLE t RESHARD n`` re-partitions live:
one bulk device-side re-split of every live row
(``kernels/ops.shard_split`` over the flattened stack) plus one hash
index rebuild per new shard; row metadata and TTL stamps ride along
verbatim, so contents round-trip exactly. ``RESHARD 1`` converts back
to a monolithic table, resharding a monolithic table partitions it.
Both statements are admin barriers at the scheduler.

Cluster-facing admin statements (all admin barriers too):
``CHECKPOINT t TO 'dir'`` snapshots the table atomically via
``checkpoint/store.py`` (interner string table in the meta, so TEXT ids
survive a cross-process move); ``RESTORE t FROM 'dir'`` replaces the
table's contents from such a snapshot, re-interning TEXT and re-splitting
rows through the RESHARD machinery so partition hashes stay exact;
``ALTER TABLE t RETAIN SLOTS i,j OF m`` masks dead every row whose
partition value hashes outside the given cluster slots — the handover
primitive after a ring change (core/cluster.py). ``REPLICAS r`` on
CREATE is stored and reported (SHOW STATS) but enforced client-side.

The daemon is also the serving plane's metadata engine: `table_state` /
`swap_table_state` hand the device arrays to jitted serving steps with
zero copies.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import threading
from typing import Any, Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import lane_mesh_for
from repro.lint import lockorder as LK
from repro.core import planner as PL
from repro.core import predicate as P
from repro.core import shards as SH
from repro.core import sqlparse as S
from repro.core import table as T
from repro.core import telemetry as TEL
from repro.core.execache import ExecutorCache, use_persistent_cache
from repro.core.schema import ExpiryPolicy, TableSchema, make_schema


class Interner:
    """Host-side string<->id map (TEXT columns / params). ``intern`` is
    locked: the batch scheduler dispatches disjoint-footprint statement
    groups concurrently, and a string must never receive two ids."""

    def __init__(self):
        self._fwd: dict[str, int] = {}
        self._rev: list[str] = [""]  # id 0 = empty/NULL
        self._lock = LK.make_lock("daemon.interner")

    def intern(self, s: str) -> int:
        i = self._fwd.get(s)
        if i is None:
            with self._lock:
                i = self._fwd.get(s)
                if i is None:
                    i = len(self._rev)
                    # append FIRST: the fast-path read above is lock-free,
                    # so an id must never be published before its reverse
                    # mapping exists
                    self._rev.append(s)
                    self._fwd[s] = i
        return i

    def lookup(self, i: int) -> str:
        if 0 <= i < len(self._rev):
            return self._rev[i]
        return f"<unknown:{i}>"


_UNSET = object()


class _HostStack:
    """One device→host transfer shared by every Result of a micro-batched
    SELECT: the per-statement Results are index views into the stacked
    [batch, ...] outputs, so materializing any of them syncs once for all.
    Thread-safe: the protocol layer's per-connection flushers may
    materialize sibling Results of one batch concurrently."""

    __slots__ = ("dev", "_np", "_lock")

    def __init__(self, dev: dict):
        self.dev = dev
        self._np = None
        self._lock = LK.make_lock("daemon.hoststack")

    def host(self) -> dict:
        if self._np is None:
            # a sibling that finds the stack synced waits for nothing
            with TEL.device_wait(), self._lock:
                if self._np is None:
                    self._np = jax.tree.map(np.asarray, self.dev)
        return self._np


class Result:
    """Lazy result of one statement.

    Device outputs stay un-synced until first access: reading ``count``,
    ``rows``, ``arrays``, ``row_ids`` or ``value`` forces (and caches) the
    device→host transfer; ``payloads``, ``row_ids_device``,
    ``count_device`` and ``present_device`` return the raw device arrays
    with no sync. A Result built from host values (e.g. ``Result(count=3)``)
    behaves exactly like the former eager dataclass.
    """

    __slots__ = ("_count", "_rows", "_arrays", "_payloads", "_row_ids",
                 "_value", "_dev", "_ctx")

    def __init__(self, count: int = 0, rows=None, arrays=None, payloads=None,
                 row_ids=None, value: Any = None, *, dev: dict | None = None,
                 ctx: dict | None = None):
        self._dev = dev or {}
        self._ctx = ctx or {}
        self._count = _UNSET if self._lazy("count") else count
        self._rows = rows
        self._arrays = arrays
        self._payloads = payloads
        self._row_ids = _UNSET if self._lazy("row_ids") else row_ids
        self._value = _UNSET if self._lazy("value") else value

    def _lazy(self, name: str) -> bool:
        stack = self._ctx.get("stack")
        if stack is not None:
            return name in stack.dev
        return name in self._dev

    def _host(self, name: str):
        """Host view of a lazy device output (stack-aware)."""
        stack = self._ctx.get("stack")
        if stack is not None:
            return stack.host()[name][self._ctx["index"]]
        with TEL.device_wait():
            return np.asarray(self._dev[name])

    # ------------------------------------------------- lazy host accessors
    @property
    def count(self) -> int:
        if self._count is _UNSET:
            self._count = int(self._host("count"))
        return self._count

    @property
    def value(self) -> Any:
        if self._value is _UNSET:
            self._value = self._host("value").item()
        return self._value

    def _shown(self) -> int:
        n = self._ctx.get("nshow")
        if n is None:
            n = min(self.count, self._ctx.get("limit", self.count))
        return n

    @property
    def row_ids(self) -> np.ndarray | None:
        if self._row_ids is _UNSET:
            self._row_ids = self._host("row_ids")[: self._shown()]
        return self._row_ids

    def _materialize_rows(self) -> None:
        if self._arrays is not None or not self._lazy("rows"):
            return
        shown = self._shown()
        present = self._host("present")
        columns = self._ctx["columns"]
        interner = self._ctx["interner"]
        text_cols = self._ctx["text_cols"]
        stack = self._ctx.get("stack")
        if stack is not None:
            i = self._ctx["index"]
            arrays = {c: stack.host()["rows"][c][i][:shown] for c in columns}
        else:
            with TEL.device_wait():
                arrays = {c: np.asarray(self._dev["rows"][c])[:shown]
                          for c in columns}
        rows = []
        for i in range(shown):
            if not present[i]:
                continue
            row = {}
            for c in columns:
                v = arrays[c][i].item()
                if c in text_cols:
                    v = interner.lookup(int(v))
                row[c] = v
            rows.append(row)
        self._arrays, self._rows = arrays, rows

    @property
    def rows(self) -> list[dict] | None:
        self._materialize_rows()
        return self._rows

    @property
    def arrays(self) -> dict[str, np.ndarray] | None:
        self._materialize_rows()
        return self._arrays

    @property
    def payloads(self) -> dict[str, jax.Array] | None:
        if self._payloads is None and "payload_stack" in self._ctx:
            i = self._ctx["index"]
            self._payloads = {k: v[i]
                              for k, v in self._ctx["payload_stack"].items()}
        return self._payloads

    # --------------------------------------------- zero-sync device access
    @property
    def count_device(self):
        return self._dev.get("count", self._count)

    @property
    def row_ids_device(self):
        ids = self._dev.get("row_ids")
        return ids if ids is not None else (
            None if self._row_ids is _UNSET else self._row_ids)

    @property
    def present_device(self):
        return self._dev.get("present")

    @property
    def value_device(self):
        return self._dev.get("value", None if self._value is _UNSET
                             else self._value)

    def __repr__(self):  # avoid forcing a sync in debuggers/logs
        lazy = ",".join(sorted(self._dev)) or "-"
        return f"Result(lazy=[{lazy}])"


@dataclasses.dataclass
class _Table:
    """One live table: its schema, device state, and the ENGINE module
    that executes statements against that state — ``core.table`` for a
    monolithic table, ``core.shards`` for a hash-partitioned one
    (``SHARDS n``). Both expose the same executor surface, so every
    daemon path below is shape-agnostic.

    Sharded tables hold their state as per-shard EXECUTION LANES
    (``lanes[i]`` — one independent handle per shard, the monolithic
    layout of ``core/table.py``; ``state`` is None). A statement group
    that provably routes to ONE shard dispatches against that lane only
    (its own buffers, its own donation), so the batch scheduler can run
    same-table groups with disjoint shard routes concurrently — each
    lane has its own asyncio lock at the scheduler. Whole-table work
    stacks the lanes inside the jitted dispatch (``core/shards``
    split/merge boundary).

    Clock lockstep is kept LAZILY: ``ticks_total`` counts the table's
    logical ticks; ``lane_ticks[i]`` counts how many have been applied
    to lane i's device clock. Every dispatch first adds the lane's
    deficit (the catch-up delta) inside the same jitted call, so any
    statement observes exactly the clock the fully-lockstep stacked
    layout would show — TTL parity with the unsharded engine is
    preserved. §4.3 op-count auto-expiry defers per lane
    (``expire_due[i]``: None, or the ``ticks_total`` value at which a
    missed table-wide expiry fired): when the interval boundary fires
    during a lane-confined dispatch, that lane expires in-dispatch and
    every other lane REPLAYS the expiry on its own next dispatch — ages
    evaluated at the recorded firing time and only validity changed, so
    the replay removes exactly the rows the lockstep engine removed at
    the boundary.

    ``stmt_routed``/``writes_routed``/``rows_in`` are host-side per-shard
    skew counters (``SHOW STATS t``): pruned statements attribute to
    their shard, fan-out to every shard.

    ``mesh`` is the table's placement mesh (``launch.mesh.lane_mesh_for``;
    None = every lane on the default device): when set, ``lanes[i]`` is
    committed to its mesh device and whole-table dispatches run in
    ``mesh`` mode — assembled into one device-sharded global array and
    executed under ``shard_map`` instead of stacking on one chip."""

    schema: TableSchema
    state: dict | None
    host_ops: int = 0
    eng: Any = T
    lanes: list | None = None
    mesh: Any = None
    lock: Any = dataclasses.field(default_factory=threading.Lock)
    ticks_total: int = 0
    lane_ticks: list = dataclasses.field(default_factory=list)
    expire_due: list = dataclasses.field(default_factory=list)
    stmt_routed: Any = None
    writes_routed: Any = None
    rows_in: Any = None
    # per-table AOT executor cache (core/execache.py): entries are keyed
    # under the cache's schema epoch — RESHARD/REINDEX/RESTORE bump it
    execs: ExecutorCache = dataclasses.field(default_factory=ExecutorCache)


@dataclasses.dataclass(frozen=True)
class StatementShape:
    """Grouping descriptor for one SQL text (see :meth:`SQLCached.shape_key`).

    ``key`` is hashable and equal exactly when two statements can ride the
    same batched executor (same parsed AST — LIMIT, ORDER BY, aggregate
    function and WHERE shape all included, only the ``?`` bindings vary).
    ``batchable`` marks shapes ``executemany`` accepts; ``is_write`` drives
    the scheduler's read/write reordering barriers.

    ``reads``/``writes`` are the statement's column footprints (reused
    from the planner's AST walk): the batch scheduler fences at column
    rather than table granularity, so e.g. an UPDATE on ``w`` no longer
    bars a SELECT that only touches ``k``. ``None`` means "the whole
    table" — unknown footprints, validity-changing writes (INSERT/DELETE
    churn every read's row set), or anything touching reserved columns."""

    key: tuple
    table: str | None
    kind: str  # "select" | "insert" | "delete" | "update" | "admin" | ...
    batchable: bool
    is_write: bool
    reads: frozenset | None = None
    writes: frozenset | None = None


def _bucket(n: int) -> int:
    """Pad batch sizes to powers of two to bound executor retraces."""
    b = 1
    while b < n:
        b *= 2
    return b


def _exec_name(kind: str, probe: bool | None = None,
               batch: bool = False) -> str:
    """The name of a statement executor's program,
    ``sqlcached_<kind>[_probe|_scan][_batch]``: ``kind`` is the statement
    (an aggregate by its function), ``probe`` its access path (None: it
    has none), ``batch`` the grouped path. Jit calls the program
    ``jit_<name>``, which is what a profiler's ``XLA Modules`` line
    shows."""
    name = "sqlcached_" + kind.lower()
    if probe is not None:
        name += "_probe" if probe else "_scan"
    return name + "_batch" if batch else name


def _named(fn, name: str):
    """``fn`` renamed: jit names a program after its function."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _np_terms_int(terms, param_cols) -> bool:
    """Host-side dtype gate for the batched probe route: every `?`-bound
    term value must be integer (floats keep exact-compare semantics on
    the scan path — same rule table._int_values applies at trace time)."""
    for t in terms:
        kind, v = t.value
        if kind == "param" and not np.issubdtype(param_cols[v].dtype,
                                                 np.integer):
            return False
    return True


class SQLCached:
    def __init__(self, auto_expire: bool = True, lane_exec: bool = True,
                 mesh_exec: bool = True, warmup: bool | None = None,
                 slow_ms: float | None = None):
        use_persistent_cache()
        self.tables: dict[str, _Table] = {}
        self.interner = Interner()
        # serving telemetry (core/telemetry.py): trace spans, latency
        # histograms, slow-statement ring. slow_ms=None defers to
        # REPRO_SLOW_MS; REPRO_TELEMETRY=0 disables tracing entirely.
        self.telemetry = TEL.Telemetry(slow_ms=slow_ms)
        self.auto_expire = auto_expire
        # lane_exec=False disables lane-confined dispatch (every sharded
        # statement takes the stacked path — the PR-4 execution regime;
        # benchmarks/lane_bench.py uses it as the paired baseline)
        self.lane_exec = lane_exec
        # mesh_exec=False (or REPRO_MESH=0) disables multi-device lane
        # placement — every lane stays on the default device and
        # whole-table work stacks on one chip (the PR-5/6 regime;
        # benchmarks/mesh_bench.py uses it as the paired baseline)
        self.mesh_exec = mesh_exec and os.environ.get("REPRO_MESH",
                                                      "1") != "0"
        # warmup=None defers to REPRO_WARMUP (default on): CREATE TABLE
        # pre-compiles the canonical hot shapes in a background thread.
        # The unit-test suite turns it off (compiles it never replays);
        # the explicit WARMUP statement works regardless.
        if warmup is None:
            warmup = os.environ.get("REPRO_WARMUP", "1") != "0"
        self.warmup = warmup
        self._warm_threads: dict[str, threading.Thread] = {}
        self._stmts: dict[str, S.Statement] = {}
        self._shapes: dict[str, StatementShape] = {}

    # ------------------------------------------------------------- plumbing
    def _parse(self, sql: str) -> S.Statement:
        stmt = self._stmts.get(sql)
        if stmt is None:
            stmt = S.parse(sql)
            self._stmts[sql] = stmt
        return stmt

    def _table(self, name: str) -> _Table:
        t = self.tables.get(name)
        if t is None:
            raise S.SQLError(f"no such table {name!r}")
        return t

    def _intern_ast(self, node):
        return P.map_consts(
            node, lambda v: self.interner.intern(v) if isinstance(v, str) else v
        )

    def _prep_params(self, params: Sequence[Any]) -> tuple:
        out = []
        for p in params:
            if isinstance(p, str):
                p = self.interner.intern(p)
            out.append(p)
        return tuple(out)

    def _executor(self, t: _Table, key: tuple, builder):
        """The table's :class:`ExecEntry` for ``key`` under the current
        schema epoch (core/execache.py) — a drop-in callable: hits
        replay the AOT executable for the dispatch's placement, misses
        compile-and-store from the concrete call args."""
        return t.execs.get(key, builder)

    def _placement(self, t: _Table, mode: str, sid) -> tuple:
        """The host-side placement token an executor call keys its AOT
        executable under: which device (mono/lane/stacked) or which mesh
        (mesh mode) the state lives on. Pure metadata — no device sync."""
        if mode == "mesh":
            return ("mesh", tuple(d.id for d in t.mesh.devices.reshape(-1)))
        if mode == "lane" and t.mesh is not None:
            return ("dev",
                    SH.lane_devices(t.mesh, t.schema.shards)[sid].id)
        return ("dev", jax.devices()[0].id)

    def _sig(self, t: _Table, stmt, kind: str, b, mode: str, sid) -> tuple:
        """The dispatch signature recorded in ``t.execs.sigs`` after a
        shape is planned: (kind, parsed stmt, bucket, mode, placement).
        ``b`` is None on the singleton executors, the power-of-two bucket
        on the executemany family (INSERT always buckets — ``execute``
        routes single inserts through the batch path)."""
        return (kind, stmt, b, mode, self._placement(t, mode, sid))

    def _note_sig(self, t: _Table, stmt, kind: str, b, mode: str,
                  sid) -> None:
        t.execs.note_sig(self._sig(t, stmt, kind, b, mode, sid))

    def _jit_with_expiry(self, schema, base, name: str, eng=T):
        """Jit a statement executor ``base(state, *args) -> (state, *outs)``
        with the §4.3 op-count expiry fused into the same dispatch: a
        device-side ``lax.cond`` on a host-computed flag replaces the former
        separate ``_do_expire`` call, so auto-expiry is dispatch-free.
        ``eng`` is the table's engine module (expiry must run the
        matching state layout); ``name`` names the program
        (:func:`_exec_name`)."""
        if schema.expiry.ops_interval > 0:
            def fn(state, expire_flag, *args):
                out = base(state, *args)
                state = jax.lax.cond(
                    expire_flag,
                    lambda s: eng.expire(schema, s)[0],
                    lambda s: s,
                    out[0])
                return (state,) + tuple(out[1:])
        else:
            def fn(state, expire_flag, *args):
                return base(state, *args)
        return jax.jit(_named(fn, name), donate_argnums=0)

    def _jit_exec(self, xsch, base, mode: str, eng, name: str):
        """Jit ``base(state, *args) -> (state, *outs)`` for one dispatch
        shape (see :meth:`_exec_mode`) as the program ``name``
        (:func:`_exec_name`), fusing the §4.3 op-count expiry
        and — on lanes — the lazy clock catch-up into the same dispatch:

        * ``mono``:    ``fn(state, flag, *args)`` (the classic wrapper);
        * ``lane``:    ``fn(lane_state, flag, delta, *args)`` — ``delta``
          catches the lane's clock up to the table's logical time before
          ``base`` runs; the expiry cond covers THIS lane only (the
          per-lane deferral contract, see ``_Table``);
        * ``stacked``: ``fn(lanes_tuple, flag, deltas, *args)`` — stacks
          the lanes (XLA's slice-of-concat simplification keeps
          pass-through leaves free), catches every clock up, runs the
          vmapped executor, splits back into lanes;
        * ``mesh``:    ``fn(global_state, flag, deltas, *args)`` — the
          multi-device twin of ``stacked``: the caller assembles the
          lanes into ONE device-sharded global array
          (``shards.assemble_lanes``), the body runs under the table's
          placement mesh (``shards.fanout_mesh`` makes every per-shard
          fan-out a ``shard_map`` over the lane axis), and the output
          state is pinned back onto the mesh so the caller's
          disassembly is a per-device slice, not a gather."""
        if mode == "mono":
            return self._jit_with_expiry(xsch, base, name, eng=eng)
        iv = xsch.expiry.ops_interval
        if mode == "lane":
            def fn(state, expire_flag, delta, pre_delta, *args):
                state = dict(state, clock=state["clock"] + delta,
                             ops=state["ops"] + delta)
                if iv > 0:
                    # replay a missed table-wide expiry FIRST: ages are
                    # evaluated at the firing statement's logical time
                    # (clock - pre_delta; pre_delta < 0 = nothing due)
                    # and only validity changes — the firing dispatch
                    # already accounted the expiry tick table-wide
                    def replay(s):
                        d = jnp.maximum(pre_delta, 0)
                        aged = dict(s, clock=s["clock"] - d,
                                    ops=s["ops"] - d)
                        return dict(s, valid=T.expire(xsch, aged)[0][
                            "valid"])

                    state = jax.lax.cond(pre_delta >= 0, replay,
                                         lambda s: s, state)
                out = base(state, *args)
                st = out[0]
                if iv > 0:
                    st = jax.lax.cond(
                        expire_flag,
                        lambda s: T.expire(xsch, s)[0],
                        lambda s: s, st)
                return (st,) + tuple(out[1:])

            return jax.jit(_named(fn, name), donate_argnums=0)

        schema = xsch  # stacked/mesh modes run on the full sharded schema

        def body(state, expire_flag, deltas, pre_deltas, *args):
            state = dict(state, clock=state["clock"] + deltas,
                         ops=state["ops"] + deltas)
            if iv > 0:
                def replay(s):
                    d = jnp.maximum(pre_deltas, 0)
                    aged = dict(s, clock=s["clock"] - d,
                                ops=s["ops"] - d)
                    exp = SH.expire(schema, aged)[0]
                    due = (pre_deltas >= 0)[:, None]
                    return dict(s, valid=jnp.where(due, exp["valid"],
                                                   s["valid"]))

                state = jax.lax.cond(jnp.any(pre_deltas >= 0), replay,
                                     lambda s: s, state)
            out = base(state, *args)
            st = out[0]
            if iv > 0:
                st = jax.lax.cond(
                    expire_flag,
                    lambda s: SH.expire(schema, s)[0],
                    lambda s: s, st)
            return st, out[1:]

        if mode == "mesh":
            def fn(state, expire_flag, deltas, pre_deltas, *args):
                # the context must wrap the BODY (jit traces lazily):
                # every shards._fanout traced inside becomes a shard_map
                # over the table's placement mesh
                mesh = lane_mesh_for(schema.shards)
                with SH.fanout_mesh(mesh):
                    st, outs = body(state, expire_flag, deltas,
                                    pre_deltas, *args)
                    st = SH.constrain_lanes(mesh, st)
                return (st,) + tuple(outs)
        else:
            def fn(lanes, expire_flag, deltas, pre_deltas, *args):
                st, outs = body(SH.stack_lanes(lanes), expire_flag,
                                deltas, pre_deltas, *args)
                return (tuple(SH.split_lanes(schema, st)),) + tuple(outs)

        return jax.jit(_named(fn, name), donate_argnums=0)

    def _lane_of(self, t: _Table, stmt, params_list,
                 pvals=None) -> int | None:
        """THE lane-route decision: the single lane id this statement
        (group) will execute on, or None for stacked/whole-table
        dispatch. The scheduler's lock choice (:meth:`group_lane`) and
        the daemon's dispatch shape (:meth:`_exec_mode`) both read this
        one predicate, so they can never disagree about whether a
        dispatch touches one lane or all of them."""
        if t.lanes is None or not self.lane_exec or stmt is None:
            return None
        try:
            ids = self._shard_ids_of(t, stmt, params_list, pvals=pvals)
        except Exception:  # noqa: BLE001 — routing is best effort
            return None
        if ids is None or len(ids) != 1:
            return None
        if isinstance(stmt, S.Insert) and _bucket(
                len(params_list)) > SH.shard_capacity(t.schema):
            # a padded batch wider than one shard must chunk through the
            # stacked split path — an all-lane dispatch
            return None
        return next(iter(ids))

    def group_lane(self, shape: StatementShape | None,
                   params_list: Sequence[Sequence[Any]]) -> int | None:
        """Scheduler-facing twin of :meth:`_lane_of`: the execution lane
        a batch of same-shape statements will run on (None = the
        dispatch takes the whole table). The BatchScheduler locks
        exactly what this reports."""
        if shape is None or shape.table is None:
            return None
        t = self.tables.get(shape.table)
        if t is None:
            return None
        stmt = shape.key[1] if len(shape.key) == 2 else None
        return self._lane_of(t, stmt, params_list)

    def item_lanes(self, shape: StatementShape | None,
                   params_list: Sequence[Sequence[Any]]) -> list | None:
        """Per-STATEMENT lane routes for one same-shape group: entry i
        is the single lane statement i provably dispatches on, or None
        when that statement fans out. Returns None outright when lane
        routing doesn't apply (unsharded table, lane exec off, no
        statement). The scheduler uses this to SPLIT a multi-lane group
        into per-lane sub-batches that overlap (each sub-batch is then
        re-verified through :meth:`group_lane`, so lock and dispatch
        still agree)."""
        if shape is None or shape.table is None:
            return None
        t = self.tables.get(shape.table)
        if t is None or t.lanes is None or not self.lane_exec:
            return None
        stmt = shape.key[1] if len(shape.key) == 2 else None
        if stmt is None:
            return None
        return [self._lane_of(t, stmt, [pr]) for pr in params_list]

    def _exec_mode(self, t: _Table, stmt, params_list, n_stmts: int,
                   pvals=None):
        """Pick the dispatch shape for one statement (group) against
        ``t`` and consume the §4.3 op-count expiry interval:

        * ``('mono', T, schema, None, flag)`` — unsharded table;
        * ``('lane', T, shard_schema, sid, flag)`` — sharded and every
          statement in the group provably routes to shard ``sid``
          (host-side, via :meth:`_lane_of`): run the monolithic
          executors against that lane's handle only;
        * ``('stacked', SH, schema, None, flag)`` — sharded fan-out /
          multi-shard / unknown route: stack the lanes in-dispatch;
        * ``('mesh', SH, schema, None, flag)`` — same routes on a
          MESH-placed table: assemble the lanes into one device-sharded
          global array and fan out under shard_map (see ``_jit_exec``).

        ``flag`` carries the expiry trigger for THIS dispatch (lane
        routes defer per lane — see ``_Table.expire_due``)."""
        sid = self._lane_of(t, stmt, params_list, pvals=pvals)
        fired = self._expire_flag(t, n_stmts)
        if t.lanes is None:
            return "mono", t.eng, t.schema, None, fired
        if sid is not None:
            return "lane", T, SH.shard_schema(t.schema), sid, fired
        if t.mesh is not None:
            return "mesh", SH, t.schema, None, fired
        return "stacked", SH, t.schema, None, fired

    def _expire_flag(self, t: _Table, n: int = 1) -> bool:
        """Paper §4.3 condition 3: expire every N cache operations. Counted
        host-side; the flag rides into the fused executor. ``n`` is the
        number of STATEMENTS the dispatch carries — a micro-batched
        executemany advances the op count by its batch size, so expiry
        cadence doesn't depend on how the scheduler grouped the traffic
        (the flag fires once per crossed interval boundary). Thread-safe:
        concurrent lane dispatches count under the table lock."""
        iv = t.schema.expiry.ops_interval
        with t.lock:
            before = t.host_ops
            t.host_ops += n
            return bool(self.auto_expire and iv > 0
                        and before // iv != t.host_ops // iv)

    def _run_state(self, t: _Table, fn, mode: str, sid, flag, ticks: int,
                   args: tuple):
        """Dispatch a ``_jit_exec`` executor against the right state
        handle(s), booking the lazy clock catch-up, and thread the new
        state back. ``ticks`` is the number of clock ticks the dispatch
        performs (1 per singleton/INSERT dispatch, the active statement
        count for micro-batches — exactly what the executor adds).
        Returns the executor's non-state outputs."""
        TEL.note_mode(mode)   # exec_mode attribution for the live traces
        # placement keys the entry's AOT executable; np.bool_ keeps the
        # runtime flag aval identical to the warm path's placeholder
        placement = self._placement(t, mode, sid)
        flag = np.bool_(flag)
        if mode == "mono":
            out = fn(t.state, flag, *args, placement=placement)
            t.state = out[0]
            return out[1:]
        n_sh = t.schema.shards
        # a fired expiry cond ticks the clock once more than the base
        # executor — account it, or catch-up deltas drift
        total = ticks + (1 if flag else 0)
        fire_at = g0 = None
        with t.lock:
            g0 = t.ticks_total
            t.ticks_total = g0 + total
            if flag:
                # the logical time the fired expiry runs (after this
                # dispatch's base ticks) — deferred lanes replay at it
                fire_at = g0 + ticks
            if mode == "lane":
                old_tick = t.lane_ticks[sid]
                t.lane_ticks[sid] = g0 + total
                pre_at = t.expire_due[sid]
                t.expire_due[sid] = None
                # NOTE: when flag fired, the other lanes' deferrals are
                # armed only AFTER the dispatch succeeds (below) — a
                # concurrent commuting lane must never replay an expiry
                # whose dispatch might still fail (its own dispatch then
                # legitimately serializes BEFORE the firing one)
            else:
                old_ticks = list(t.lane_ticks)
                deltas = np.asarray([g0 - lt for lt in t.lane_ticks],
                                    np.int32)
                t.lane_ticks = [g0 + total] * n_sh
                pre_ats = list(t.expire_due)
                t.expire_due = [None] * n_sh
        try:
            if mode == "lane":
                pre_d = -1 if pre_at is None else g0 - pre_at
                out = fn(t.lanes[sid], flag, jnp.int32(g0 - old_tick),
                         jnp.int32(pre_d), *args, placement=placement)
                with t.lock:  # commit atomically vs advance_clock et al
                    t.lanes[sid] = out[0]
                    if flag:
                        # the boundary fired and RAN on this lane: every
                        # other lane replays it on its own next dispatch
                        # (a newer fire_at supersedes an older pending
                        # one — ages at the later time are a superset)
                        for i in range(n_sh):
                            if i != sid:
                                t.expire_due[i] = fire_at
                return out[1:]
            pre_ds = np.asarray(
                [(-1 if (at is None) else g0 - at) for at in pre_ats],
                np.int32)
            if mode == "mesh":
                glob = SH.assemble_lanes(t.mesh, t.lanes)
                out = fn(glob, flag, deltas, pre_ds, *args,
                         placement=placement)
                new_lanes = SH.disassemble_lanes(t.mesh, n_sh, out[0])
            else:
                out = fn(tuple(t.lanes), flag, deltas, pre_ds, *args,
                         placement=placement)
                new_lanes = out[0]
            with t.lock:
                for i, st in enumerate(new_lanes):
                    t.lanes[i] = st
            return out[1:]
        except Exception:
            # the executor raised before mutating state (trace-time error,
            # e.g. a bad binding): un-book the ticks so clocks don't
            # drift. ticks_total only rolls back when nobody advanced it
            # since (monotonicity keeps concurrent catch-ups sound), and
            # only OUR OWN due entries are restored — deferrals for the
            # other lanes were never armed (arm-on-success above), so a
            # fired expiry whose dispatch failed is DROPPED everywhere,
            # exactly as the monolithic engine drops it.
            with t.lock:
                if mode == "lane":
                    t.lane_ticks[sid] = old_tick
                    t.expire_due[sid] = pre_at
                else:
                    t.lane_ticks = old_ticks
                    t.expire_due = pre_ats
                if t.ticks_total == g0 + total:
                    t.ticks_total = g0
            raise

    def _note_route(self, t: _Table, sid, n: int, is_write: bool,
                    rows_in=None) -> None:
        """Per-shard skew accounting (``SHOW STATS t``): pruned traffic
        attributes to its shard, fan-out (sid None) to every shard."""
        with t.lock:
            if sid is None:
                t.stmt_routed += n
                if is_write:
                    t.writes_routed += n
            else:
                t.stmt_routed[sid] += n
                if is_write:
                    t.writes_routed[sid] += n
            if rows_in is not None:
                t.rows_in += rows_in

    @staticmethod
    def _insert_sids(t: _Table, pvals, n_rows: int):
        """Per-shard inserted-row counts (np int64) from pre-extracted
        partition values (``pvals``; None = not host-readable). Feeds
        the ``rows_in`` skew counter; monolithic tables count every row
        into their single entry so the report stays consistent with the
        ``statements``/``writes`` counters."""
        if t.lanes is None:
            return np.asarray([n_rows], np.int64)
        if pvals is None:
            return None
        n_sh = t.schema.shards
        out = np.zeros(n_sh, np.int64)
        for v in pvals:
            out[SH.shard_of_host(v, n_sh)] += 1
        return out

    @staticmethod
    def _check_partition_update(t: _Table, set_cols) -> None:
        """Refuse partition-column UPDATEs on sharded tables up front
        (the engines raise too, but only at trace time — this keeps the
        op counters clean and covers the lane path, whose monolithic
        executor has no partition concept)."""
        if t.lanes is None:
            return
        cols = {("_ttl" if c.upper() == "TTL" else c) for c in set_cols}
        if t.schema.partition_by in cols:
            raise ValueError(
                f"cannot UPDATE partition column "
                f"{t.schema.partition_by!r} of sharded table "
                f"{t.schema.name!r} (DELETE + INSERT instead)")

    def _caught_up_lanes(self, t: _Table) -> list:
        """SNAPSHOT of every lane brought up to the table's logical
        time (admin paths — RESHARD, ``table_state`` — need lockstep
        NOW): clocks catch up their deltas AND any still-deferred
        op-interval expiry is replayed into the snapshot (ages at its
        recorded firing time, validity only) — so the snapshot never
        shows rows the lockstep engine already expired. Pure read:
        nothing is written back into ``t.lanes`` and no bookkeeping
        changes, so a concurrent lane dispatch can never be clobbered
        by the snapshot."""
        with t.lock:
            g0 = t.ticks_total
            deltas = [g0 - lt for lt in t.lane_ticks]
            dues = list(t.expire_due)
            lanes = list(t.lanes)
        s_sch = SH.shard_schema(t.schema)
        iv = t.schema.expiry.ops_interval
        out = []
        for lane, d, due in zip(lanes, deltas, dues):
            if d:
                lane = dict(lane, clock=lane["clock"] + d,
                            ops=lane["ops"] + d)
            if due is not None and iv > 0:
                back = g0 - due
                aged = dict(lane, clock=lane["clock"] - back,
                            ops=lane["ops"] - back)
                lane = dict(lane, valid=T.expire(s_sch, aged)[0]["valid"])
            out.append(lane)
        return out

    # -------------------------------------------------- executor warm-up
    def _state_avals(self, t: _Table, mode: str, sid):
        """Abstract avals of the state argument one ``_jit_exec`` mode
        receives, derived from the SCHEMA (``jax.eval_shape`` over the
        init path — no real state is built) and carrying the placement
        sharding the runtime handle will have: a placed lane's leaves
        are committed to its device, a mesh-assembled global is sharded
        along the lane axis. AOT compilation from these avals produces
        the exact executable a live dispatch would compile."""
        if mode == "mono":
            return jax.eval_shape(lambda: T.init_state(t.schema))
        s_sch = SH.shard_schema(t.schema)
        if mode == "lane":
            av = jax.eval_shape(lambda: T.init_state(s_sch))
            devs = SH.lane_devices(t.mesh, t.schema.shards)
            if devs is None:
                return av
            sh = jax.sharding.SingleDeviceSharding(devs[sid])
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh), av)
        stacked = jax.eval_shape(
            lambda: SH.stack_lanes(SH.init_lanes(t.schema)))
        if mode == "mesh":
            from repro.launch.mesh import LANE_AXIS
            ns = jax.sharding.NamedSharding(
                t.mesh, jax.sharding.PartitionSpec(LANE_AXIS))
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=ns), stacked)
        lane_av = jax.eval_shape(lambda: T.init_state(s_sch))
        return tuple(lane_av for _ in range(t.schema.shards))

    def _warm_args(self, t: _Table, mode: str, sid, site_args: tuple):
        """The full argument tuple :meth:`ExecEntry.warm` lowers from:
        abstract state avals + concrete placeholders whose avals match
        what ``_run_state`` passes (np.bool_ flag, int32 clock deltas)."""
        st = self._state_avals(t, mode, sid)
        if mode == "mono":
            return (st, np.bool_(False)) + tuple(site_args)
        if mode == "lane":
            return (st, np.bool_(False), jnp.int32(0),
                    jnp.int32(-1)) + tuple(site_args)
        n = t.schema.shards
        return (st, np.bool_(False), np.zeros(n, np.int32),
                np.full(n, -1, np.int32)) + tuple(site_args)

    def _warm_env(self, t: _Table, mode: str):
        """(eng, xsch) for a forced dispatch mode — the side-effect-free
        twin of :meth:`_exec_mode` the warm paths use (``_exec_mode``
        consumes the op-count expiry interval, which a warm-up must
        not)."""
        if mode == "mono":
            return t.eng, t.schema
        if mode == "lane":
            return T, SH.shard_schema(t.schema)
        return SH, t.schema

    def _finish_warm(self, t: _Table, entry, stmt, kind: str, b, mode: str,
                     sid, site_args: tuple) -> int:
        """Shared tail of every site's warm branch: AOT-compile the
        entry for the dispatch's placement and record the signature."""
        placement = self._placement(t, mode, sid)
        new = entry.warm(placement, self._warm_args(t, mode, sid,
                                                    site_args))
        self._note_sig(t, stmt, kind, b, mode, sid)
        return int(new)

    def _prunable(self, t: _Table, stmt) -> bool:
        """Host-side: can this statement ever take a single-lane route?
        (INSERTs always hash-route row by row; WHERE statements prune
        when the planner finds a partition-key equality.)"""
        if isinstance(stmt, S.Insert):
            return True
        if not isinstance(stmt, (S.Select, S.Update, S.Delete)):
            return False
        route = PL.plan_shards(t.schema, self._intern_ast(stmt.where))
        return route.key is not None

    def _warm_modes(self, t: _Table, stmt) -> list:
        """The (mode, sid) dispatch shapes to pre-plan for ``stmt`` —
        one per DISTINCT placement: a prunable statement on a placed
        table warms its lane executor once per lane device (any lane on
        that device then replays it); everything else warms the one
        fan-out (mesh/stacked/mono) executor."""
        if t.lanes is None:
            return [("mono", None)]
        if self.lane_exec and self._prunable(t, stmt):
            devs = SH.lane_devices(t.mesh, t.schema.shards)
            if devs is None:
                return [("lane", 0)]
            seen, out = set(), []
            for sid, d in enumerate(devs):
                if d.id not in seen:
                    seen.add(d.id)
                    out.append(("lane", sid))
            return out
        return [("mesh" if t.mesh is not None else "stacked", None)]

    def _warm_statement(self, t: _Table, stmt) -> int:
        """Pre-plan one statement's executors for every placement it can
        dispatch to. Returns the number of newly compiled executables."""
        new = 0
        for mode, sid in self._warm_modes(t, stmt):
            if isinstance(stmt, S.Insert):
                new += self._do_insert_batch(stmt, [], None,
                                             _warm=(mode, sid))
            elif isinstance(stmt, S.Select):
                new += self._do_select(stmt, (), _warm=(mode, sid))
            elif isinstance(stmt, S.Update):
                new += self._do_update(stmt, (), _warm=(mode, sid))
            elif isinstance(stmt, S.Delete):
                new += self._do_delete(stmt, (), _warm=(mode, sid))
            else:
                raise S.SQLError(
                    "WARMUP supports SELECT/INSERT/UPDATE/DELETE shapes")
        return new

    def _canonical_warm_sqls(self, schema: TableSchema) -> list[str]:
        """The canonical hot shapes CREATE-time warm-up pre-plans: the
        full-row INSERT plus a pruned eq-SELECT and eq-DELETE on the
        partition / index columns (the web-cache working set — see the
        paper's GET/SET/DELETE triple)."""
        cols = schema.column_names
        out = [f"INSERT INTO {schema.name} ({', '.join(cols)}) "
               f"VALUES ({', '.join('?' for _ in cols)})"]
        keys = [c for c in (schema.partition_by, *schema.indexes)
                if c is not None]
        if not keys and cols:
            keys = [cols[0]]
        for c in dict.fromkeys(keys):
            out.append(f"SELECT * FROM {schema.name} WHERE {c} = ?")
            out.append(f"DELETE FROM {schema.name} WHERE {c} = ?")
        return out

    def _do_warmup(self, stmt: S.Warmup) -> Result:
        """WARMUP t [LIKE '<stmt>']: synchronously pre-plan executors —
        the given statement's shapes, or the canonical hot set. Returns
        the number of newly compiled executables as ``count`` (0 =
        everything was already planned) and the schema epoch as
        ``value``."""
        t = self._table(stmt.table)
        sqls = ([stmt.like] if stmt.like is not None
                else self._canonical_warm_sqls(t.schema))
        new = 0
        for sql in sqls:
            self.shape_key(sql)  # prime the scheduler's admission cache
            new += self._warm_statement(t, self._parse(sql))
        return Result(count=new, value=t.execs.epoch)

    def _warm_table_bg(self, name: str) -> None:
        """CREATE-time background warm-up: pre-plan the canonical hot
        shapes off the dispatch thread. Best-effort by contract — a
        statement that raced a DROP/RESHARD just stops; warm-up must
        never take down serving. A failure is recorded in the table's
        ``SHOW STATS`` ``executors.warmup_errors``."""
        t = self.tables.get(name)
        if t is None:
            return
        for sql in self._canonical_warm_sqls(t.schema):
            if self.tables.get(name) is not t:
                return  # dropped/recreated under us
            try:
                self.shape_key(sql)
                self._warm_statement(t, self._parse(sql))
            except Exception as e:  # noqa: BLE001 — warm-up is best effort
                t.execs.warmup_errors.append(
                    f"{sql}: {type(e).__name__}: {e}"[:2000])
                return

    def drain_warmup(self, table: str | None = None) -> None:
        """Join the CREATE-time background warm-up thread(s) — operators
        and benchmarks call this to start timing from a planned state."""
        for nm, th in list(self._warm_threads.items()):
            if table is None or nm == table:
                th.join()

    def group_warm(self, shape: StatementShape | None,
                   params_list: Sequence[Sequence[Any]]) -> bool:
        """Scheduler admission hook: will this group's dispatch replay
        an already-planned executable? Recomputes the dispatch signature
        host-side (sig-set lookup — never a device sync, never an op
        count tick) so the wave builder can keep a still-cold group out
        of warm waves instead of stalling commuting groupmates on its
        compile. Unknown shapes report warm: admin statements and
        unroutable groups must never serialize a wave."""
        if shape is None or shape.table is None or len(shape.key) != 2:
            return True
        if shape.kind not in ("select", "insert", "delete", "update"):
            return True
        t = self.tables.get(shape.table)
        if t is None:
            return True
        kind, stmt = shape.key
        n = len(params_list)
        try:
            prepped = [self._prep_params(p) for p in params_list]
            sid = self._lane_of(t, stmt, prepped)
            if t.lanes is None:
                mode = "mono"
            elif sid is not None:
                mode = "lane"
            elif t.mesh is not None:
                mode = "mesh"
            else:
                mode = "stacked"
            b = _bucket(n) if (n > 1 or kind == "insert") else None
            return t.execs.has_sig(self._sig(t, stmt, kind, b, mode, sid))
        except Exception:  # noqa: BLE001 — admission is best effort
            return True

    def _preplanned(self, t: _Table, stmt) -> bool:
        """EXPLAIN's ``preplanned`` bit: every placement this statement
        can dispatch to has a compiled executable (host signature set
        only — no device sync)."""
        kind = type(stmt).__name__.lower()
        b = 1 if kind == "insert" else None
        try:
            return all(
                t.execs.has_sig(self._sig(t, stmt, kind, b, mode, sid))
                for mode, sid in self._warm_modes(t, stmt))
        except Exception:  # noqa: BLE001
            return False

    # ----------------------------------------------------------- statements
    def execute(
        self,
        sql: str,
        params: Sequence[Any] = (),
        payloads: Mapping[str, Any] | None = None,
    ) -> Result:
        stmt = self._parse(sql)
        return self._dispatch_stmt(stmt, params, payloads)

    def _dispatch_stmt(
        self,
        stmt: S.Statement,
        params: Sequence[Any] = (),
        payloads: Mapping[str, Any] | None = None,
    ) -> Result:
        """Route one PARSED statement to its handler (shared by
        :meth:`execute` and EXPLAIN ANALYZE, which holds the parsed
        inner statement but no standalone SQL text)."""
        if isinstance(stmt, S.CreateTable):
            return self._do_create(stmt)
        if isinstance(stmt, S.DropTable):
            self.tables.pop(stmt.table, None)
            return Result()
        if isinstance(stmt, S.Insert):
            return self._do_insert_batch(stmt, [tuple(params)],
                                         [payloads] if payloads else None)
        if isinstance(stmt, S.Select):
            return self._do_select(stmt, self._prep_params(params))
        if isinstance(stmt, S.Update):
            return self._do_update(stmt, self._prep_params(params))
        if isinstance(stmt, S.Delete):
            return self._do_delete(stmt, self._prep_params(params))
        if isinstance(stmt, S.Expire):
            return self._do_expire(stmt.table)
        if isinstance(stmt, S.Flush):
            return self._do_flush(stmt.table)
        if isinstance(stmt, S.Reindex):
            return self._do_reindex(stmt.table)
        if isinstance(stmt, S.Warmup):
            return self._do_warmup(stmt)
        if isinstance(stmt, S.ShowStats):
            return self._do_show_stats(stmt.table)
        if isinstance(stmt, S.ShowMetrics):
            return self._do_show_metrics(stmt)
        if isinstance(stmt, S.ShowSlow):
            return self._do_show_slow()
        if isinstance(stmt, S.AlterReshard):
            return self._do_reshard(stmt)
        if isinstance(stmt, S.AlterRetain):
            return self._do_retain(stmt)
        if isinstance(stmt, S.Checkpoint):
            return self._do_checkpoint(stmt)
        if isinstance(stmt, S.Restore):
            return self._do_restore(stmt)
        if isinstance(stmt, S.Explain):
            return self._do_explain(stmt.inner)
        if isinstance(stmt, S.ExplainAnalyze):
            return self._do_explain_analyze(stmt, params)
        raise S.SQLError(f"unhandled statement {stmt!r}")

    @staticmethod
    def _clean_footprint(cols) -> frozenset | None:
        """None (whole-table) when a footprint touches reserved columns —
        their cross-statement couplings (touch stamps, TTL aging) are not
        worth modelling at the scheduler."""
        fp = frozenset(cols)
        if any(c.startswith("_") for c in fp):
            return None
        return fp

    def shape_key(self, sql: str) -> StatementShape:
        """Classify ``sql`` for cross-connection batching (the scheduler's
        grouping hook): statements whose ``.key`` compare equal share one
        jitted executor and may be dispatched together through
        :meth:`executemany`, so a heterogeneous admission batch splits into
        the minimal number of dispatches. The read/write column footprints
        ride along (planner AST walk) for column-level fencing. Shapes are
        pure functions of the statement TEXT, memoized — the scheduler
        calls this on every admission. Raises ``SQLError`` on bad SQL."""
        cached = self._shapes.get(sql)
        if cached is not None:
            return cached
        shape = self._shape_key_uncached(sql)
        self._shapes[sql] = shape
        return shape

    def _shape_key_uncached(self, sql: str) -> StatementShape:
        stmt = self._parse(sql)
        clean = self._clean_footprint
        if isinstance(stmt, S.Select):
            reads = set(PL.columns_of(stmt.where))
            if stmt.agg is not None:
                if stmt.agg[1] is not None:
                    reads.add(stmt.agg[1])
            elif stmt.columns:
                reads |= set(stmt.columns)
            else:
                # SELECT *: whole-table reads. The footprint must come
                # from the statement TEXT alone — expanding `*` against
                # the live schema goes stale when a DROP/CREATE for the
                # same table is queued ahead of this statement, and a
                # stale expansion could merge the read past a write to a
                # column that exists only in the new schema.
                reads = None
            if reads is not None and stmt.order_by is not None:
                reads.add(stmt.order_by)
            if reads is not None:
                reads |= set(stmt.payloads)
                reads = clean(reads)
            return StatementShape(("select", stmt), stmt.table, "select",
                                  True, False, reads, frozenset())
        if isinstance(stmt, S.Insert):
            # inserts write validity (and may LRU-evict): every read's row
            # set is at stake -> whole-table write footprint
            return StatementShape(("insert", stmt), stmt.table, "insert",
                                  True, True, frozenset(), None)
        if isinstance(stmt, S.Delete):
            return StatementShape(("delete", stmt), stmt.table, "delete",
                                  True, True,
                                  clean(PL.columns_of(stmt.where)), None)
        if isinstance(stmt, S.Update):
            reads = set(PL.columns_of(stmt.where))
            writes = set()
            for col, expr in stmt.sets:
                writes.add("_ttl" if col.upper() == "TTL" else col)
                reads |= set(PL.columns_of(expr))
            return StatementShape(("update", stmt), stmt.table, "update",
                                  True, True, clean(reads), clean(writes))
        if isinstance(stmt, (S.Explain, S.ShowMetrics, S.ShowSlow)):
            # pure metadata (host counters only): never merges, never
            # fences — SHOW METRICS / SHOW SLOW may overlap live waves
            return StatementShape(("explain", stmt), None, "explain",
                                  False, False, frozenset(), frozenset())
        if isinstance(stmt, S.ExplainAnalyze):
            # executes its inner statement: admin barrier on its table
            return StatementShape(("admin", stmt),
                                  getattr(stmt.inner, "table", None),
                                  "admin", False, True)
        table = getattr(stmt, "table", None)
        return StatementShape(("admin", stmt), table, "admin", False, True)

    def group_shard_ids(self, shape: StatementShape | None,
                        params_list: Sequence[Sequence[Any]]
                        ) -> frozenset | None:
        """The exact set of shard ids a batch of same-shape statements
        will touch, when that is provable host-side: the table is sharded
        and every statement prunes (eq on the partition column, or an
        INSERT whose partition value is a literal/placeholder). ``None``
        means unknown / fan-out / unsharded — the scheduler treats it as
        touching every shard. Two groups with disjoint id sets commute,
        which lets the batch scheduler overlap independent-shard traffic
        on one table: a SINGLETON id set additionally routes the whole
        group onto that shard's execution lane (see ``_exec_mode``), so
        the scheduler only locks that one lane."""
        if shape is None or shape.table is None:
            return None
        t = self.tables.get(shape.table)
        if t is None or not SH.is_sharded(t.schema):
            return None
        stmt = shape.key[1] if len(shape.key) == 2 else None
        if stmt is None:
            return None
        return self._shard_ids_of(t, stmt, params_list)

    def _shard_ids_of(self, t: _Table, stmt,
                      params_list: Sequence[Sequence[Any]],
                      pvals=None) -> frozenset | None:
        """Host-side shard routing for one statement (group) — the body
        behind :meth:`group_shard_ids`, shared with the daemon's own
        lane-route decision. ``pvals`` lets the INSERT path reuse an
        extraction the caller already paid for."""
        n = t.schema.shards
        if isinstance(stmt, S.Insert):
            if pvals is None:
                pvals = self._insert_pvals(t, stmt, params_list)
            if pvals is None:
                return None
            return frozenset(SH.shard_of_host(v, n) for v in pvals)
        if not isinstance(stmt, (S.Select, S.Update, S.Delete)):
            return None
        route = PL.plan_shards(t.schema, self._intern_ast(stmt.where))
        if route.key is None:
            return None
        kind, v = route.key.value
        out = set()
        for pr in params_list:
            if kind == "const":
                val = v
            else:
                if v >= len(pr):
                    return None
                val = self._host_pval(pr[v])
                if val is None:
                    return None
            out.add(SH.shard_of_host(int(val), n))
        return frozenset(out)

    def _host_pval(self, val) -> int | None:
        """Normalize one bound partition-key value for host-side
        routing: TEXT interned to its id, ints passed through, anything
        non-integer (floats keep exact-compare semantics on the scan
        path) -> None. THE value rule for every host routing consumer —
        `_shard_ids_of` and `_insert_pvals` — so INSERT and
        SELECT/UPDATE/DELETE routing can never drift apart."""
        if isinstance(val, str):
            val = self.interner.intern(val)
        if isinstance(val, bool) or not isinstance(val, (int, np.integer)):
            return None
        return int(val)

    def _insert_pvals(self, t: _Table, stmt,
                      params_list: Sequence[Sequence[Any]]
                      ) -> list | None:
        """The host-readable partition value of every row of an INSERT
        batch (ints, TEXT interned), or None when the value is not
        provable (computed expression, non-integer binding). ONE
        extractor feeds both shard routing (:meth:`_shard_ids_of`) and
        the ``inserted_rows`` skew counter (:meth:`_insert_sids`)."""
        pcol = t.schema.partition_by
        cols = stmt.columns or t.schema.column_names[: len(stmt.values)]
        if pcol not in cols:
            # omitted partition column inserts its default (0)
            return [0] * len(params_list)
        vast = stmt.values[list(cols).index(pcol)]
        if isinstance(vast, P.Const) and isinstance(vast.value, int) \
                and not isinstance(vast.value, bool):
            return [int(vast.value)] * len(params_list)
        if not isinstance(vast, P.Param):
            return None
        j = vast.index
        out = []
        for pr in params_list:
            if j >= len(pr):
                return None
            val = self._host_pval(pr[j])
            if val is None:
                return None
            out.append(val)
        return out

    def execute_async(
        self,
        sql: str,
        params: Sequence[Any] = (),
        payloads: Mapping[str, Any] | None = None,
    ) -> Result:
        """Enqueue a statement without any device round trip (the returned
        :class:`Result` is lazy — see the module docstring). ``execute`` is
        already sync-free; this alias names the intent at call sites that
        pipeline statements and ``drain()`` later."""
        return self.execute(sql, params, payloads)

    def drain(self, table: str | None = None) -> None:
        """Block until every enqueued device op for ``table`` (default: all
        tables) has retired. The pipeline barrier matching execute_async."""
        names = [table] if table else list(self.tables)
        for nm in names:
            t = self._table(nm)
            jax.block_until_ready(t.lanes if t.lanes is not None
                                  else t.state)

    def _do_create(self, stmt: S.CreateTable) -> Result:
        from repro.core.sqlparse import _PAYLOAD_DTYPES

        schema = make_schema(
            stmt.table,
            list(stmt.columns),
            [(n, s, _PAYLOAD_DTYPES[d]) for (n, s, d) in stmt.payloads],
            capacity=stmt.capacity,
            max_select=stmt.max_select,
            expiry=ExpiryPolicy(stmt.ttl, stmt.max_rows, stmt.ops_interval),
            indexes=stmt.indexes,
            shards=stmt.shards,
            partition_by=stmt.partition_by,
            replicas=stmt.replicas,
        )
        self.tables[stmt.table] = self._make_table(schema)
        if self.warmup:
            # pre-plan the canonical hot shapes off the dispatch thread:
            # by the time traffic lands, every placed lane device already
            # holds its eq-SELECT/INSERT/DELETE executables
            th = threading.Thread(target=self._warm_table_bg,
                                  args=(stmt.table,),
                                  name=f"warmup-{stmt.table}", daemon=True)
            self._warm_threads[stmt.table] = th
            th.start()
        return Result()

    def _mesh_for(self, schema: TableSchema):
        """The placement mesh this daemon gives an ``schema.shards``-way
        table (None = unplaced — unsharded table, kill-switch off, or a
        single visible device)."""
        if not SH.is_sharded(schema) or not self.mesh_exec:
            return None
        return lane_mesh_for(schema.shards)

    def _make_table(self, schema: TableSchema) -> _Table:
        n = schema.shards
        lock = LK.make_lock(f"table:{schema.name}")
        if SH.is_sharded(schema):
            mesh = self._mesh_for(schema)
            lanes = SH.place_lanes(mesh, SH.init_lanes(schema))
            return _Table(schema, None, eng=SH, lanes=lanes, mesh=mesh,
                          lock=lock,
                          lane_ticks=[0] * n, expire_due=[None] * n,
                          stmt_routed=np.zeros(n, np.int64),
                          writes_routed=np.zeros(n, np.int64),
                          rows_in=np.zeros(n, np.int64))
        return _Table(schema, T.init_state(schema), eng=T, lock=lock,
                      stmt_routed=np.zeros(1, np.int64),
                      writes_routed=np.zeros(1, np.int64),
                      rows_in=np.zeros(1, np.int64))

    @staticmethod
    def _colocate(lanes: list, mesh) -> list:
        """One-device copies of per-lane states: the admin paths below
        stack/concat lanes (or feed them all into one jitted call), and
        jnp refuses mixed-device operands — so mesh-placed lanes stage
        through the first device first. No-op when unplaced."""
        if mesh is None:
            return list(lanes)
        dev = jax.devices()[0]
        return [jax.device_put(l, dev) for l in lanes]

    def _do_reindex(self, name: str) -> Result:
        """REINDEX t: bulk-rebuild every hash index from the live rows —
        the recovery path after a bucket overflow (``stale``) once the
        offending duplicate burst has been deleted or expired. Returns
        the residual overflow count as ``value`` (0 = probes are back).
        Sharded tables rebuild lane by lane (the index reads no clock,
        so no catch-up is involved)."""
        t = self._table(name)
        if not t.schema.indexes:
            return Result(count=0, value=0)
        # rebuilt indexes change probe behaviour for every cached plan:
        # retire the pre-planned executables (schema epoch bump) before
        # building fresh ones under the new epoch
        t.execs.bump()
        if t.lanes is None:
            key = ("reindex", t.schema)
            fn = self._executor(
                t, key, lambda: jax.jit(
                    lambda st: T.build_index(t.schema, st),
                    donate_argnums=0))
            t.state = fn(t.state, placement=self._placement(t, "mono",
                                                            None))
            residual = sum(int(np.sum(np.asarray(
                t.state["indexes"][c]["stale"]))) for c in t.schema.indexes)
            return Result(count=len(t.schema.indexes), value=residual)
        s_sch = SH.shard_schema(t.schema)
        key = ("lane", "reindex", s_sch)
        fn = self._executor(
            t, key, lambda: jax.jit(
                lambda st: T.build_index(s_sch, st), donate_argnums=0))
        for i in range(t.schema.shards):
            t.lanes[i] = fn(t.lanes[i],
                            placement=self._placement(t, "lane", i))
        residual = sum(int(np.sum(np.asarray(
            lane["indexes"][c]["stale"])))
            for lane in t.lanes for c in t.schema.indexes)
        return Result(count=len(t.schema.indexes), value=residual)

    def _do_flush(self, name: str) -> Result:
        t = self._table(name)
        # FLUSH keeps the schema epoch: it empties contents but changes
        # no shapes or placements, so every pre-planned executable stays
        # valid (warmed daemons flush their warm-up rows for free)
        if t.lanes is None:
            key = ("flush", t.schema)
            fn = self._executor(
                t, key,
                lambda: jax.jit(lambda st: T.flush(t.schema, st)))
            t.state, n = fn(t.state,
                            placement=self._placement(t, "mono", None))
            return Result(dev={"count": n})
        mode = "mesh" if t.mesh is not None else "stacked"
        key = (mode, "flush", t.schema)
        fn = self._executor(
            t, key, lambda: self._jit_exec(
                t.schema, lambda st: SH.flush(t.schema, st), mode, SH,
                _exec_name("flush")))
        n, = self._run_state(t, fn, mode, None, False, 1, ())
        return Result(dev={"count": n})

    def _do_show_stats(self, name: str | None) -> Result:
        """SHOW STATS t (= ``EXPLAIN t``): the per-shard skew report —
        live rows straight from each lane's validity bits plus the
        host-side routed-statement counters — as one JSON ``VALUE`` row,
        observable from any socket client. A hot shard shows up as one
        lane's counters and row count running away from its peers.
        Mesh-placed tables report each lane's device id from host-side
        placement metadata (``shards.lane_devices`` — never a
        cross-device sync, so the report can't stall dispatches).

        Without a table, the daemon-wide roll-up: every table's live
        rows, summed executor-cache counters, the scheduler/server stats
        registered via ``telemetry.attach`` and daemon uptime."""
        if name is None:
            return self._do_show_stats_all()
        t = self._table(name)
        n = t.schema.shards
        if t.lanes is None:
            live = [int(T.live_count(t.state))]
            devs = None
        else:
            # caught-up snapshot: deferred expiry replays applied, so the
            # report never counts rows the lockstep engine already dropped
            live = [int(T.live_count(lane))
                    for lane in self._caught_up_lanes(t)]
            placed = SH.lane_devices(t.mesh, n)
            devs = ([d.id for d in placed] if placed is not None
                    else [next(iter(lane["valid"].devices())).id
                          for lane in t.lanes])
        with t.lock:
            stmts = t.stmt_routed.tolist()
            writes = t.writes_routed.tolist()
            rows_in = t.rows_in.tolist()
            host_ops = t.host_ops
        per = [{"shard": i, "live_rows": live[i], "statements": stmts[i],
                "writes": writes[i], "inserted_rows": rows_in[i],
                **({"device": devs[i]} if devs is not None else {})}
               for i in range(n)]
        info = {"table": name, "shards": n,
                "devices": (len(t.mesh.devices.reshape(-1))
                            if t.mesh is not None else 1),
                "replicas": t.schema.replicas,
                "partition_by": t.schema.partition_by,
                "capacity": t.schema.capacity,
                "shard_capacity": (SH.shard_capacity(t.schema) if n > 1
                                   else t.schema.capacity),
                "host_ops": host_ops,
                # AOT executor-cache counters (core/execache.py): cached
                # executables, compiles + total compile wall time, and
                # serve-path hit/miss traffic
                "executors": t.execs.stats_dict(),
                "per_shard": per}
        return Result(count=n, value=json.dumps(info, sort_keys=True))

    def _do_show_stats_all(self) -> Result:
        """``SHOW STATS`` with no table: the daemon-wide roll-up. Admin
        barrier like the per-table form — live-row counts sync each
        table's validity bits, which is fine off the serving path."""
        tables = {}
        exec_totals: dict[str, Any] = {"cached": 0, "entries": 0, "hits": 0,
                                       "misses": 0, "compiles": 0,
                                       "fallbacks": 0,
                                       "compile_ms_total": 0.0}
        for name, t in sorted(self.tables.items()):
            ed = t.execs.stats_dict()
            for k in exec_totals:
                exec_totals[k] += ed[k]
            tables[name] = {"shards": t.schema.shards,
                            "live_rows": self.live_rows(name),
                            "host_ops": t.host_ops}
        exec_totals["compile_ms_total"] = round(
            exec_totals["compile_ms_total"], 3)
        info = {"tables": tables,
                "executors": exec_totals,
                "uptime_s": self.telemetry.uptime_s(),
                "telemetry": self.telemetry.enabled,
                # lock-order sanitizer state (lint/lockorder.py): armed
                # bit + observed acquisition-order edges/cycles, so chaos
                # runs are auditable from the wire
                "lockcheck": LK.summary(),
                **self.telemetry.sources()}
        return Result(count=len(tables),
                      value=json.dumps(info, sort_keys=True))

    def _do_show_metrics(self, stmt: S.ShowMetrics) -> Result:
        """SHOW METRICS [t] [FORMAT 'prom']: the serving-telemetry
        report. Host counters and monotonic-clock aggregates only —
        never a device sync, so it can run mid-traffic without stalling
        dispatches. The prom exposition is multi-line text, so it ships
        JSON-string-encoded to stay one VALUE wire line."""
        if stmt.table is not None:
            self._table(stmt.table)   # unknown table -> SQLError
        rep = self.telemetry.report(stmt.table)
        if stmt.fmt == "prom":
            return Result(count=len(rep["shapes"]),
                          value=json.dumps(TEL.prom(rep)))
        return Result(count=len(rep["shapes"]),
                      value=json.dumps(rep, sort_keys=True))

    def _do_show_slow(self) -> Result:
        """SHOW SLOW: the bounded slow-statement ring (span trees of
        statements that crossed ``slow_ms``), oldest first."""
        entries = [tr.to_dict() for tr in self.telemetry.slow_entries()]
        return Result(count=len(entries), rows=entries)

    def _do_explain_analyze(self, stmt: S.ExplainAnalyze,
                            params: Sequence[Any] = ()) -> Result:
        """EXPLAIN ANALYZE <stmt>: execute the inner statement and
        report its measured per-stage spans next to the plan. When the
        statement arrived over the wire, the scheduler's ambient trace
        already carries the wire/parse/queue/lock spans — this handler
        adds execute + render (it materializes the inner result: a
        diagnostic statement pays the sync the response flusher would).
        Called directly (no scheduler), it traces just its own stages."""
        amb = TEL.current_traces()
        tr = amb[0] if amb else TEL.Trace()
        try:
            plan = json.loads(self._do_explain(stmt.inner).value)
        except S.SQLError:
            plan = {"statement": type(stmt.inner).__name__.lower()}
        with TEL.dispatch_span([tr]):
            res = self._dispatch_stmt(stmt.inner, params)
            tr.mark("execute")
            TEL.render_begin(tr)
            try:
                count = res.count
                _ = res.rows
                _ = res.value
            finally:
                TEL.render_end(tr)
        # the six stages sum to the wall clock; children lie inside them
        info = {"analyze": True,
                "plan": plan,
                "stages": {k: round(v, 1)
                           for k, v in tr.stage_totals().items()},
                "children": {k: round(v, 1)
                             for k, v in tr.child_totals().items()},
                "total_us": round((tr.last - tr.t0) * 1e6, 1),
                "count": count}
        if tr.mode is not None:
            info["exec_mode"] = tr.mode
        if tr.cache is not None:
            info["cache"] = tr.cache
        if tr.compile_ms:
            info["compile_ms"] = round(tr.compile_ms, 3)
        if tr.group is not None:
            info["group"] = tr.group
        if tr.wave is not None:
            info["wave"] = tr.wave
        return Result(count=count, value=json.dumps(info, sort_keys=True))

    def _do_reshard(self, stmt: S.AlterReshard) -> Result:
        """ALTER TABLE t RESHARD n: live re-partition. One bulk
        device-side re-split of every live row (``shards.reshard``; row
        metadata and TTL stamps ride along verbatim, so contents
        round-trip exactly) plus one hash-index rebuild per new shard.
        ``n = 1`` converts back to a monolithic table; resharding a
        monolithic table partitions it. Refused (table untouched — the
        old state is never donated) when skew would overflow a new
        shard's capacity. Admin barrier at the scheduler. The skew
        counters (``statements``/``writes``/``inserted_rows``) CARRY
        through the re-split: per-shard attribution under the old map is
        meaningless under the new one, so each total is re-spread evenly
        across the new lanes (remainder to the low shards) — ``SHOW
        STATS`` totals are invariant across a RESHARD."""
        t = self._table(stmt.table)
        old_schema = t.schema
        new_n = stmt.shards
        if new_n == old_schema.shards:
            return Result(count=self.live_rows(stmt.table), value=new_n)
        try:
            new_schema = dataclasses.replace(old_schema, shards=new_n)
        except (ValueError, KeyError) as e:
            raise S.SQLError(str(e)) from e
        if t.lanes is not None:
            # mesh-placed lanes stage through one device: the re-split
            # concatenates every lane's rows in one jitted call
            lanes = self._colocate(self._caught_up_lanes(t), t.mesh)
        else:
            lanes = [t.state]
        key = ("reshard", old_schema, new_schema)
        fn = self._executor(
            t, key, lambda: jax.jit(
                lambda ls: SH.reshard(old_schema, new_schema, ls)))
        new_lanes, counts = fn(tuple(lanes))
        counts = np.asarray(counts)  # admin op: the sync is fine
        cap_new = (SH.shard_capacity(new_schema) if new_n > 1
                   else new_schema.capacity)
        if int(counts.max()) > cap_new:
            raise S.SQLError(
                f"RESHARD {new_n}: {int(counts.max())} live rows hash to "
                f"one shard but a shard holds only {cap_new} — resolve "
                f"the skew (or raise CAPACITY) first")
        # re-place on the NEW shard count's mesh (device counts may
        # differ — the divisor policy re-evaluates per shard count)
        new_mesh = self._mesh_for(new_schema)
        with t.lock:
            g0 = t.ticks_total
            if new_n > 1:
                t.lanes = SH.place_lanes(new_mesh, list(new_lanes))
                t.state = None
                t.eng = SH
            else:
                t.state = new_lanes[0]
                t.lanes = None
                t.eng = T
            t.mesh = new_mesh
            t.schema = new_schema
            t.lane_ticks = [g0] * new_n
            t.expire_due = [None] * new_n
            t.stmt_routed = self._respread(t.stmt_routed, new_n)
            t.writes_routed = self._respread(t.writes_routed, new_n)
            t.rows_in = self._respread(t.rows_in, new_n)
            # every cached executable was compiled for the OLD shard
            # count / placement: retire them atomically with the swap
            t.execs.bump()
        return Result(count=int(counts.sum()), value=new_n)

    @staticmethod
    def _respread(old: np.ndarray, new_n: int) -> np.ndarray:
        """Carry a per-shard counter through a RESHARD: the old per-shard
        attribution is tied to the old shard map, so the TOTAL is re-
        attributed uniformly across the new lanes (remainder to the low
        shards). Totals — what capacity planning reads — are exactly
        preserved; only the (now meaningless) old split is smoothed."""
        total = int(old.sum())
        out = np.full(new_n, total // new_n, np.int64)
        out[: total % new_n] += 1
        return out

    def _do_retain(self, stmt: S.AlterRetain) -> Result:
        """ALTER TABLE t RETAIN SLOTS i,j,... OF m: keep only the rows
        whose partition value hashes (``shards.shard_of`` at modulus m)
        into the given cluster slots; everything else is masked dead in
        one device pass. This is the cluster handover primitive: after a
        ring change the shrunk holder RETAINs the slots it still owns —
        the moved 1/N of the keyspace is dropped locally because a new
        owner already restored it from a checkpoint. Validity-only (like
        DELETE): indexes mask dead rows at probe time, TTL stamps are
        untouched. Returns the number of rows dropped."""
        t = self._table(stmt.table)
        pby = t.schema.partition_by
        if pby is None:
            raise S.SQLError(
                f"RETAIN: table {stmt.table!r} has no PARTITION BY column "
                f"(cluster slot ownership needs a partition key)")
        sch = (SH.shard_schema(t.schema) if t.lanes is not None
               else t.schema)
        key = ("retain", sch, pby, stmt.slots, stmt.of)

        def build():
            slots = jnp.asarray(stmt.slots, jnp.int32)

            def run(st):
                slot = SH.shard_of(st["cols"][pby].astype(jnp.int32),
                                   stmt.of)
                member = (slot[:, None] == slots[None, :]).any(axis=-1)
                dropped = jnp.sum((st["valid"] & ~member).astype(jnp.int32))
                return dict(st, valid=st["valid"] & member), dropped

            return jax.jit(run, donate_argnums=0)

        fn = self._executor(t, key, build)
        if t.lanes is None:
            t.state, d = fn(t.state,
                            placement=self._placement(t, "mono", None))
            return Result(count=int(d), value=len(stmt.slots))
        total = 0
        for i in range(t.schema.shards):
            t.lanes[i], d = fn(t.lanes[i],
                               placement=self._placement(t, "lane", i))
            total += int(d)
        return Result(count=total, value=len(stmt.slots))

    def _do_checkpoint(self, stmt: S.Checkpoint) -> Result:
        """CHECKPOINT t TO 'dir': atomic on-disk snapshot of the table via
        ``checkpoint/store.py`` (step 0; ``step_0.tmp/`` -> rename, one
        .npy per leaf). Sharded tables save the caught-up STACKED layout
        so the snapshot is lockstep-consistent. TEXT columns hold ids
        from THIS daemon's interner, so the interner's string table rides
        along in the meta — RESTORE on any daemon re-interns and remaps.
        Returns live rows saved; ``value`` is the directory."""
        from repro.checkpoint import store as CK

        t = self._table(stmt.table)
        if t.lanes is None:
            state = t.state
            live = int(T.live_count(state))
        else:
            state = SH.stack_lanes(
                self._colocate(self._caught_up_lanes(t), t.mesh))
            live = int(np.sum(np.asarray(state["valid"])))
        meta = {
            "table": stmt.table,
            "shards": t.schema.shards,
            "capacity": t.schema.capacity,
            "live_rows": live,
            "strings": list(self.interner._rev),
        }
        CK.save(stmt.path, 0, state, meta=meta)
        return Result(count=live, value=stmt.path)

    def _do_restore(self, stmt: S.Restore) -> Result:
        """RESTORE t FROM 'dir': replace the table's contents with a
        CHECKPOINT snapshot — the replica-bootstrap path. The table must
        already exist with a matching schema (the cluster client replays
        the CREATE first). Cross-process correctness: saved TEXT ids are
        the SOURCE daemon's interner ids, so each saved string is
        re-interned HERE and a lut rewrites every TEXT column; because
        that moves partition hashes, rows are then re-split through the
        RESHARD machinery, so shard pruning and index probes stay exact.
        The restore is ELASTIC across shard counts and mesh sizes: the
        snapshot's own ``shards`` count is read from its meta, the
        snapshot is loaded in ITS layout, re-split into this table's
        shard count, and the lanes are placed on THIS process's mesh —
        a checkpoint taken on 8 devices round-trips onto 1 and back.
        Refused on overflow skew, like RESHARD; the old contents are
        never touched before the skew check passes (the snapshot is
        validated against its own saved layout)."""
        from repro.checkpoint import store as CK

        t = self._table(stmt.table)
        try:
            raw = json.loads((pathlib.Path(stmt.path) / "step_0" /
                              "meta.json").read_text())
        except FileNotFoundError as e:
            raise S.SQLError(f"RESTORE: no checkpoint at {stmt.path!r} "
                             f"({e})") from e
        saved_n = int(raw.get("meta", {}).get("shards", t.schema.shards))
        try:
            saved_sch = (t.schema if saved_n == t.schema.shards
                         else dataclasses.replace(t.schema, shards=saved_n))
            # `like` is built in the SNAPSHOT's layout (shapes/dtypes
            # only) — restoring never depends on the live table's shape
            like = (T.init_state(saved_sch) if saved_n == 1
                    else SH.stack_lanes(SH.init_lanes(saved_sch)))
            state, info = CK.restore(stmt.path, 0, like)
        except FileNotFoundError as e:
            raise S.SQLError(f"RESTORE: no checkpoint at {stmt.path!r} "
                             f"({e})") from e
        except (KeyError, ValueError) as e:
            raise S.SQLError(
                f"RESTORE: checkpoint at {stmt.path!r} does not match "
                f"table {stmt.table!r}'s schema ({e})") from e
        saved_meta = info.get("meta", {})
        strings = saved_meta.get("strings") or [""]
        text_cols = t.schema.text_columns()
        if text_cols:
            lut = np.zeros(len(strings), np.int32)
            for i, s in enumerate(strings):
                if i:  # id 0 is the reserved empty/NULL id on every daemon
                    lut[i] = self.interner.intern(s)
            cols = dict(state["cols"])
            for c in text_cols:
                ids = np.asarray(state["cols"][c])
                cols[c] = jnp.asarray(lut[np.clip(ids, 0, len(lut) - 1)])
            state = dict(state, cols=cols)
        lanes = ([state] if saved_n == 1
                 else SH.split_lanes(saved_sch, state))
        key = ("reshard", saved_sch, t.schema)
        fn = self._executor(
            t, key, lambda: jax.jit(
                lambda ls: SH.reshard(saved_sch, t.schema, ls)))
        new_lanes, counts = fn(tuple(lanes))
        counts = np.asarray(counts)  # admin op: the sync is fine
        cap = (SH.shard_capacity(t.schema) if t.schema.shards > 1
               else t.schema.capacity)
        if int(counts.max()) > cap:
            raise S.SQLError(
                f"RESTORE: {int(counts.max())} restored rows hash to one "
                f"shard but a shard holds only {cap}")
        with t.lock:
            g0 = t.ticks_total
            if t.lanes is None:
                t.state = new_lanes[0]
            else:
                t.lanes = SH.place_lanes(t.mesh, list(new_lanes))
            t.lane_ticks = [g0] * t.schema.shards
            t.expire_due = [None] * t.schema.shards
            # restored contents were re-split and re-placed: retire the
            # pre-planned executables with the swap (mesh re-placement)
            t.execs.bump()
        return Result(count=int(counts.sum()), value=stmt.path)

    def _do_explain(self, stmt: S.Statement) -> Result:
        """EXPLAIN <stmt>: report (don't run) the inner statement's plan
        as one VALUE row of JSON — index-probe / fused-scan / generic-scan
        plus the column footprint, observable from any socket client."""
        if isinstance(stmt, (S.Select, S.Update, S.Delete)):
            t = self._table(stmt.table)
            where = self._intern_ast(stmt.where)
            ranked = isinstance(stmt, S.Select) and stmt.order_by is not None
            info = PL.explain(t.schema, where, ranked=ranked)
            info["statement"] = type(stmt).__name__.lower()
            # pre-planned = every placement this statement can route to
            # already holds its AOT executable (host sig set, no sync)
            info["preplanned"] = self._preplanned(t, stmt)
            if t.mesh is not None:
                # placement report from host metadata only (no sync): a
                # const-pruned route names the one device it dispatches
                # to, anything else names the whole mesh
                route = PL.plan_shards(t.schema, where)
                if route.key is not None and route.key.value[0] == "const":
                    sid = SH.shard_of_host(int(route.key.value[1]),
                                           t.schema.shards)
                    info["device"] = SH.lane_devices(
                        t.mesh, t.schema.shards)[sid].id
                else:
                    info["devices"] = len(t.mesh.devices.reshape(-1))
            if info["plan"] == "index-probe":
                # surface index health: stale > 0 means every probe is
                # currently taking the scan fallback (REINDEX recovers).
                # Sharded tables report the stale total across lanes.
                if t.lanes is not None:
                    info["stale"] = sum(int(np.sum(np.asarray(
                        lane["indexes"][info["index"]]["stale"])))
                        for lane in t.lanes)
                else:
                    info["stale"] = int(np.sum(np.asarray(
                        t.state["indexes"][info["index"]]["stale"])))
            return Result(count=1, value=json.dumps(info, sort_keys=True))
        info = {"statement": type(stmt).__name__.lower(),
                "plan": "insert" if isinstance(stmt, S.Insert) else "admin"}
        table = getattr(stmt, "table", None)
        if table is not None:
            info["table"] = table
            t = self.tables.get(table)
            if t is not None and isinstance(stmt, S.Insert):
                info["preplanned"] = self._preplanned(t, stmt)
            if (t is not None and SH.is_sharded(t.schema)
                    and isinstance(stmt, S.Insert)):
                # inserts always hash-route row-by-row (one device split)
                info["shards"] = t.schema.shards
                info["shard_route"] = f"split x {t.schema.shards}"
        return Result(count=1, value=json.dumps(info, sort_keys=True))

    def executemany(
        self,
        sql: str,
        params_list: Sequence[Sequence[Any]],
        payloads_list: Sequence[Mapping[str, Any]] | None = None,
        *,
        per_statement: bool = False,
    ) -> "Result | list[Result]":
        """Micro-batch one statement over many parameter rows — ONE device
        dispatch per call (rows are padded to a power-of-two bucket so one
        compiled executor serves many batch sizes).

        INSERT/DELETE/UPDATE return a single aggregate :class:`Result`.
        SELECT (row reads AND aggregates) returns ``list[Result]`` — one
        per parameter row (empty list for an empty ``params_list``), all
        views into one stacked transfer.

        ``per_statement=True`` makes EVERY statement kind return
        ``list[Result]`` with per-statement counts under sequential
        semantics (the wire scheduler needs one response per client
        statement): DELETE counts credit overlapping rows to the earliest
        statement (the one-pass sorted-membership path attributes in the
        same pass for the eq shape; other shapes take the vectorized
        union path), UPDATE counts come from the scan, INSERT rows count
        1 each with the batch's eviction total as ``value``."""
        stmt = self._parse(sql)
        if isinstance(stmt, (S.Delete, S.Update)):
            return self._do_batch_dml(stmt, params_list,
                                      per_statement=per_statement)
        if isinstance(stmt, S.Select):
            return self._do_batch_select(stmt, params_list)
        if not isinstance(stmt, S.Insert):
            raise S.SQLError("executemany supports INSERT/SELECT/DELETE/"
                             "UPDATE")
        return self._do_insert_batch(stmt, params_list, payloads_list,
                                     per_statement=per_statement)

    def _do_insert_batch(self, stmt: S.Insert,
                         params_list: Sequence[Sequence[Any]],
                         payloads_list=None, *, per_statement: bool = False,
                         _warm=None) -> "Result | list[Result] | int":
        """The INSERT arm of :meth:`executemany` (see its docstring).
        ``_warm=(mode, sid)`` pre-plans the b=1 executor for that
        dispatch shape instead of running — abstract state avals,
        placeholder params, no clock ticks (returns the compile count)."""
        t = self._table(stmt.table)
        schema = t.schema
        cols = stmt.columns or schema.column_names[: len(stmt.values)]
        if len(cols) != len(stmt.values):
            raise S.SQLError("INSERT column/value count mismatch")
        if _warm is None:
            n = len(params_list)
            if n == 0:
                return [] if per_statement else Result(count=0)
        else:
            n = 1
        b = _bucket(n)
        # host-side param matrix [b, n_params]
        n_params = max((P.collect_params(v) for v in stmt.values), default=0)
        if stmt.ttl is not None:
            n_params = max(n_params, P.collect_params(stmt.ttl))
        pm = []
        for i in range(b):
            row = ((0,) * n_params if _warm is not None
                   else params_list[min(i, n - 1)])
            pm.append(self._prep_params(row))
        param_cols = tuple(
            np.asarray([pm[i][j] for i in range(b)]) for j in range(n_params)
        )
        row_mask = np.arange(b) < n

        pl_args = {}
        for p in schema.payloads:
            if payloads_list and p.name in (payloads_list[0] or {}):
                arrs = [np.asarray(pl[p.name]) for pl in payloads_list]
                # stack rows (concatenate would join along the first payload
                # axis and corrupt every non-power-of-two batch)
                pl_args[p.name] = np.stack(arrs + [arrs[-1]] * (b - n))

        values_ast = tuple(self._intern_ast(v) for v in stmt.values)
        ttl_ast = self._intern_ast(stmt.ttl) if stmt.ttl is not None else None
        if _warm is None:
            # ONE partition-value extraction per dispatch: it feeds the
            # lane route AND the inserted_rows skew counter
            pvals = (self._insert_pvals(t, stmt, pm[:n])
                     if t.lanes is not None else None)
            mode, eng, xsch, sid, flag = self._exec_mode(t, stmt, pm[:n],
                                                         n, pvals=pvals)
        else:
            mode, sid = _warm
            eng, xsch = self._warm_env(t, mode)
        key = (mode, "insert", xsch, values_ast, ttl_ast, tuple(cols), b,
               tuple(sorted(pl_args)))

        def build():
            def base(state, off_d, param_cols, pl_args, row_mask):
                values = {}
                for cname, vast in zip(cols, values_ast):
                    v = P.eval_expr(vast, {}, param_cols)
                    values[cname] = jnp.broadcast_to(jnp.asarray(v), (b,))
                ttl = 0
                if ttl_ast is not None:
                    ttl = P.eval_expr(ttl_ast, {}, param_cols)
                state, slots, ev = eng.insert(xsch, state, values, pl_args,
                                              row_mask, ttl)
                if mode == "lane":
                    slots = slots + off_d  # globalize this lane's row ids
                return state, slots, ev

            return self._jit_exec(xsch, base, mode, eng,
                                  _exec_name("insert", batch=True))

        fn = self._executor(t, key, build)
        if _warm is not None:
            return self._finish_warm(
                t, fn, stmt, "insert", b, mode, sid,
                (jnp.int32(0), param_cols, pl_args, row_mask))
        off = sid * SH.shard_capacity(schema) if mode == "lane" else 0
        slots, evicted = self._run_state(
            t, fn, mode, sid, flag, 1,
            (jnp.int32(off), param_cols, pl_args, row_mask))
        self._note_sig(t, stmt, "insert", b, mode, sid)
        self._note_route(t, sid, n, True,
                         rows_in=self._insert_sids(t, pvals, n))
        if per_statement:
            # one row per statement; evictions have no per-statement
            # attribution, so each Result reports the batch's eviction
            # total as its (lazy, shared-sync) value — the wire response
            # keeps the same COUNT/VALUE shape whether or not a statement
            # rode a cross-connection group
            return [Result(count=1, dev={"value": evicted})
                    for _ in range(n)]
        return Result(count=n, dev={"row_ids": slots, "value": evicted},
                      ctx={"nshow": n})

    def _do_batch_dml(self, stmt, params_list: Sequence[Sequence[Any]],
                      per_statement: bool = False) -> "Result | list[Result]":
        """Micro-batch same-executor DELETE/UPDATE statements into ONE
        dispatch. Single-column equality DELETEs (the Table 2 hot shape,
        ``... WHERE page_id = ?``) collapse into ONE pass over the table
        (sorted multi-value membership — see T.delete_many_eq); other
        DELETEs vectorize to a [W, capacity] union (deletes commute, so
        the union count equals the sequential total). UPDATEs keep a
        ``lax.scan`` so later statements observe earlier SETs. Padded rows
        are deactivated via ``extra_mask``/``active``.

        ``per_statement=True`` returns ``list[Result]`` whose counts match
        sequential execution: a row deleted by several statements in the
        batch is credited to the earliest — the eq fast path attributes
        via its stable sort in the same pass; other DELETE shapes use an
        exclusive-claim cumsum over the [W, capacity] masks."""
        t = self._table(stmt.table)
        n = len(params_list)
        if n == 0:
            return [] if per_statement else Result(count=0)
        is_delete = isinstance(stmt, S.Delete)
        if not is_delete:
            self._check_partition_update(t, (c for c, _ in stmt.sets))
        mode, eng, xsch, sid, flag = self._exec_mode(t, stmt, params_list,
                                                     n)
        b = _bucket(n)
        where = self._intern_ast(stmt.where)
        sets = ()
        n_params = P.collect_params(where)
        if not is_delete:
            sets = tuple((c, self._intern_ast(e)) for c, e in stmt.sets)
            for _, e in sets:
                n_params = max(n_params, P.collect_params(e))
        pm = [self._prep_params(params_list[min(i, n - 1)])
              for i in range(b)]
        param_cols = tuple(
            np.asarray([pm[i][j] for i in range(b)]) for j in range(n_params)
        )
        active = np.arange(b) < n
        fused = eng._fused_plan(xsch, where) if is_delete else None
        eq_term = (fused.terms[0]
                   if fused is not None and len(fused.terms) == 1
                   and fused.terms[0].op == "==" else None)
        if (eq_term is not None and eq_term.value[0] == "param"
                and not np.issubdtype(param_cols[eq_term.value[1]].dtype,
                                      np.integer)):
            eq_term = None  # float param: keep exact-compare semantics
        update_plan = None
        idx_rebuild = ()
        if not is_delete:
            set_cols = {("_ttl" if c.upper() == "TTL" else c)
                        for c, _ in sets}
            idx_rebuild = tuple(c for c in xsch.indexes if c in set_cols)
            update_plan = eng.plan_for(xsch, where)
            if isinstance(update_plan, PL.IndexProbe) and (
                    idx_rebuild
                    or not _np_terms_int(
                        (update_plan.key,) + update_plan.residual,
                        param_cols)):
                # rewriting the key column mid-scan would strand the index
                # entries the later iterations probe — take the scan route
                # and rebuild once after the batch
                update_plan = update_plan.fallback
        key = (mode, "dml", xsch, is_delete, where, sets, b, eq_term,
               update_plan, per_statement)

        def build():
            if eq_term is not None:
                kind, v = eq_term.value

                def base(state, param_cols, active):
                    vals = (jnp.asarray(param_cols[v], jnp.int32)
                            if kind == "param"
                            else jnp.full((b,), v, jnp.int32))
                    return eng.delete_many_eq(xsch, state, eq_term.col,
                                              vals, active,
                                              per_statement=per_statement)

                return self._jit_exec(
                    xsch, base, mode, eng,
                    _exec_name("delete", False, batch=True))

            def base(state, param_cols, active):
                if is_delete:
                    def one_mask(pr, act):
                        return eng._match_mask(xsch, state, where,
                                               pr) & act

                    # [b, *mask_shape]: mask_shape is [cap] for monolithic
                    # tables, [n_shards, shard_cap] for sharded ones — the
                    # union/claim math below is layout-generic
                    m = jax.vmap(one_mask)(param_cols, active)
                    rest = tuple(range(1, m.ndim))
                    hit = jnp.any(m, axis=0)
                    n_hit = jnp.sum(hit.astype(jnp.int32))
                    # sequential attribution: a row hit by several
                    # statements counts for the EARLIEST one (by the time
                    # the later ones run it is already gone)
                    mi = m.astype(jnp.int32)
                    claimed = (jnp.cumsum(mi, axis=0) - mi) > 0
                    ns = jnp.sum((m & ~claimed).astype(jnp.int32),
                                 axis=rest)
                    # clock advances by the REAL statement count (from the
                    # runtime active mask — the executor is cached per
                    # bucket, so n must not be baked in at trace time);
                    # padding must not age TTLs
                    nact = jnp.sum(active.astype(jnp.int32))
                    state = dict(state, valid=state["valid"] & ~hit,
                                 clock=state["clock"] + nact,
                                 ops=state["ops"] + nact)
                    return state, n_hit, ns

                def run(route):
                    def body(st, xs):
                        pr, act = xs
                        return eng.update(xsch, st, where, dict(sets), pr,
                                          extra_mask=act, plan=route,
                                          probe_mode="ref",
                                          maintain_indexes=False)

                    return jax.lax.scan(body, state, (param_cols, active))

                if isinstance(update_plan, PL.IndexProbe):
                    # freshness cond hoisted outside the scan: W indexed
                    # UPDATEs cost W bucket probes, not W full scans
                    state, ns = jax.lax.cond(
                        eng.index_fresh(state, update_plan.column),
                        lambda _: run(update_plan),
                        lambda _: run(update_plan.fallback),
                        None)
                else:
                    state, ns = run(update_plan)
                for c in idx_rebuild:  # deferred: ONE rebuild per dispatch
                    state = eng.build_index(xsch, state, c)
                # un-tick the padded scan iterations (runtime count — see
                # the delete branch note on executor caching)
                pad = b - jnp.sum(active.astype(jnp.int32))
                state = dict(state, clock=state["clock"] - pad,
                             ops=state["ops"] - pad)
                return state, jnp.sum(ns), ns

            return self._jit_exec(
                xsch, base, mode, eng,
                _exec_name("delete", False, batch=True) if is_delete
                else _exec_name("update",
                                isinstance(update_plan, PL.IndexProbe),
                                batch=True))

        fn = self._executor(t, key, build)
        kind = "delete" if is_delete else "update"
        if eq_term is not None and not per_statement:
            total, = self._run_state(t, fn, mode, sid, flag, n,
                                     (param_cols, active))
            self._note_sig(t, stmt, kind, b, mode, sid)
            self._note_route(t, sid, n, True)
            return Result(dev={"count": total})
        total, ns = self._run_state(t, fn, mode, sid, flag, n,
                                    (param_cols, active))
        self._note_sig(t, stmt, kind, b, mode, sid)
        self._note_route(t, sid, n, True)
        if per_statement:
            stack = _HostStack({"count": ns})
            return [Result(ctx={"stack": stack, "index": i})
                    for i in range(n)]
        return Result(dev={"count": total})

    def _do_batch_select(self, stmt: S.Select,
                         params_list: Sequence[Sequence[Any]]
                         ) -> list[Result]:
        """Micro-batch N same-statement SELECTs into ONE dispatch (the
        pipelined read path): the read is vmapped over the parameter rows,
        so W statements cost ONE [W, capacity] broadcast pass over the
        table instead of W sequential scans. Returns one lazy Result per
        statement — all index views into the stacked device outputs,
        sharing a single device→host transfer.

        Semantics vs N separate executes: reads don't interleave with
        writes inside a batch, the logical clock advances once per batch
        (by the batch size), and LRU touch covers the *returned* rows
        (up to LIMIT per statement) rather than every matching row.

        Aggregate SELECTs (COUNT/SUM/MIN/MAX/AVG ... WHERE ?) batch too:
        the aggregate is vmapped over the parameter rows and each Result
        carries its own ``value`` — the wire scheduler relies on this to
        group per-connection aggregate polls into one dispatch."""
        if stmt.agg is not None:
            return self._do_batch_agg(stmt, params_list)
        t = self._table(stmt.table)
        schema = t.schema
        n = len(params_list)
        if n == 0:
            return []
        mode, eng, xsch, sid, flag = self._exec_mode(t, stmt, params_list,
                                                     n)
        b = _bucket(n)
        where = self._intern_ast(stmt.where)
        columns = stmt.columns or schema.column_names
        limit = stmt.limit if stmt.limit is not None else schema.max_select
        n_params = P.collect_params(where)
        pm = [self._prep_params(params_list[min(i, n - 1)])
              for i in range(b)]
        param_cols = tuple(
            np.asarray([pm[i][j] for i in range(b)]) for j in range(n_params)
        )
        active = np.arange(b) < n
        plan = eng.plan_for(xsch, where, ranked=stmt.order_by is not None)
        if (isinstance(plan, PL.IndexProbe)
                and not _np_terms_int((plan.key,) + plan.residual,
                                      param_cols)):
            plan = plan.fallback
        probe = isinstance(plan, PL.IndexProbe)
        key = (mode, "select_batch", xsch, where, tuple(columns),
               stmt.payloads, stmt.order_by, stmt.descending, limit, b,
               probe)

        def build():
            def base(state, off_d, param_cols, active):
                def run(route):
                    def one(pr, act):
                        _, res = eng.select(
                            xsch, state, where, pr,
                            columns=columns, order_by=stmt.order_by,
                            descending=stmt.descending, limit=limit,
                            with_payloads=stmt.payloads, active=act,
                            touch=False, fused_mode="ref",
                            probe_mode="ref", plan=route,
                        )
                        return res

                    return jax.vmap(one)(param_cols, active)

                if probe:
                    # ONE freshness cond hoisted outside the vmap: W
                    # indexed lookups cost O(W x bucket_cap) gathers, or
                    # the whole batch falls back to the broadcast scan
                    res = jax.lax.cond(
                        eng.index_fresh(state, plan.column),
                        lambda _: run(plan),
                        lambda _: run(plan.fallback),
                        None)
                else:
                    res = run(plan)
                # one fused epilogue for the whole batch: touch the
                # returned rows and advance the clock by the REAL
                # statement count (padding must not age TTLs)
                state = eng.batch_touch(xsch, state, res, active)
                if mode == "lane":
                    res = dict(res, row_ids=jnp.where(
                        res["present"], res["row_ids"] + off_d, 0))
                return state, res

            return self._jit_exec(xsch, base, mode, eng,
                                  _exec_name("select", probe, batch=True))

        fn = self._executor(t, key, build)
        off = sid * SH.shard_capacity(schema) if mode == "lane" else 0
        res, = self._run_state(t, fn, mode, sid, flag, n,
                               (jnp.int32(off), param_cols, active))
        self._note_sig(t, stmt, "select", b, mode, sid)
        self._note_route(t, sid, n, False)
        stack = _HostStack({"count": res["count"], "rows": res["rows"],
                            "present": res["present"],
                            "row_ids": res["row_ids"]})
        ctx = {"columns": tuple(columns), "limit": limit,
               "text_cols": set(schema.text_columns()),
               "interner": self.interner, "stack": stack}
        if stmt.payloads:
            ctx["payload_stack"] = dict(res["payloads"])
        return [Result(ctx=dict(ctx, index=i)) for i in range(n)]

    def _do_batch_agg(self, stmt: S.Select,
                      params_list: Sequence[Sequence[Any]]) -> list[Result]:
        """Micro-batch N same-shape aggregate SELECTs into ONE dispatch:
        the aggregate is vmapped over the parameter rows; the logical
        clock advances by the number of ACTIVE statements (padded rows
        are free). Returns one lazy Result per statement (``value``
        views into one stacked transfer)."""
        t = self._table(stmt.table)
        n = len(params_list)
        if n == 0:
            return []
        mode, eng, xsch, sid, flag = self._exec_mode(t, stmt, params_list,
                                                     n)
        b = _bucket(n)
        agg, col = stmt.agg
        where = self._intern_ast(stmt.where)
        n_params = P.collect_params(where)
        pm = [self._prep_params(params_list[min(i, n - 1)])
              for i in range(b)]
        param_cols = tuple(
            np.asarray([pm[i][j] for i in range(b)]) for j in range(n_params)
        )
        active = np.arange(b) < n
        plan = eng.plan_for(xsch, where)
        if (isinstance(plan, PL.IndexProbe)
                and not _np_terms_int((plan.key,) + plan.residual,
                                      param_cols)):
            plan = plan.fallback
        probe = isinstance(plan, PL.IndexProbe)
        key = (mode, "agg_batch", xsch, agg, col, where, b, probe)

        def build():
            def base(state, param_cols, active):
                def run(route):
                    def one(pr, act):
                        # `act` only carries the batch axis for
                        # parameterless aggregates (vmap needs >=1 mapped
                        # argument); padded rows are never exposed, so
                        # their values don't matter
                        _, v = eng.aggregate(xsch, state, agg, col, where,
                                             pr, plan=route,
                                             fused_mode="ref",
                                             probe_mode="ref")
                        return v

                    return jax.vmap(one)(param_cols, jnp.asarray(active))

                if probe:
                    vals = jax.lax.cond(
                        eng.index_fresh(state, plan.column),
                        lambda _: run(plan),
                        lambda _: run(plan.fallback),
                        None)
                else:
                    vals = run(plan)
                nact = jnp.sum(active.astype(jnp.int32))
                state = dict(state, clock=state["clock"] + nact,
                             ops=state["ops"] + nact)
                return state, vals

            return self._jit_exec(xsch, base, mode, eng,
                                  _exec_name(agg, probe, batch=True))

        fn = self._executor(t, key, build)
        vals, = self._run_state(t, fn, mode, sid, flag, n,
                                (param_cols, active))
        self._note_sig(t, stmt, "select", b, mode, sid)
        self._note_route(t, sid, n, False)
        stack = _HostStack({"value": vals})
        return [Result(ctx={"stack": stack, "index": i}) for i in range(n)]

    def _do_select(self, stmt: S.Select, params: tuple,
                   _warm=None) -> "Result | int":
        t = self._table(stmt.table)
        schema = t.schema
        where = self._intern_ast(stmt.where)
        if _warm is None:
            mode, eng, xsch, sid, flag = self._exec_mode(t, stmt,
                                                         [params], 1)
        else:
            # pre-plan for a forced dispatch shape: placeholder params
            # (one int 0 per `?` — the executor is shape-, not value-
            # keyed), no expiry flag consumed, no clock ticks
            mode, sid = _warm
            eng, xsch = self._warm_env(t, mode)
            params = (0,) * P.collect_params(where)
        if stmt.agg is not None:
            agg, col = stmt.agg
            key = (mode, "agg", xsch, agg, col, where)
            fn = self._executor(
                t, key,
                lambda: self._jit_exec(
                    xsch,
                    lambda st, pr: eng.aggregate(xsch, st, agg, col,
                                                 where, pr),
                    mode, eng,
                    _exec_name(agg, isinstance(eng.plan_for(xsch, where),
                                               PL.IndexProbe)),
                ),
            )
            if _warm is not None:
                return self._finish_warm(t, fn, stmt, "select", None,
                                         mode, sid, (params,))
            val, = self._run_state(t, fn, mode, sid, flag, 1, (params,))
            self._note_sig(t, stmt, "select", None, mode, sid)
            self._note_route(t, sid, 1, False)
            return Result(dev={"value": val})
        columns = stmt.columns or schema.column_names
        limit = stmt.limit if stmt.limit is not None else schema.max_select
        key = (mode, "select", xsch, where, tuple(columns), stmt.payloads,
               stmt.order_by, stmt.descending, limit)

        def build():
            def base(st, off_d, pr):
                st, res = eng.select(
                    xsch, st, where, pr,
                    columns=columns, order_by=stmt.order_by,
                    descending=stmt.descending, limit=limit,
                    with_payloads=stmt.payloads,
                )
                if mode == "lane":
                    res = dict(res, row_ids=jnp.where(
                        res["present"], res["row_ids"] + off_d, 0))
                return st, res
            plan = eng.plan_for(xsch, where, ranked=stmt.order_by is not None)
            return self._jit_exec(
                xsch, base, mode, eng,
                _exec_name("select", isinstance(plan, PL.IndexProbe)))

        fn = self._executor(t, key, build)
        if _warm is not None:
            return self._finish_warm(t, fn, stmt, "select", None, mode,
                                     sid, (jnp.int32(0), params))
        off = sid * SH.shard_capacity(schema) if mode == "lane" else 0
        res, = self._run_state(t, fn, mode, sid, flag, 1,
                               (jnp.int32(off), params))
        self._note_sig(t, stmt, "select", None, mode, sid)
        self._note_route(t, sid, 1, False)
        return Result(
            payloads=dict(res["payloads"]),
            dev={"count": res["count"], "rows": res["rows"],
                 "present": res["present"], "row_ids": res["row_ids"]},
            ctx={"columns": tuple(columns), "limit": limit,
                 "text_cols": set(schema.text_columns()),
                 "interner": self.interner},
        )

    def _do_update(self, stmt: S.Update, params: tuple,
                   _warm=None) -> "Result | int":
        t = self._table(stmt.table)
        where = self._intern_ast(stmt.where)
        sets = tuple((c, self._intern_ast(e)) for c, e in stmt.sets)
        self._check_partition_update(t, (c for c, _ in sets))
        if _warm is None:
            mode, eng, xsch, sid, flag = self._exec_mode(t, stmt,
                                                         [params], 1)
        else:
            mode, sid = _warm
            eng, xsch = self._warm_env(t, mode)
            n_params = P.collect_params(where)
            for _, e in sets:
                n_params = max(n_params, P.collect_params(e))
            params = (0,) * n_params
        key = (mode, "update", xsch, where, sets)

        def build():
            def base(st, pr):
                return eng.update(xsch, st, where, dict(sets), pr)
            probe = isinstance(eng.plan_for(xsch, where), PL.IndexProbe)
            return self._jit_exec(xsch, base, mode, eng,
                                  _exec_name("update", probe))

        fn = self._executor(t, key, build)
        if _warm is not None:
            return self._finish_warm(t, fn, stmt, "update", None, mode,
                                     sid, (params,))
        n, = self._run_state(t, fn, mode, sid, flag, 1, (params,))
        self._note_sig(t, stmt, "update", None, mode, sid)
        self._note_route(t, sid, 1, True)
        return Result(dev={"count": n})

    def _do_delete(self, stmt: S.Delete, params: tuple,
                   _warm=None) -> "Result | int":
        t = self._table(stmt.table)
        schema = t.schema
        where = self._intern_ast(stmt.where)
        if _warm is None:
            mode, eng, xsch, sid, flag = self._exec_mode(t, stmt,
                                                         [params], 1)
        else:
            mode, sid = _warm
            eng, xsch = self._warm_env(t, mode)
            params = (0,) * P.collect_params(where)
        # fusable deletes on payload-bearing tables also report WHICH rows
        # went (row_ids feeds incremental index maintenance, e.g. the
        # serving page table); scalar tables keep the mask-only path —
        # nothing indexes their rows, so the compaction would be pure
        # cost. Sharded tables route through the same returning epilogue
        # with GLOBAL row ids: pruned deletes report one lane's rows,
        # fan-out concat-merges the per-shard reclaimed rows
        # (shards.delete_returning).
        fused_sch = SH.shard_schema(schema) if t.lanes is not None \
            else schema
        returning = (T._fused_plan(fused_sch, where) is not None
                     and bool(schema.payloads))
        key = (mode, "delete", xsch, where, returning)

        def build():
            def base(st, off_d, pr):
                if returning:
                    st, n, ids, present = eng.delete_returning(
                        xsch, st, where, pr)
                    if mode == "lane":
                        ids = jnp.where(present, ids + off_d, 0)
                    return st, n, ids, present
                st, n = eng.delete(xsch, st, where, pr)
                return st, n
            probe = isinstance(eng.plan_for(xsch, where), PL.IndexProbe)
            return self._jit_exec(xsch, base, mode, eng,
                                  _exec_name("delete", probe))

        fn = self._executor(t, key, build)
        if _warm is not None:
            return self._finish_warm(t, fn, stmt, "delete", None, mode,
                                     sid, (jnp.int32(0), params))
        off = sid * SH.shard_capacity(schema) if mode == "lane" else 0
        outs = self._run_state(t, fn, mode, sid, flag, 1,
                               (jnp.int32(off), params))
        self._note_sig(t, stmt, "delete", None, mode, sid)
        self._note_route(t, sid, 1, True)
        if returning:
            n, ids, present = outs
            return Result(dev={"count": n, "row_ids": ids,
                               "present": present},
                          ctx={"limit": schema.max_select})
        return Result(dev={"count": outs[0]})

    def _do_expire(self, name: str) -> Result:
        t = self._table(name)
        if t.lanes is None:
            key = ("expire", t.schema)
            fn = self._executor(
                t, key, lambda: jax.jit(lambda st: T.expire(t.schema, st),
                                        donate_argnums=0)
            )
            t.state, n = fn(t.state,
                            placement=self._placement(t, "mono", None))
            return Result(dev={"count": n})
        mode = "mesh" if t.mesh is not None else "stacked"
        key = (mode, "expire", t.schema)
        fn = self._executor(
            t, key, lambda: self._jit_exec(
                t.schema, lambda st: SH.expire(t.schema, st), mode, SH,
                _exec_name("expire")))
        # (_run_state's stacked booking consumed every lane deferral and
        # the dispatch replayed them — nothing left to clear here)
        n, = self._run_state(t, fn, mode, None, False, 1, ())
        return Result(dev={"count": n})

    # ----------------------------------------------------- serving-plane API
    def table_state(self, name: str) -> dict:
        """Zero-copy handle to the device-resident table state (for jitted
        serving steps that read the pool directly). Sharded tables return
        the STACKED view of their lanes (clocks caught up first) — a
        snapshot; use :meth:`swap_table_state` to install changes."""
        t = self._table(name)
        if t.lanes is None:
            return t.state
        return SH.stack_lanes(
            self._colocate(self._caught_up_lanes(t), t.mesh))

    def swap_table_state(self, name: str, state: dict) -> None:
        """Install a state produced by an external jitted step (sharded
        tables accept the stacked layout, split it back into lanes, and
        re-place them on the table's mesh)."""
        t = self._table(name)
        if t.lanes is None:
            t.state = state
            return
        lanes = SH.place_lanes(t.mesh, SH.split_lanes(t.schema, state))
        with t.lock:
            t.lane_ticks = [t.ticks_total] * t.schema.shards
            for i, lane in enumerate(lanes):
                t.lanes[i] = lane

    def schema(self, name: str) -> TableSchema:
        return self._table(name).schema

    def live_rows(self, name: str) -> int:
        t = self._table(name)
        if t.lanes is None:
            return int(T.live_count(t.state))
        # count through the caught-up snapshot: a lane with a deferred
        # expiry replay pending must not report rows the lockstep engine
        # already dropped (no-op when nothing is deferred)
        return sum(int(T.live_count(lane))
                   for lane in self._caught_up_lanes(t))

    def advance_clock(self, ticks: int, table: str | None = None) -> None:
        """Advance the logical clock (tests / wall-time sync)."""
        names = [table] if table else list(self.tables)
        for nm in names:
            t = self._table(nm)
            if t.lanes is None:
                st = dict(t.state)
                st["clock"] = st["clock"] + jnp.asarray(
                    ticks, dtype=st["clock"].dtype)
                t.state = st
                continue
            # ticks commute with the lazy catch-up: advance every lane's
            # device clock AND both sides of the bookkeeping, atomically
            # vs lane-dispatch commits (which also hold t.lock). Like any
            # external clock mutation this assumes no dispatch is
            # IN FLIGHT on the table — tests/wall-time sync call it
            # quiescent.
            with t.lock:
                t.ticks_total += ticks
                t.lane_ticks = [lt + ticks for lt in t.lane_ticks]
                for i, lane in enumerate(t.lanes):
                    t.lanes[i] = dict(
                        lane, clock=lane["clock"] + jnp.asarray(
                            ticks, dtype=lane["clock"].dtype))
