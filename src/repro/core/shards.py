"""ShardedTable: hash-partitioned storage over N independent shard tables.

The horizontal-scaling leg of the roadmap (the mdbcached companion paper
frames sharding as the path past single-instance limits): a table created
with ``SHARDS n [PARTITION BY col]`` splits its rows across ``n``
shard-local :mod:`repro.core.table` states — each shard has its own
validity mask, relscan tiles and hash indexes — and this module exposes
the SAME executor surface as ``table.py`` (``insert/select/update/
delete/aggregate/expire/flush/...``), so the daemon stays shape-agnostic:
it binds ``t.eng`` to either module and never looks inside.

Storage is the shard states STACKED along a leading axis (every leaf of
the state pytree is ``[n_shards, ...]``), which makes the two execution
shapes cheap:

*   **pruned** — an equality conjunct on the partition column
    (``planner.plan_shards``) anchors the statement to exactly ONE shard:
    the executor computes ``shard_of(value)`` on device, dynamic-slices
    that shard's leaves out of the stack, runs the ordinary within-shard
    plan (index probe / fused scan / generic scan) and writes back only
    what changed. Lookup latency is that of a single shard — flat as the
    total capacity grows by adding shards — and under the daemon's
    vmapped micro-batch executor each statement routes to its own shard
    inside one dispatch (independent-shard traffic overlaps
    data-parallel).
*   **fan-out** — everything else runs on every shard via ``vmap`` over
    the stacked state (one dispatch, no per-shard Python loop) and merges
    the partials: SELECT concatenates per-shard candidate rows and takes
    the first ``limit`` through one compaction (ORDER BY re-ranks the
    per-shard top-k globally), COUNT/SUM add, MIN/MAX fold, AVG merges
    as (Σ sum)/(Σ count), DML counts sum.

INSERT always *routes*: ``kernels/ops.shard_split`` (the hashidx
sort+searchsorted machinery at shard granularity) splits the batch by
``shard_of(partition value)`` on device and one vmapped ``table.insert``
feeds every shard — one dispatch regardless of ``n``.

**Mesh placement (PR 7).** With more than one jax device the daemon
keeps each lane's state committed to its OWN device
(``launch/mesh.lane_mesh_for`` picks the largest divisor of
``n_shards`` that fits the host; lane ``i`` lives on device
``i // (n_shards // n_devices)``) and the helpers at the bottom of
this module make the two execution shapes physical:

*   pruned statements run the lane executor against the lane's
    committed device — jit specializes per device, so a partition-eq
    lookup touches exactly one device with zero cross-device traffic;
*   fan-out assembles the lane handles zero-copy into ONE global
    array per leaf (``assemble_lanes`` →
    ``jax.make_array_from_single_device_arrays`` over
    ``lane_mesh_for``'s ``NamedSharding``), runs the ordinary stacked
    executor inside ``fanout_mesh`` — ``_fanout`` then lowers the
    per-shard map through ``parallel/sharding.shard_map`` instead of
    ``vmap``, so the per-shard body becomes the per-device program and
    the id-only merge concatenation becomes the cross-device gather —
    pins the result layout with ``constrain_lanes``, and splits it
    back into per-device lane handles (``disassemble_lanes``, again
    zero-copy via ``addressable_shards``).

Admin paths (RESHARD, CHECKPOINT/RESTORE, ``table_state``) first
*colocate* every lane onto one device (mixed-device stacks are
illegal), re-split through :func:`reshard`, then re-place on the new
mesh via ``place_lanes`` — which is what makes snapshots elastic
across BOTH shard counts and mesh sizes. ``lane_devices`` answers
"which device owns lane i" without touching device data, so SHOW
STATS / EXPLAIN report placement sync-free.

Semantics vs an unsharded table (the parity contract, exercised by
``tests/test_shard_parity.py``): every statement advances EVERY shard's
logical clock by exactly what the unsharded table would add, so TTL
ageing and expiry behave identically; counts, row sets and aggregates
match bit-for-bit while row *order* inside a SELECT merge follows
(shard, slot) rather than global slot order (row ids are globalized as
``shard * shard_capacity + slot``). Deliberate divergences: LRU
capacity-pressure eviction and ``MAX_ROWS`` expiry are per shard (a hot
shard evicts before a cold one), and the partition column cannot be
UPDATEd in place — rows would land in the wrong shard (delete+reinsert
instead).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PSpec

from repro.core import planner as PL
from repro.core import predicate as P
from repro.core import table as T
from repro.core.schema import TableSchema
from repro.kernels import hashidx as HX
from repro.kernels import ops as OPS
from repro.launch.mesh import LANE_AXIS
from repro.parallel.sharding import shard_map as _shard_map

_PRIME = 2654435761  # 2^32 / phi — same multiplier as kernels/hashidx
_SHIFT = 17          # use well-mixed upper bits before the modulo


def shard_of(keys: jax.Array, n_shards: int) -> jax.Array:
    """Partition hash: int32 keys -> shard ids in [0, n_shards)."""
    ku = keys.astype(jnp.uint32) * jnp.uint32(_PRIME)
    return ((ku >> jnp.uint32(_SHIFT)) % jnp.uint32(n_shards)).astype(
        jnp.int32)


def shard_of_host(key: int, n_shards: int) -> int:
    """Host-side twin of :func:`shard_of` (same bits for any int32 value)
    — the scheduler and EXPLAIN route statements without a device trip."""
    ku = (int(key) * _PRIME) & 0xFFFFFFFF
    return (ku >> _SHIFT) % n_shards


def is_sharded(schema: TableSchema) -> bool:
    return schema.shards > 1


@functools.lru_cache(maxsize=1024)
def shard_schema(schema: TableSchema) -> TableSchema:
    """The per-shard schema: capacity split ceil-wise, ``MAX_ROWS`` split
    likewise (per-shard expiry — see module docstring), shards=1 so the
    within-shard planner/executors see an ordinary table."""
    cap = -(-schema.capacity // schema.shards)
    exp = schema.expiry
    if exp.max_rows > 0:
        exp = dataclasses.replace(
            exp, max_rows=max(1, -(-exp.max_rows // schema.shards)))
    return dataclasses.replace(
        schema, capacity=cap, max_select=min(schema.max_select, cap),
        expiry=exp, shards=1, partition_by=None)


def shard_capacity(schema: TableSchema) -> int:
    return shard_schema(schema).capacity


def init_state(schema: TableSchema) -> dict:
    one = T.init_state(shard_schema(schema))
    return jax.tree.map(
        lambda x: jnp.repeat(x[None], schema.shards, axis=0), one)


# ------------------------------------------------------------ lane boundary
#
# The daemon's per-shard EXECUTION LANES (PR 5) hold one independent state
# handle per shard — the per-shard layout of core/table.py, i.e. exactly
# one slice of the stacked pytree. These two functions are the split/merge
# boundary: the daemon stores lanes, a lane-confined dispatch runs the
# ordinary table executors on ONE lane (its own buffers, its own donation),
# and whole-table dispatches stack the lanes inside the jitted executor
# (XLA's slice-of-concat simplification keeps pass-through leaves free).

def init_lanes(schema: TableSchema) -> list:
    """Fresh per-shard lane states (shards independent handles)."""
    return [T.init_state(shard_schema(schema)) for _ in range(schema.shards)]


def stack_lanes(lanes) -> dict:
    """Per-lane states -> the stacked state every fan-out executor eats."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)


def split_lanes(schema: TableSchema, state: dict) -> list:
    """Stacked state -> per-lane states (inverse of :func:`stack_lanes`)."""
    return [jax.tree.map(lambda x: x[i], state)
            for i in range(schema.shards)]


# ----------------------------------------------------------- mesh placement
#
# Multi-device execution (PR 7): a table whose shard count admits it gets a
# 1-D ``"lane"`` mesh (``launch/mesh.lane_mesh_for``) and each lane's
# buffers are COMMITTED to its device. Three consequences:
#
# *   lane-confined dispatches (pruned routes, singleton scheduler groups)
#     jit against a single lane's committed buffers, so jax places the
#     whole computation on that lane's device — single-device dispatch,
#     zero cross-chip traffic, and disjoint-device groups overlap for
#     real.
# *   whole-table fan-out runs under the daemon's "mesh" executor: lanes
#     are ASSEMBLED (:func:`assemble_lanes`, zero-copy) into one global
#     array per leaf sharded ``P("lane")``, the executor traces with
#     :func:`fanout_mesh` installed so every :func:`_fanout` below lowers
#     to ``shard_map`` over the lane axis, merges (sum/top-k/compaction
#     over the per-shard partials) lower under GSPMD as cross-device
#     gather + tree-reduce of the same O(n·limit) id-only wire shape the
#     vmap path uses, and the output state is DISASSEMBLED back to
#     per-device lane handles (:func:`disassemble_lanes`, zero-copy).
# *   everything stays semantics-free: with no mesh installed ``_fanout``
#     IS ``jax.vmap``, so single-device behavior and jit caches are
#     untouched (the parity contract extends across device counts —
#     tests/test_mesh_parity.py).

_MESH_TL = threading.local()


@contextlib.contextmanager
def fanout_mesh(mesh):
    """Install ``mesh`` for the duration of an executor TRACE: every
    :func:`_fanout` in scope lowers to ``shard_map`` over its ``"lane"``
    axis instead of ``vmap``. Trace-time only — nothing escapes into the
    compiled executable except the sharded lowering."""
    prev = getattr(_MESH_TL, "mesh", None)
    _MESH_TL.mesh = mesh
    try:
        yield
    finally:
        _MESH_TL.mesh = prev


def current_fanout_mesh():
    return getattr(_MESH_TL, "mesh", None)


def _fanout(one, state, *extra):
    """Map ``one`` over the leading shard axis of ``state`` (and of any
    ``extra`` trees sharing it). Unplaced: plain ``vmap``. Under a
    :func:`fanout_mesh` scope: ``shard_map`` over the 1-D lane mesh with
    an inner ``vmap`` over each device's contiguous lane block (supports
    ``n_shards`` a multiple of the device count). Values ``one`` closes
    over (params, predicate masks) are replicated to every device."""
    mesh = current_fanout_mesh()
    if mesh is None:
        return jax.vmap(one)(state, *extra)
    spec = PSpec(LANE_AXIS)

    def block(st, *ex):
        return jax.vmap(one)(st, *ex)

    return _shard_map(
        block, mesh=mesh, in_specs=(spec,) * (1 + len(extra)),
        out_specs=spec, check_vma=False)(state, *extra)


def lane_devices(mesh, n_shards: int):
    """Device of each lane under ``mesh`` placement (contiguous blocks of
    ``n_shards // n_devices`` lanes per device), or None when unplaced."""
    if mesh is None:
        return None
    devs = list(mesh.devices.reshape(-1))
    per = n_shards // len(devs)
    return [devs[i // per] for i in range(n_shards)]


def place_lanes(mesh, lanes):
    """Commit each lane's buffers to its mesh device. No-op placement
    (mesh None) and already-resident lanes are free (device_put to the
    owning device does not copy)."""
    if mesh is None:
        return list(lanes)
    devs = lane_devices(mesh, len(lanes))
    return [jax.device_put(l, d) for l, d in zip(lanes, devs)]


def assemble_lanes(mesh, lanes) -> dict:
    """Per-lane states -> ONE global array per leaf, sharded
    ``P("lane")`` over ``mesh`` — the input shape of the daemon's "mesh"
    executor. Each device's block is built ON that device (stack of its
    resident lanes — no cross-device traffic for lanes already placed),
    then the blocks are assembled zero-copy via
    ``jax.make_array_from_single_device_arrays``."""
    n_sh = len(lanes)
    devs = list(mesh.devices.reshape(-1))
    per = n_sh // len(devs)
    sharding = NamedSharding(mesh, PSpec(LANE_AXIS))
    lane_leaves = [jax.tree.flatten(l) for l in lanes]
    treedef = lane_leaves[0][1]
    out = []
    for li in range(len(lane_leaves[0][0])):
        parts = []
        for di, dev in enumerate(devs):
            blk = [jax.device_put(lane_leaves[i][0][li], dev)
                   for i in range(di * per, (di + 1) * per)]
            parts.append(jnp.stack(blk) if per > 1 else blk[0][None])
        shape = (n_sh,) + tuple(lane_leaves[0][0][li].shape)
        out.append(jax.make_array_from_single_device_arrays(
            shape, sharding, parts))
    return jax.tree.unflatten(treedef, out)


def disassemble_lanes(mesh, n_shards: int, state: dict) -> list:
    """Global mesh-sharded state -> per-lane states, each committed to
    its device (inverse of :func:`assemble_lanes`; zero-copy up to the
    on-device slice when a device owns several lanes)."""
    del mesh  # the arrays carry their sharding; kept for call-site symmetry
    leaves, treedef = jax.tree.flatten(state)
    per_leaf = []
    for x in leaves:
        blocks = sorted(x.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        lanes_x = []
        for blk in blocks:
            data = blk.data
            lanes_x.extend(data[j] for j in range(data.shape[0]))
        per_leaf.append(lanes_x)
    return [jax.tree.unflatten(treedef, [c[i] for c in per_leaf])
            for i in range(n_shards)]


def constrain_lanes(mesh, tree):
    """Pin every leaf of ``tree`` to ``P("lane")`` sharding inside a jit
    trace — the mesh executor pins its OUTPUT state so disassembly by
    addressable shards is layout-safe regardless of what GSPMD inferred."""
    s = NamedSharding(mesh, PSpec(LANE_AXIS))
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, s), tree)


def flat_schema(schema: TableSchema):
    """Monolithic-layout schema whose capacity covers the flattened shard
    stack (``shards * shard_capacity`` — global row ids index it
    directly). For kvpool-style readers of :func:`flat_state`."""
    cap = shard_capacity(schema) * schema.shards
    return dataclasses.replace(schema, capacity=cap, shards=1,
                               partition_by=None)


def flat_state(state: dict) -> dict:
    """Monolithic-layout view of a stacked sharded state: cols, validity
    and payload pools flattened along (shard, slot) so GLOBAL row ids
    (``shard * shard_cap + slot``) index them like an unsharded table —
    the bridge that lets row-id consumers (e.g. the serving page table,
    core/kvpool.py) run against a sharded metadata table."""
    return dict(
        state,
        cols={c: v.reshape((-1,) + v.shape[2:])
              for c, v in state["cols"].items()},
        payloads={p: v.reshape((-1,) + v.shape[2:])
                  for p, v in state["payloads"].items()},
        valid=state["valid"].reshape(-1),
        clock=state["clock"][0],
        ops=state["ops"][0],
    )


# ------------------------------------------------------------- state pieces

def _slice_shard(state: dict, sid: jax.Array) -> dict:
    """One shard's view of the stacked state (``sid`` may be traced —
    XLA DCEs the slices of leaves the executor never reads)."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, sid, 0, keepdims=False),
        state)


def _writeback(state: dict, sub: dict, sid: jax.Array, keys) -> dict:
    """Scatter the changed top-level entries of one shard's state back
    into the stack (only ``keys`` — untouched leaves never round-trip)."""
    out = dict(state)
    for k in keys:
        out[k] = jax.tree.map(
            lambda full, part: jax.lax.dynamic_update_index_in_dim(
                full, part, sid, 0),
            state[k], sub[k])
    return out


def _tick_all(state: dict, n: jax.Array | int = 1) -> dict:
    """Advance every shard's clock in lockstep (the all-equal invariant
    that keeps TTL semantics identical to the unsharded table)."""
    return dict(state, clock=state["clock"] + n, ops=state["ops"] + n)


def _route_key(schema: TableSchema, where, params):
    """The pruning key term when this statement prunes AND its runtime
    value has an integer dtype (floats demote to fan-out for exact-compare
    semantics, mirroring table's probe demotion). Trace-time decision."""
    route = PL.plan_shards(schema, where)
    if route.key is None:
        return None
    if not jnp.issubdtype(jnp.result_type(route.key.resolve(params)),
                          jnp.integer):
        return None
    return route.key


def index_fresh(state: dict, column: str) -> jax.Array:
    """Scalar bool: NO shard's index on ``column`` has overflowed (the
    hoisted freshness cond for batched executors — conservative: one
    stale shard sends the whole fan-out to the scan fallback)."""
    return jnp.all(state["indexes"][column]["stale"] == 0)


def _run_fanout(schema, state, where, params, plan, run, *,
                ranked: bool = False):
    """Shared fan-out routing for every executor below: a caller-forced
    within-shard ``plan`` wins verbatim; otherwise take the planner's
    choice, demoted to its scan fallback when a probe term binds a
    non-integer runtime value (trace time). Un-forced probes run under
    ONE index-freshness ``lax.cond`` hoisted OUTSIDE the vmapped
    ``run`` (inside it, the cond would lower to a select and every
    shard would pay for both branches)."""
    forced = plan is not None
    inner = plan
    if not forced:
        inner = PL.plan_where(shard_schema(schema), where, ranked)
        if isinstance(inner, PL.IndexProbe) and not T._int_values(
                (inner.key,) + inner.residual, params):
            inner = inner.fallback
    if isinstance(inner, PL.IndexProbe) and not forced:
        return jax.lax.cond(
            index_fresh(state, inner.column),
            lambda _: run(inner),
            lambda _: run(inner.fallback),
            None)
    return run(inner)


def plan_for(schema: TableSchema, where, ranked: bool = False) -> PL.Plan:
    """The WITHIN-SHARD plan (the daemon's batched routing reads this —
    shard routing itself is value-directed and lives in the executors)."""
    return PL.plan_where(shard_schema(schema), where, ranked)


def _fused_plan(schema: TableSchema, where) -> P.FusedScan | None:
    return PL.as_fused(plan_for(schema, where))


def _match_mask(schema: TableSchema, state: dict, where, params):
    """[n_shards, shard_cap] fan-out match mask (shape of ``valid``) —
    the daemon's batched-DELETE union path is layout-generic over it."""
    s_sch = shard_schema(schema)
    return _fanout(lambda st: T._match_mask(s_sch, st, where, params),
                   state)


def live_count(state: dict) -> jax.Array:
    return jnp.sum(state["valid"].astype(jnp.int32))


# ------------------------------------------------------------------- insert

def insert(
    schema: TableSchema,
    state: dict,
    values: Mapping[str, jax.Array],
    payloads: Mapping[str, jax.Array] | None = None,
    row_mask: jax.Array | None = None,
    ttl: jax.Array | int = 0,
):
    """Hash-routed batch insert: ONE device-side split + ONE vmapped
    per-shard insert. Returns (state, slots[n], evicted) — slots are
    GLOBAL row ids (``shard * shard_cap + slot``). Rows that omit the
    partition column hash its default (0), like any other column."""
    s_sch = shard_schema(schema)
    n_sh, cap_s = schema.shards, s_sch.capacity
    payloads = payloads or {}
    b = None
    for v in list(values.values()) + list(payloads.values()):
        b = np.shape(v)[0]
        break
    if b is None:
        raise ValueError("insert needs at least one column or payload")
    if row_mask is None:
        row_mask = jnp.ones((b,), dtype=bool)
    row_mask = jnp.asarray(row_mask, dtype=bool)
    pcol = schema.partition_by
    pkeys = values.get(pcol)
    pkeys = (jnp.zeros((b,), jnp.int32) if pkeys is None
             else jnp.broadcast_to(jnp.asarray(pkeys), (b,)).astype(
                 jnp.int32))
    sid = shard_of(pkeys, n_sh)
    rows, mask = OPS.shard_split(sid, n_sh, row_mask)   # [n_sh, b] each
    vals_b = {c: jnp.broadcast_to(jnp.asarray(v), (b,))
              for c, v in values.items()}
    pls_b = {k: jnp.asarray(v) for k, v in payloads.items()}
    ttl_b = jnp.broadcast_to(jnp.asarray(ttl, jnp.int32), (b,))
    offs = (jnp.arange(n_sh, dtype=jnp.int32) * cap_s)[:, None]

    def one(alloc):
        def fn(st, r_l, m_l):
            # device-local fan-out split: each lane gathers its OWN rows
            # from the (replicated) batch INSIDE the mapped executor.
            # Under a fanout mesh the only cross-device movement is the
            # [b]-row batch broadcast — the old outer gather materialized
            # a padded [n_sh, w] per-shard assembly first and moved THAT
            # through the mesh (up to n_sh x the batch on a skewed
            # split).
            vals = {c: v[r_l] for c, v in vals_b.items()}
            pls = {k: v[r_l] for k, v in pls_b.items()}
            return T.insert(s_sch, st, vals, pls, m_l, ttl_b[r_l],
                            alloc=alloc)

        return fn

    # A shard's slot allocator (one top_k over its rows) serves at most
    # cap_s rows per call, but a skewed batch can route up to b rows to
    # one shard — chunk the split batch to the shard width. The common
    # case (b <= shard capacity) is exactly one vmapped dispatch; later
    # chunks overwrite LRU rows like sequential inserts would.
    w = min(b, cap_s)
    slots = jnp.zeros((b,), jnp.int32)
    evicted = jnp.zeros((), jnp.int32)
    n_chunks = -(-b // w)
    for ci in range(n_chunks):
        r = rows[:, ci * w:(ci + 1) * w]
        m = mask[:, ci * w:(ci + 1) * w]
        args = (state, r, m)
        # allocator cond hoisted OUTSIDE the vmap (inside, it would lower
        # to a select and pay for both paths on every shard): the cheap
        # free-list path needs every shard to hold the chunk comfortably
        free_ok = jnp.min(
            jnp.sum((~state["valid"]).astype(jnp.int32), axis=1)) >= w
        state, slots_sh, ev = jax.lax.cond(
            free_ok,
            lambda a: _fanout(one("free"), *a),
            lambda a: _fanout(one("lru"), *a),
            args)
        # map per-shard slots back to original batch positions, globalized
        tgt = jnp.where(m, r, b)  # b = out of range -> dropped
        slots = slots.at[tgt].set(slots_sh + offs, mode="drop")
        evicted = evicted + jnp.sum(ev)
    if n_chunks > 1:
        # the whole batch is ONE logical statement dispatch: undo the
        # extra per-chunk ticks so clocks stay in lockstep with the
        # unsharded table's +1-per-dispatch
        state = _tick_all(state, 1 - n_chunks)
    return state, slots, evicted


# ------------------------------------------------------------------- select

def _merge_select(schema, state, res, limit, order_by, descending,
                  columns, with_payloads):
    """Fan-out merge: per-shard fixed-width CANDIDATES (row ids + the
    ORDER BY key only — see :func:`select`) -> one result of ``limit``
    rows. Unranked: first ``limit`` present candidates in (shard, slot)
    order via one compaction. Ranked: global top-k over the per-shard
    top-k candidates (each shard returned up to ``limit`` rows, so the
    union covers the global top ``limit``). Only the ``limit`` WINNING
    rows gather their columns/payloads — from the stacked ``state``, by
    (shard, slot) — so the merge buffer is O(n_shards x limit) ids plus
    O(limit) rows, never n x limit materialized row sets."""
    n_sh = res["count"].shape[0]
    s_limit = res["present"].shape[1]
    cap_s = shard_capacity(schema)
    m = n_sh * s_limit
    count = jnp.sum(res["count"])
    present = res["present"].reshape(m)
    slots = res["row_ids"].reshape(m)
    sids = jnp.repeat(jnp.arange(n_sh, dtype=jnp.int32), s_limit)
    if order_by is None:
        idx, pres = T._compact(present, limit, m)
    else:
        key = res["rows"][order_by].reshape(m)
        if jnp.issubdtype(key.dtype, jnp.integer):
            key = key if descending else ~key
            key = jnp.where(present, key, jnp.iinfo(key.dtype).min)
        else:
            key = key if descending else -key
            key = jnp.where(present, key, -jnp.inf)
        _, idx = jax.lax.top_k(key, limit)
        pres = present[idx]
        pres = pres & (jnp.arange(idx.shape[0], dtype=jnp.int32) < count)
    sel_s, sel_r = sids[idx], slots[idx]
    rows = {c: state["cols"][c][sel_s, sel_r] for c in columns}
    pls = {p: state["payloads"][p][sel_s, sel_r] for p in with_payloads}
    return {
        "count": count,
        "rows": rows,
        "present": pres,
        "row_ids": jnp.where(pres, sel_s * cap_s + sel_r, 0).astype(
            jnp.int32),
        "payloads": pls,
    }


def _pad_result(res, limit):
    """Pad a single-shard result's row axis from its shard limit up to the
    logical ``limit`` (absent rows)."""
    s_limit = res["present"].shape[0]
    if s_limit >= limit:
        return res
    pad = limit - s_limit

    def padv(v):
        return jnp.concatenate(
            [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)])

    return {
        "count": res["count"],
        "rows": {c: padv(v) for c, v in res["rows"].items()},
        "present": padv(res["present"]),
        "row_ids": padv(res["row_ids"]),
        "payloads": {p: padv(v) for p, v in res["payloads"].items()},
    }


def select(
    schema: TableSchema,
    state: dict,
    where: P.Node | None,
    params: Sequence[Any] = (),
    *,
    columns: Sequence[str] | None = None,
    order_by: str | None = None,
    descending: bool = False,
    limit: int | None = None,
    with_payloads: Sequence[str] = (),
    touch: bool = True,
    active: jax.Array | None = None,
    fused_mode: str | None = None,
    probe_mode: str | None = None,
    plan: PL.Plan | None = None,
):
    """SELECT with shard routing. ``plan`` forces the WITHIN-shard plan
    (the shard route itself is recomputed here — it is value-directed).
    Same result contract as ``table.select`` with global row ids."""
    s_sch = shard_schema(schema)
    n_sh, cap_s = schema.shards, s_sch.capacity
    limit = schema.max_select if limit is None else min(limit,
                                                        schema.max_select)
    s_limit = min(limit, s_sch.max_select)
    columns = tuple(columns) if columns is not None else schema.column_names
    inner_cols = columns
    if order_by is not None and order_by not in inner_cols:
        inner_cols = inner_cols + (order_by,)

    key = _route_key(schema, where, params)
    if key is not None:
        # ---- pruned: one shard, ordinary executor, writeback _accessed
        sid = shard_of(jnp.asarray(key.resolve(params), jnp.int32)[None],
                       n_sh)[0]
        sub = _slice_shard(state, sid)
        sub2, res = T.select(
            s_sch, sub, where, params, columns=inner_cols,
            order_by=order_by, descending=descending, limit=s_limit,
            with_payloads=with_payloads, touch=touch, active=active,
            fused_mode=fused_mode, probe_mode=probe_mode, plan=plan)
        res = _pad_result(res, limit)
        ids = jnp.where(res["present"],
                        res["row_ids"] + sid * cap_s, 0).astype(jnp.int32)
        res = dict(res, row_ids=ids)
        if touch:
            # the only thing SELECT writes is the touch stamps — scatter
            # just that column back instead of round-tripping the shard
            acc = jax.lax.dynamic_update_index_in_dim(
                state["cols"]["_accessed"], sub2["cols"]["_accessed"],
                sid, 0)
            state = dict(state, cols=dict(state["cols"], _accessed=acc))
        state = _tick_all(state)
    else:
        # ---- fan-out: vmap over the stacked shards, merge partials.
        # Each shard returns only row ids (+ the ORDER BY key when
        # ranked); the merge gathers columns/payloads for the WINNING
        # ``limit`` rows straight from the stacked state, so candidate
        # materialization is bounded at O(n_shards x limit) ids.
        fan_cols = (order_by,) if order_by is not None else ()

        def run(rt):
            def one(st):
                return T.select(
                    s_sch, st, where, params, columns=fan_cols,
                    order_by=order_by, descending=descending,
                    limit=s_limit, with_payloads=(),
                    touch=touch, active=active,
                    fused_mode="ref", probe_mode="ref", plan=rt)

            return _fanout(one, state)

        state, res = _run_fanout(schema, state, where, params, plan, run,
                                 ranked=order_by is not None)
        res = _merge_select(schema, state, res, limit, order_by,
                            descending, columns, with_payloads)
    res["rows"] = {c: res["rows"][c] for c in columns}
    return state, res


# ---------------------------------------------------------------------- DML

def update(
    schema: TableSchema,
    state: dict,
    where: P.Node | None,
    set_exprs: Mapping[str, P.Node],
    params: Sequence[Any] = (),
    *,
    extra_mask: jax.Array | None = None,
    plan: PL.Plan | None = None,
    probe_mode: str | None = None,
    maintain_indexes: bool = True,
):
    """UPDATE with shard routing. Rewriting the partition column is
    refused — the row would stay in a shard its new hash doesn't name
    (DELETE + INSERT moves rows across shards). Returns (state, n)."""
    set_cols = {("_ttl" if c.upper() == "TTL" else c) for c in set_exprs}
    if schema.partition_by in set_cols:
        raise ValueError(
            f"cannot UPDATE partition column {schema.partition_by!r} of "
            f"sharded table {schema.name!r} (DELETE + INSERT instead)")
    s_sch = shard_schema(schema)
    key = _route_key(schema, where, params)
    if key is not None:
        sid = shard_of(jnp.asarray(key.resolve(params), jnp.int32)[None],
                       schema.shards)[0]
        sub = _slice_shard(state, sid)
        sub2, n = T.update(
            s_sch, sub, where, set_exprs, params, extra_mask=extra_mask,
            plan=plan, probe_mode=probe_mode,
            maintain_indexes=maintain_indexes)
        # scatter back ONLY what UPDATE can change: the SET columns and
        # any index it rebuilt — untouched leaves never round-trip, so a
        # pruned update's cost stays O(shard), not O(shard x columns)
        cols = dict(state["cols"])
        for c in set_cols:
            cols[c] = jax.lax.dynamic_update_index_in_dim(
                state["cols"][c], sub2["cols"][c], sid, 0)
        state = dict(state, cols=cols)
        if maintain_indexes:
            rebuilt = tuple(c for c in schema.indexes if c in set_cols)
            if rebuilt:
                idxs = dict(state["indexes"])
                for c in rebuilt:
                    idxs[c] = jax.tree.map(
                        lambda full, part: jax.lax.
                        dynamic_update_index_in_dim(full, part, sid, 0),
                        state["indexes"][c], sub2["indexes"][c])
                state = dict(state, indexes=idxs)
        return _tick_all(state), n
    def run(rt):
        def one(st):
            return T.update(
                s_sch, st, where, set_exprs, params,
                extra_mask=extra_mask, plan=rt, probe_mode="ref",
                maintain_indexes=maintain_indexes)

        return _fanout(one, state)

    state, ns = _run_fanout(schema, state, where, params, plan, run)
    return state, jnp.sum(ns)


def delete(
    schema: TableSchema,
    state: dict,
    where: P.Node | None,
    params: Sequence[Any] = (),
    *,
    extra_mask: jax.Array | None = None,
    plan: PL.Plan | None = None,
    probe_mode: str | None = None,
):
    """DELETE with shard routing (validity flips only). Returns
    (state, n)."""
    s_sch = shard_schema(schema)
    key = _route_key(schema, where, params)
    if key is not None:
        sid = shard_of(jnp.asarray(key.resolve(params), jnp.int32)[None],
                       schema.shards)[0]
        sub = _slice_shard(state, sid)
        sub2, n = T.delete(s_sch, sub, where, params,
                           extra_mask=extra_mask, plan=plan,
                           probe_mode=probe_mode)
        state = _writeback(state, sub2, sid, ("valid",))
        return _tick_all(state), n
    def run(rt):
        def one(st):
            return T.delete(s_sch, st, where, params,
                            extra_mask=extra_mask, plan=rt,
                            probe_mode="ref")

        return _fanout(one, state)

    state, ns = _run_fanout(schema, state, where, params, plan, run)
    return state, jnp.sum(ns)


def delete_returning(
    schema: TableSchema,
    state: dict,
    where: P.Node | None,
    params: Sequence[Any] = (),
    *,
    limit: int | None = None,
    plan: PL.Plan | None = None,
    probe_mode: str | None = None,
):
    """DELETE that also reports WHICH rows went, with shard routing —
    the sharded twin of ``table.delete_returning`` (global row ids feed
    incremental index maintenance, e.g. the serving page table over a
    :func:`flat_state` view). Pruned runs one shard; fan-out concatenates
    the per-shard reclaimed rows and compacts the first ``limit`` global
    ids in (shard, slot) order. Returns (state, n, ids[limit],
    present[limit])."""
    s_sch = shard_schema(schema)
    n_sh, cap_s = schema.shards, s_sch.capacity
    limit = schema.max_select if limit is None else limit
    s_limit = min(limit, cap_s)
    key = _route_key(schema, where, params)
    if key is not None:
        sid = shard_of(jnp.asarray(key.resolve(params), jnp.int32)[None],
                       n_sh)[0]
        sub = _slice_shard(state, sid)
        sub2, n, ids, present = T.delete_returning(
            s_sch, sub, where, params, limit=s_limit, plan=plan,
            probe_mode=probe_mode)
        state = _writeback(state, sub2, sid, ("valid",))
        ids = jnp.where(present, ids + sid * cap_s, 0).astype(jnp.int32)
        if s_limit < limit:
            pad = limit - s_limit
            ids = jnp.concatenate([ids, jnp.zeros((pad,), jnp.int32)])
            present = jnp.concatenate(
                [present, jnp.zeros((pad,), dtype=bool)])
        return _tick_all(state), n, ids, present

    def run(rt):
        def one(st):
            return T.delete_returning(s_sch, st, where, params,
                                      limit=s_limit, plan=rt,
                                      probe_mode="ref")

        return _fanout(one, state)

    state, ns, ids, present = _run_fanout(schema, state, where, params,
                                          plan, run)
    m = n_sh * s_limit
    pres_f = present.reshape(m)
    ids_g = (ids + (jnp.arange(n_sh, dtype=jnp.int32) * cap_s)[:, None]
             ).reshape(m)
    idx, pres = T._compact(pres_f, limit, m)
    ids_out = jnp.where(pres, ids_g[idx], 0).astype(jnp.int32)
    return state, jnp.sum(ns), ids_out, pres


def delete_many_eq(
    schema: TableSchema,
    state: dict,
    column: str,
    vals: jax.Array,
    active: jax.Array,
    *,
    per_statement: bool = False,
):
    """Multi-value eq DELETE, one pass PER SHARD in one vmapped dispatch
    (total work O(capacity) — same as unsharded; each shard only scans
    its slice; per-statement counts sum across shards). Returns
    (state, n) or (state, n, counts[W])."""
    s_sch = shard_schema(schema)
    if per_statement:
        state, n_sh, ns_sh = _fanout(
            lambda st: T.delete_many_eq(s_sch, st, column, vals, active,
                                        per_statement=True), state)
        return state, jnp.sum(n_sh), jnp.sum(ns_sh, axis=0)
    state, ns = _fanout(
        lambda st: T.delete_many_eq(s_sch, st, column, vals, active), state)
    return state, jnp.sum(ns)


_MERGE = {
    "COUNT": jnp.sum,
    "SUM": jnp.sum,
    "MIN": jnp.min,
    "MAX": jnp.max,
}


def aggregate(
    schema: TableSchema,
    state: dict,
    agg: str,
    column: str | None,
    where: P.Node | None,
    params: Sequence[Any] = (),
    *,
    plan: PL.Plan | None = None,
    fused_mode: str | None = None,
    probe_mode: str | None = None,
):
    """Aggregates with shard routing: pruned runs one shard; fan-out
    vmaps per-shard partials and merges (COUNT/SUM add, MIN/MAX fold —
    empty shards contribute the executor's identity sentinels — and AVG
    merges as (Σ sum) / max(Σ count, 1), matching the unsharded
    definition). Returns (state, value)."""
    agg = agg.upper()
    s_sch = shard_schema(schema)
    key = _route_key(schema, where, params)
    if key is not None:
        sid = shard_of(jnp.asarray(key.resolve(params), jnp.int32)[None],
                       schema.shards)[0]
        sub = _slice_shard(state, sid)
        _, val = T.aggregate(s_sch, sub, agg, column, where, params,
                             plan=plan, fused_mode=fused_mode,
                             probe_mode=probe_mode)
        return _tick_all(state), val
    def run(rt):
        def one(st, what, col):
            # aggregates never mutate beyond the tick; drop the state to
            # keep the vmap output small and tick the stack once below
            _, v = T.aggregate(s_sch, st, what, col, where, params,
                               plan=rt, fused_mode="ref", probe_mode="ref")
            return v

        if agg == "AVG" and column is not None:
            sums = _fanout(lambda st: one(st, "SUM", column), state)
            cnts = _fanout(lambda st: one(st, "COUNT", None), state)
            return (jnp.sum(sums.astype(jnp.float32))
                    / jnp.maximum(jnp.sum(cnts), 1))
        vals = _fanout(lambda st: one(st, agg, column), state)
        if agg == "COUNT" or column is None:
            return jnp.sum(vals)
        return _MERGE[agg](vals)

    val = _run_fanout(schema, state, where, params, plan, run)
    return _tick_all(state), val


# ----------------------------------------------------------------- lifecycle

def expire(schema: TableSchema, state: dict):
    """§4.3 automatic expiry, every shard in one vmapped dispatch. The
    age condition matches the unsharded table exactly (clocks are in
    lockstep); the MAX_ROWS cap is per shard (see module docstring)."""
    s_sch = shard_schema(schema)
    state, ns = _fanout(lambda st: T.expire(s_sch, st), state)
    return state, jnp.sum(ns)


def flush(schema: TableSchema, state: dict):
    s_sch = shard_schema(schema)
    state, ns = _fanout(lambda st: T.flush(s_sch, st), state)
    return state, jnp.sum(ns)


def build_index(schema: TableSchema, state: dict,
                column: str | None = None) -> dict:
    """(Re)build hash indexes on every shard (vmapped)."""
    s_sch = shard_schema(schema)
    return _fanout(lambda st: T.build_index(s_sch, st, column), state)


def reshard(old_schema: TableSchema, new_schema: TableSchema, lanes):
    """Bulk re-split behind ``ALTER TABLE t RESHARD n``: rebuild the
    shard pytree at ``new_schema.shards`` by ONE device-side re-split of
    every live row (the ``kernels/ops.shard_split`` argsort machinery
    over the flattened old stack) plus one hash-index rebuild per new
    shard. ``lanes`` is a sequence of per-shard states in the OLD layout
    (a monolithic state is one lane); caller must have clocks in
    lockstep (caught up).

    Row metadata (``_created``/``_accessed``/``_ttl``) and the clock ride
    along verbatim, so TTL ageing is unchanged by the move — contents
    round-trip exactly. Returns (new_lanes list, counts[new_n]): counts
    are live rows per NEW shard from the FULL split, so the caller can
    detect overflow (``counts[i] > new shard capacity`` — the new layout
    cannot hold the skew) before installing. NOT donated: on overflow the
    old state stays live."""
    new_n = new_schema.shards
    s_new = shard_schema(new_schema) if new_n > 1 else new_schema
    cap_new = s_new.capacity
    pcol = new_schema.partition_by if new_n > 1 else old_schema.partition_by

    # flatten the old lanes ((shard, slot) order — stable, so repeated
    # reshards keep deterministic layouts)
    def flat(get):
        return jnp.concatenate([get(l) for l in lanes])

    valid = flat(lambda l: l["valid"])
    cols = {c: flat(lambda l, _c=c: l["cols"][_c])
            for c in lanes[0]["cols"]}
    pls = {p: flat(lambda l, _p=p: l["payloads"][_p])
           for p in lanes[0]["payloads"]}
    pkeys = (cols[pcol].astype(jnp.int32) if pcol is not None
             else jnp.zeros(valid.shape, jnp.int32))
    sid = shard_of(pkeys, new_n)
    rows, mask = OPS.shard_split(sid, new_n, valid)
    counts = jnp.sum(mask.astype(jnp.int32), axis=1)
    r, m = rows[:, :cap_new], mask[:, :cap_new]
    if r.shape[1] < cap_new:  # growing capacity: pad the gather frame
        pad = cap_new - r.shape[1]
        r = jnp.concatenate(
            [r, jnp.zeros((new_n, pad), jnp.int32)], axis=1)
        m = jnp.concatenate(
            [m, jnp.zeros((new_n, pad), dtype=bool)], axis=1)

    def gather(a):
        g = a[r]  # [new_n, cap_new, ...]
        keep = m.reshape(m.shape + (1,) * (g.ndim - 2))
        return jnp.where(keep, g, jnp.zeros((), a.dtype))

    n_cols = {c: gather(v) for c, v in cols.items()}
    n_pls = {p: gather(v) for p, v in pls.items()}
    clock = jnp.broadcast_to(lanes[0]["clock"], (new_n,))
    ops = jnp.broadcast_to(lanes[0]["ops"], (new_n,))
    indexes = {}
    for c in new_schema.indexes:
        nb = HX.n_buckets_for(cap_new)
        rid, key, ov = jax.vmap(
            lambda kc, v: OPS.hash_build(kc, v, n_buckets=nb))(
                n_cols[c], m)
        indexes[c] = {"rid": rid, "key": key, "stale": ov}
    stacked = {"cols": n_cols, "payloads": n_pls, "valid": m,
               "clock": clock, "ops": ops, "indexes": indexes}
    return split_lanes(new_schema, stacked), counts


# ------------------------------------------------------- batched epilogues

def batch_touch(schema: TableSchema, state: dict, res: dict,
                active: jax.Array) -> dict:
    """The micro-batched SELECT epilogue (daemon ``_do_batch_select``):
    touch the returned rows — global ids decompose to (shard, slot) — and
    advance every shard's clock by the ACTIVE statement count."""
    cap_s = shard_capacity(schema)
    now = state["clock"][0].astype(jnp.int32)  # clocks are in lockstep
    ids = res["row_ids"]
    sid = jnp.clip(ids // cap_s, 0, schema.shards - 1)
    loc = jnp.where(res["present"], ids % cap_s, cap_s)  # cap_s -> dropped
    acc = state["cols"]["_accessed"].at[
        sid.reshape(-1), loc.reshape(-1)].set(now, mode="drop")
    nact = jnp.sum(active.astype(jnp.int32))
    state = dict(state, cols=dict(state["cols"], _accessed=acc))
    return _tick_all(state, nact)
