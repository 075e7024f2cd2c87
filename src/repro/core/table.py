"""RelTable: a fixed-capacity, device-resident relational cache table,
executed as *plans*.

The TPU-native reimagining of SQLcached's SQLite-backed store (DESIGN.md
§2): storage is struct-of-arrays with a validity bitmap; every operation
is a *pure function* ``(state, ...) -> (state, result)`` so the daemon can
jit + donate it and thread it through pjit programs; slot allocation
unifies the free list with LRU eviction (one ``top_k``); a logical clock
stamps ``_created`` / ``_accessed`` and drives the paper's three automatic
expiry conditions (§4.3, :func:`expire`).

Query execution is a two-stage affair since the planner split:

1. ``core/planner.plan_where`` lowers the WHERE AST into a Plan —
   IndexProbe | FusedScan | GenericScan (memoized per schema × AST; the
   prepared-statement planner cache).
2. ``select`` / ``update`` / ``delete`` / ``aggregate`` here are thin
   *plan executors*: they route the plan to the matching device program —
   a hash-bucket probe (kernels/hashidx), the fused Pallas relscan
   (kernels/relscan), or the generic jnp masked scan — and share one
   epilogue (touch, compaction contract, clock tick).

Index-probe execution is O(bucket_cap), independent of table capacity.
Because a bucket can overflow (``stale``), every probing executor embeds
its fallback scan behind a device-side ``lax.cond`` on the index's stale
flag — plan revalidation costs zero host syncs. Index maintenance is
fused into the mutating executors: ``insert`` re-homes each written slot
(clearing the overwritten row's entry via its still-readable old key —
the kvpool page-table trick), ``update`` rebuilds any index whose column
it sets, and DELETE/FLUSH/EXPIRE touch nothing (dead entries are masked
by the validity gather at probe time and reclaimed on slot reuse).

Callers may pass ``plan=`` explicitly to force a route (the parity suite
and the daemon's batched executors do); a forced IndexProbe skips the
staleness cond and trusts the caller.

Row results of SELECT are fixed-size (``schema.max_select``) with an
exact ``count`` — the host slices; payload gathers stay on device for
zero-copy hand-off to compute (e.g. paged attention reading KV blocks).
"""
from __future__ import annotations

from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import planner as PL
from repro.core import predicate as P
from repro.core.schema import RESERVED_COLUMNS, TableSchema
from repro.kernels import hashidx as HX
from repro.kernels import ops as OPS

CLOCK_DTYPE = jnp.int64 if jax.config.read("jax_enable_x64") else jnp.int32
# NOTE: we keep clocks in int32 unless x64 is enabled; the daemon widens by
# running with jax_enable_x64 when available. 2^31 ops is plenty for tests.

# multi-value eq DELETE batches up to this wide use direct per-value
# compares; wider ones sort the values and binary-search each row once
_EQ_DIRECT_MAX = 16

# INSERT batches at least this wide maintain hash indexes by ONE bulk
# sort-based rebuild (kernels/hashidx.build) instead of the sequential
# per-slot re-home fori_loop — the loop's O(batch) serial chain dominates
# large bulk loads, while the rebuild is one O(cap log cap) sort whatever
# the batch width. The rebuild is complete by construction, so it also
# RESETS a stale flag whenever the live rows fit their buckets again.
BULK_INDEX_THRESHOLD = 64


def init_state(schema: TableSchema) -> dict:
    cap = schema.capacity
    cols = {c.name: jnp.zeros((cap,), dtype=c.dtype) for c in schema.columns}
    for r in RESERVED_COLUMNS:
        cols[r] = jnp.zeros((cap,), dtype=jnp.int32)
    payloads = {
        p.name: jnp.zeros((cap,) + p.shape, dtype=p.dtype) for p in schema.payloads
    }
    nb = HX.n_buckets_for(cap)
    indexes = {c: HX.empty_index(nb) for c in schema.indexes}
    return {
        "cols": cols,
        "payloads": payloads,
        "valid": jnp.zeros((cap,), dtype=bool),
        "clock": jnp.zeros((), dtype=jnp.int32),
        "ops": jnp.zeros((), dtype=jnp.int32),
        "indexes": indexes,
    }


def _tick(state: dict) -> dict:
    state = dict(state)
    state["clock"] = state["clock"] + 1
    state["ops"] = state["ops"] + 1
    return state


def _free_slots(state: dict, n: int):
    """The first ``n`` invalid row ids, via ONE cumsum + ``n`` binary
    searches (the k-th free slot is where the running free count reaches
    k). O(capacity) with a tiny constant — more than 10x cheaper than the
    top_k it replaces on large tables. Only exact when the table has at
    least ``n`` free slots (the caller conds on that)."""
    cum = jnp.cumsum((~state["valid"]).astype(jnp.int32))
    return jnp.searchsorted(
        cum, jnp.arange(1, n + 1, dtype=jnp.int32)).astype(jnp.int32)


def _lru_slots(state: dict, n: int):
    """Invalid rows first (key -1 < any clock stamp), then LRU-evict valid
    rows — one top_k does both the free list and the paper's capacity-
    pressure expiry. Ties (all-invalid) break toward lower row ids, so
    this matches ``_free_slots`` whenever that path is applicable."""
    valid = state["valid"]
    accessed = state["cols"]["_accessed"]
    key = jnp.where(valid, accessed, -1)
    _, slots = jax.lax.top_k(-key, n)  # n smallest keys
    return slots


def _alloc_slots(state: dict, n: int, alloc: str | None = None):
    """Pick ``n`` slots: invalid rows first, then LRU-evict valid rows.

    The common case (table not full) takes the cheap free-list path; a
    device-side cond falls back to the LRU top_k under capacity pressure.
    ``alloc`` pins a path statically: executors running under vmap hoist
    the cond OUTSIDE the vmap (a vmapped cond lowers to select and would
    pay for BOTH paths) — "free" asserts the caller checked the free
    count, "lru" always evicts correctly. (The eviction count is computed
    by the caller, which knows the row mask.)"""
    if alloc == "free":
        return _free_slots(state, n)
    if alloc == "lru":
        return _lru_slots(state, n)
    return jax.lax.cond(
        jnp.sum((~state["valid"]).astype(jnp.int32)) >= n,
        lambda _: _free_slots(state, n),
        lambda _: _lru_slots(state, n),
        None)


def insert(
    schema: TableSchema,
    state: dict,
    values: Mapping[str, jax.Array],
    payloads: Mapping[str, jax.Array] | None = None,
    row_mask: jax.Array | None = None,
    ttl: jax.Array | int = 0,
    alloc: str | None = None,
):
    """Insert a batch of rows. ``values[col]`` has shape [n]; all columns
    not supplied default to 0. ``row_mask`` ([n] bool) lets a fixed-width
    executor insert fewer than n rows (padding support). Hash-index
    maintenance for ``schema.indexes`` is fused in: batches narrower than
    ``BULK_INDEX_THRESHOLD`` re-home the written slots with
    ``HX.insert_update_batched`` — each slot's old entry is cleared in
    the one bucket of its pre-insert key (an indexed column changes only
    here, and an UPDATE of it rebuilds the index, so the entry can live
    nowhere else), then the batch is rank-placed in its new buckets, with
    no serial per-slot chain; wider batches take ONE bulk sort-based
    rebuild instead. ``alloc`` pins the slot-allocator path (see
    ``_alloc_slots``).

    Returns (state, slots[n], evicted_count)."""
    payloads = payloads or {}
    n = None
    for v in values.values():
        n = np.shape(v)[0]
        break
    for v in payloads.values():
        n = np.shape(v)[0] if n is None else n
        break
    if n is None:
        raise ValueError("insert needs at least one column or payload")
    slots = _alloc_slots(state, n, alloc)
    if row_mask is None:
        row_mask = jnp.ones((n,), dtype=bool)
    # Rows whose mask is off write to a scratch slot? No — we redirect them
    # onto themselves by scattering with mode='drop' on an out-of-range index.
    cap = schema.capacity
    tgt = jnp.where(row_mask, slots, cap)  # cap is out-of-range -> dropped

    cols = dict(state["cols"])
    for c in schema.columns:
        vals = values.get(c.name)
        if vals is None:
            vals = jnp.zeros((n,), dtype=c.dtype)
        else:
            vals = jnp.asarray(vals).astype(c.dtype)
        cols[c.name] = cols[c.name].at[tgt].set(vals, mode="drop")
    now = state["clock"].astype(jnp.int32)
    now_b = jnp.broadcast_to(now, (n,))
    cols["_created"] = cols["_created"].at[tgt].set(now_b, mode="drop")
    cols["_accessed"] = cols["_accessed"].at[tgt].set(now_b, mode="drop")
    ttl_b = jnp.broadcast_to(jnp.asarray(ttl, dtype=jnp.int32), (n,))
    cols["_ttl"] = cols["_ttl"].at[tgt].set(ttl_b, mode="drop")

    pls = dict(state["payloads"])
    for p in schema.payloads:
        if p.name in payloads:
            pv = jnp.asarray(payloads[p.name]).astype(p.dtype)
            pls[p.name] = pls[p.name].at[tgt].set(pv, mode="drop")

    valid = state["valid"].at[tgt].set(True, mode="drop")
    indexes = state.get("indexes", {})
    if schema.indexes and indexes:
        row_mask_b = jnp.asarray(row_mask, dtype=bool)
        upd = {}
        if n >= BULK_INDEX_THRESHOLD:
            # bulk-load fast path: one sort-based rebuild from the
            # post-insert columns replaces the O(n) serial re-home chain
            nb = HX.n_buckets_for(cap)
            for ixc in schema.indexes:
                rid, key, overflow = OPS.hash_build(
                    cols[ixc], valid, n_buckets=nb)
                upd[ixc] = {"rid": rid, "key": key, "stale": overflow}
        else:
            for ixc in schema.indexes:
                # old keys come from the PRE-insert column (they name the
                # bucket holding the overwritten slot's entry)
                upd[ixc] = HX.insert_update_batched(
                    indexes[ixc], slots, state["cols"][ixc][slots],
                    cols[ixc][slots], row_mask_b, valid)
        indexes = dict(indexes, **upd)
    new_state = dict(state, cols=cols, payloads=pls, valid=valid,
                     indexes=indexes)
    new_state = _tick(new_state)
    # only count evictions of rows we actually overwrote
    evicted = jnp.sum((state["valid"][slots] & row_mask).astype(jnp.int32))
    return new_state, slots, evicted


def _match_mask(schema: TableSchema, state: dict, where: P.Node | None, params):
    mask = P.eval_predicate(where, state["cols"], params, schema.capacity)
    return mask & state["valid"]


def plan_for(schema: TableSchema, where, ranked: bool = False) -> PL.Plan:
    """The memoized plan for one WHERE against this schema (``ranked``
    marks ORDER BY statements — the planner sends those to the scan)."""
    return PL.plan_where(schema, where, ranked)


def _fused_plan(schema: TableSchema, where) -> P.FusedScan | None:
    """Legacy shim: the <=4-term fused-conjunction view of the plan (what
    ``classify_fusable`` used to return) — still used by the batched-DML
    eq-shape detection and the parity suites."""
    return PL.as_fused(PL.plan_where(schema, where))


def _fused_scan(schema, state, plan: P.FusedScan, params, *, limit,
                want_ids=True, mode=None):
    """Dispatch a classified predicate to the fused relscan path. Returns
    (ids, present, mask, count) or None if a runtime param has a non-int
    dtype (decided at trace time — dtypes are static under jit)."""
    vals = [t.resolve(params) for t in plan.terms]
    if not all(
        jnp.issubdtype(jnp.result_type(v), jnp.integer) for v in vals
    ):
        return None
    cols_t = tuple(state["cols"][c] for c in plan.columns)
    return OPS.predicate_scan(
        cols_t, state["valid"], jnp.asarray(vals, jnp.int32),
        ops=plan.ops, limit=limit, want_ids=want_ids, mode=mode)


def _compact(mask: jax.Array, limit: int, capacity: int):
    """Indices of the first ``limit`` set bits (row order), padded.

    Pure-jnp path (argmax / one-hot contraction — see kernels/relscan
    ``compact``); the Pallas ``relscan`` kernel implements the same
    contract in-kernel for on-TPU pools."""
    from repro.kernels.relscan import compact
    return compact(mask, limit=min(limit, capacity))


# ------------------------------------------------------ index-probe pieces

def index_fresh(state: dict, column: str) -> jax.Array:
    """Scalar bool: the hash index on ``column`` has never overflowed (a
    probe is complete). Executors cond their scan fallback on this."""
    return state["indexes"][column]["stale"] == 0


def _int_values(terms, params) -> bool:
    """Trace-time check: every term's runtime value has an integer dtype
    (a float bound to an int column must keep exact-compare semantics and
    demotes the plan to its scan fallback)."""
    return all(
        jnp.issubdtype(jnp.result_type(t.resolve(params)), jnp.integer)
        for t in terms
    )


def _probe_candidates(schema, state, plan: PL.IndexProbe, params, *,
                      mode=None, extra_mask=None):
    """One hash-bucket probe + candidate verification.

    Returns (safe [bucket_cap] clipped row ids, ok [bucket_cap] match
    bits): ``ok`` ANDs the bucket hit (lane occupied, stored key equal),
    the live key column (belt and braces for the entry invariant), the
    validity bitmap and every residual term — all gathers over one
    bucket, O(bucket_cap) regardless of capacity."""
    cap = schema.capacity
    idx = state["indexes"][plan.column]
    qv = jnp.asarray(plan.key.resolve(params), jnp.int32)
    cand, hit = OPS.hash_probe(idx["rid"], idx["key"], qv[None], mode=mode)
    cand, hit = cand[0], hit[0]
    safe = jnp.clip(cand, 0, cap - 1)
    ok = hit & state["valid"][safe] & (state["cols"][plan.column][safe] == qv)
    for t in plan.residual:
        tv = jnp.asarray(t.resolve(params), jnp.int32)
        ok = ok & P._CMP[t.op](state["cols"][t.col][safe], tv)
    if extra_mask is not None:
        ok = ok & jnp.broadcast_to(extra_mask, (cap,))[safe]
    return safe, ok


def _probe_ids(safe, ok, limit: int, capacity: int):
    """Candidate matches -> the scan compaction contract: first ``limit``
    matching row ids in ROW ORDER (0-padded) + presence + count. A fresh
    probe has count <= bucket_cap by construction (one key, one bucket)."""
    count = jnp.sum(ok.astype(jnp.int32))
    ordered = jnp.sort(jnp.where(ok, safe, capacity))
    if limit <= ordered.shape[0]:
        ids = ordered[:limit]
    else:
        ids = jnp.concatenate([
            ordered,
            jnp.full((limit - ordered.shape[0],), capacity, jnp.int32)])
    present = jnp.arange(limit, dtype=jnp.int32) < count
    return jnp.where(present, ids, 0).astype(jnp.int32), present, count


def _route(schema, where, params, plan):
    """Resolve the executor's route: caller-forced plan wins verbatim;
    otherwise the planner's choice, demoted to its fallback when a probe
    term is bound to a non-integer runtime value (trace-time)."""
    if plan is not None:
        return plan, True
    route = plan_for(schema, where)
    if isinstance(route, PL.IndexProbe) and not _int_values(
            (route.key,) + route.residual, params):
        route = route.fallback
    return route, False


def build_index(schema: TableSchema, state: dict,
                column: str | None = None) -> dict:
    """(Re)build the hash index(es) from the current column/validity state
    — the bulk path behind CREATE-with-data, UPDATEs that rewrite an
    indexed column, and explicit recovery from a stale (overflowed)
    index. Pure function of the state; jit/fuse freely."""
    cols = [column] if column is not None else list(schema.indexes)
    indexes = dict(state["indexes"])
    nb = HX.n_buckets_for(schema.capacity)
    for c in cols:
        rid, key, overflow = OPS.hash_build(
            state["cols"][c], state["valid"], n_buckets=nb)
        indexes[c] = {"rid": rid, "key": key, "stale": overflow}
    return dict(state, indexes=indexes)


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
    """SELECT, executed by plan. Returns (state, result dict).

    result = {"count": scalar, "rows": {col: [limit]}, "present": bool[limit],
              "payloads": {name: [limit, *shape]}}

    ``active`` (scalar bool) no-ops the whole statement — count 0, nothing
    present, no touch — so the daemon's micro-batch executor can pad its
    scan to a fixed bucket without side effects. ``plan`` forces a route
    (see module docstring); ``fused_mode``/``probe_mode`` pin the kernel
    implementation (the vmapped batch executor uses ``ref``).

    Every route returns through one epilogue: (new ``_accessed`` column,
    ids, present, count) — which is also what lets the index-probe route
    and its staleness-fallback scan share a ``lax.cond``.
    """
    limit = schema.max_select if limit is None else min(limit, schema.max_select)
    cap = schema.capacity
    now = state["clock"].astype(jnp.int32)
    accessed = state["cols"]["_accessed"]

    def finish_mask(mask, idx, present, count):
        if active is not None:
            count = jnp.where(active, count, 0)
            present = present & active
            mask = mask & active  # gates the touch below
        acc = jnp.where(mask, now, accessed) if touch else accessed
        return acc, idx.astype(jnp.int32), present, count

    def scan_route(r):
        fused = None
        if isinstance(r, PL.FusedScan):
            fused = _fused_scan(schema, state, r.scan, params, limit=limit,
                                mode=fused_mode)
        if fused is not None:
            idx, present, mask, count = fused
        else:
            mask = _match_mask(schema, state, where, params)
            count = jnp.sum(mask.astype(jnp.int32))
            idx, present = _compact(mask, limit, cap)
        return finish_mask(mask, idx, present, count)

    def probe_route(r):
        safe, ok = _probe_candidates(schema, state, r, params,
                                     mode=probe_mode)
        if active is not None:
            ok = ok & active
        ids, present, count = _probe_ids(safe, ok, limit, cap)
        acc = (accessed.at[jnp.where(ok, safe, cap)].set(now, mode="drop")
               if touch else accessed)
        return acc, ids, present, count

    if order_by is not None:
        # ranked reads stay on the scan path: top_k needs the full mask
        mask = _match_mask(schema, state, where, params)
        count = jnp.sum(mask.astype(jnp.int32))
        key = state["cols"][order_by]
        if jnp.issubdtype(key.dtype, jnp.integer):
            # monotone integer key: ~k = -k-1 flips the order without the
            # float32 cast (which collapses int32 values above 2^24) and
            # without the -k overflow at iinfo.min
            key = key if descending else ~key
            key = jnp.where(mask, key, jnp.iinfo(key.dtype).min)
        else:
            key = key if descending else -key
            key = jnp.where(mask, key, -jnp.inf)
        _, idx = jax.lax.top_k(key, limit)
        present = mask[idx]
        acc, idx, present, count = finish_mask(mask, idx, present, count)
    else:
        route, forced = _route(schema, where, params, plan)
        if isinstance(route, PL.IndexProbe):
            if forced:
                acc, idx, present, count = probe_route(route)
            else:
                acc, idx, present, count = jax.lax.cond(
                    index_fresh(state, route.column),
                    lambda _: probe_route(route),
                    lambda _: scan_route(route.fallback),
                    None)
        else:
            acc, idx, present, count = scan_route(route)

    columns = tuple(columns) if columns is not None else schema.column_names
    rows = {c: state["cols"][c][idx] for c in columns}
    pls = {p: state["payloads"][p][idx] for p in with_payloads}
    if touch:
        state = dict(state, cols=dict(state["cols"], _accessed=acc))
    state = _tick(state)
    return state, {
        "count": count,
        "rows": rows,
        "present": present,
        "row_ids": idx,
        "payloads": pls,
    }


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
    """UPDATE t SET col = expr ... WHERE pred, executed by plan. Returns
    (state, n_updated). ``extra_mask`` gates the match (micro-batch
    padding support). The probe route evaluates SET expressions in
    candidate space (per-bucket gathers + scatters, never a full-column
    where). An UPDATE that writes an indexed column rebuilds that index
    in the same dispatch (``maintain_indexes=False`` lets a batched
    executor defer ONE rebuild to after its scan)."""
    cap = schema.capacity
    set_items = [("_ttl" if name.upper() == "TTL" else name, expr)
                 for name, expr in set_exprs.items()]

    def scan_route(r):
        fused = None
        if isinstance(r, PL.FusedScan):
            fused = _fused_scan(schema, state, r.scan, params, limit=1,
                                want_ids=False)
        mask = (fused[2] if fused is not None
                else _match_mask(schema, state, where, params))
        if extra_mask is not None:
            mask = mask & extra_mask
        cols = dict(state["cols"])
        for tgt, expr in set_items:
            spec_dtype = cols[tgt].dtype
            newv = P.eval_expr(expr, state["cols"], params)
            newv = jnp.broadcast_to(jnp.asarray(newv, dtype=spec_dtype),
                                    (cap,))
            cols[tgt] = jnp.where(mask, newv, cols[tgt])
        return cols, jnp.sum(mask.astype(jnp.int32))

    def probe_route(r):
        safe, ok = _probe_candidates(schema, state, r, params,
                                     mode=probe_mode,
                                     extra_mask=extra_mask)
        gathered = {c: v[safe] for c, v in state["cols"].items()}
        tgt_rows = jnp.where(ok, safe, cap)
        cols = dict(state["cols"])
        for tgt, expr in set_items:
            spec_dtype = cols[tgt].dtype
            newv = P.eval_expr(expr, gathered, params)
            newv = jnp.broadcast_to(jnp.asarray(newv, dtype=spec_dtype),
                                    (safe.shape[0],))
            cols[tgt] = cols[tgt].at[tgt_rows].set(newv, mode="drop")
        return cols, jnp.sum(ok.astype(jnp.int32))

    route, forced = _route(schema, where, params, plan)
    if isinstance(route, PL.IndexProbe):
        if forced:
            cols, n = probe_route(route)
        else:
            cols, n = jax.lax.cond(
                index_fresh(state, route.column),
                lambda _: probe_route(route),
                lambda _: scan_route(route.fallback),
                None)
    else:
        cols, n = scan_route(route)
    state = dict(state, cols=cols)
    if maintain_indexes and schema.indexes:
        written = {tgt for tgt, _ in set_items}
        for ixc in schema.indexes:
            if ixc in written:
                state = build_index(schema, state, ixc)
    state = _tick(state)
    return state, n


def _delete_core(schema, state, where, params, *, want_ids, limit,
                 extra_mask=None, plan=None, probe_mode=None):
    """Shared DELETE executor: returns (valid', n, ids, present) — ids and
    present are None when ``want_ids`` is False. Probe route flips only
    the candidate rows' validity bits (O(bucket_cap) scatter)."""
    cap = schema.capacity
    no_ids = (jnp.zeros((limit,), jnp.int32),
              jnp.zeros((limit,), dtype=bool))

    def scan_route(r):
        # ids must reflect the FINAL (extra_mask-gated) match, identically
        # to probe_route, so the in-kernel compaction serves them only
        # when no extra_mask applies afterwards
        kernel_ids = want_ids and extra_mask is None
        fused = None
        if isinstance(r, PL.FusedScan):
            fused = _fused_scan(schema, state, r.scan, params, limit=limit,
                                want_ids=kernel_ids)
        if fused is not None:
            ids, present, mask, _ = fused
        else:
            mask = _match_mask(schema, state, where, params)
            ids = present = None
        if extra_mask is not None:
            mask = mask & extra_mask
        n = jnp.sum(mask.astype(jnp.int32))
        if want_ids and ids is None:
            ids, present = _compact(mask, limit, cap)
        if not want_ids:
            ids, present = no_ids
        return state["valid"] & ~mask, n, ids, present

    def probe_route(r):
        safe, ok = _probe_candidates(schema, state, r, params,
                                     mode=probe_mode,
                                     extra_mask=extra_mask)
        n = jnp.sum(ok.astype(jnp.int32))
        valid = state["valid"].at[jnp.where(ok, safe, cap)].set(
            False, mode="drop")
        ids, present = (_probe_ids(safe, ok, limit, cap)[:2] if want_ids
                        else no_ids)
        return valid, n, ids, present

    route, forced = _route(schema, where, params, plan)
    if isinstance(route, PL.IndexProbe):
        if forced:
            return probe_route(route)
        return jax.lax.cond(
            index_fresh(state, route.column),
            lambda _: probe_route(route),
            lambda _: scan_route(route.fallback),
            None)
    return scan_route(route)


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
    """DELETE FROM t WHERE pred — flips validity bits only; payload bytes
    never move (the 0.2 ms-vs-1000 ms effect from the paper's Table 2).
    ``extra_mask`` (scalar or [cap] bool) further gates the match — the
    daemon's micro-batch executor uses it to no-op padded statements.
    Hash indexes need no maintenance here: dead entries are masked by the
    validity gather at probe time."""
    valid, n, _, _ = _delete_core(schema, state, where, params,
                                  want_ids=False, limit=1,
                                  extra_mask=extra_mask, plan=plan,
                                  probe_mode=probe_mode)
    state = dict(state, valid=valid)
    state = _tick(state)
    return state, n


def delete_many_eq(
    schema: TableSchema,
    state: dict,
    column: str,
    vals: jax.Array,
    active: jax.Array,
    *,
    per_statement: bool = False,
):
    """One-pass multi-value equality DELETE: flip every valid row whose
    ``column`` equals ANY active entry of ``vals`` — W statements, ONE scan
    over the table (sort the W values, binary-search each row into them).
    The count equals the sequential per-statement total because deletes
    commute. INT32_MAX is reserved as the padding sentinel. The logical
    clock advances by the number of ACTIVE statements (padding is free),
    matching the sequential path's TTL semantics.

    ``per_statement=True`` additionally attributes each deleted row to
    ONE statement under sequential semantics — the EARLIEST statement
    carrying that row's value (later duplicates find it already gone).
    The stable sort keeps equal values in admission order, so the first
    lane of each equal-value run is that earliest statement; every row
    scatter-adds its count there. Still one pass — this is what lets the
    wire scheduler keep the fast path while answering every client with
    its own COUNT.

    Returns (state, n_deleted) or (state, n_deleted, counts[W])."""
    w = vals.shape[0]
    sentinel = jnp.iinfo(jnp.int32).max
    keyed = jnp.where(active, vals.astype(jnp.int32), sentinel)
    n_act = jnp.sum(active.astype(jnp.int32))
    col = state["cols"][column]
    valid = state["valid"]
    ns = None
    act = jnp.asarray(active, dtype=bool)
    if per_statement and w <= _EQ_DIRECT_MAX:
        # narrow batches: claim rows statement by statement (a short
        # unrolled chain of compares) — the wide path's O(capacity)
        # attribution scatter costs more than the whole delete here.
        # Inactive lanes must be gated explicitly: their sentinel key
        # would otherwise match genuine INT32_MAX rows.
        remaining = valid
        parts = []
        for i in range(w):
            m = remaining & (col == keyed[i]) & act[i]
            parts.append(jnp.sum(m.astype(jnp.int32)))
            remaining = remaining & ~m
        hit = valid & ~remaining
        ns = jnp.stack(parts)
    elif w <= _EQ_DIRECT_MAX:
        # small batches: W direct compares beat the sort+searchsorted,
        # whose fixed per-row binary-search cost only amortizes wide
        # (inactive lanes gated as above)
        hit = valid & jnp.any(
            (col[None, :] == keyed[:, None]) & act[:, None], axis=0)
    else:
        order = jnp.argsort(keyed, stable=True).astype(jnp.int32)
        sv = keyed[order]
        pos = jnp.clip(jnp.searchsorted(sv, col), 0, w - 1)
        hit = valid & (sv[pos] == col) & (pos < n_act)
        if per_statement:
            # searchsorted('left') lands every row on the FIRST lane of
            # its value's run = the earliest statement with that value
            ns = jnp.zeros((w,), jnp.int32).at[
                jnp.where(hit, order[pos], w)].add(
                    hit.astype(jnp.int32), mode="drop")
    n = jnp.sum(hit.astype(jnp.int32))
    state = dict(state, valid=valid & ~hit)
    state["clock"] = state["clock"] + n_act
    state["ops"] = state["ops"] + n_act
    if not per_statement:
        return state, n
    return state, n, ns


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
    """DELETE that also reports which rows went: returns
    (state, n, row_ids[limit], present[limit]). Row ids feed incremental
    index maintenance (kvpool.page_table_update) — the metadata columns of
    deleted rows stay intact, so callers can still read slot/pos there."""
    limit = schema.max_select if limit is None else limit
    valid, n, ids, present = _delete_core(schema, state, where, params,
                                          want_ids=True, limit=limit,
                                          plan=plan, probe_mode=probe_mode)
    state = dict(state, valid=valid)
    state = _tick(state)
    return state, n, ids, present


_AGGS = {
    "COUNT": lambda v, m: jnp.sum(m.astype(jnp.int32)),
    "SUM": lambda v, m: jnp.sum(jnp.where(m, v, 0)),
    "MIN": lambda v, m: jnp.min(jnp.where(m, v, jnp.inf)).astype(v.dtype)
    if jnp.issubdtype(v.dtype, jnp.floating)
    else jnp.min(jnp.where(m, v, jnp.iinfo(v.dtype).max)),
    "MAX": lambda v, m: jnp.max(jnp.where(m, v, -jnp.inf)).astype(v.dtype)
    if jnp.issubdtype(v.dtype, jnp.floating)
    else jnp.max(jnp.where(m, v, jnp.iinfo(v.dtype).min)),
    "AVG": lambda v, m: jnp.sum(jnp.where(m, v.astype(jnp.float32), 0.0))
    / jnp.maximum(jnp.sum(m.astype(jnp.int32)), 1),
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
    """COUNT/SUM/MIN/MAX/AVG over the matching rows, executed by plan
    (an indexed eq WHERE aggregates over one bucket's candidates instead
    of a full column). Returns (state, value)."""
    agg = agg.upper()

    def reduce(vals, mask):
        if agg == "COUNT" or column is None:
            return _AGGS["COUNT"](None, mask)
        return _AGGS[agg](vals, mask)

    def scan_route(r):
        fused = None
        if isinstance(r, PL.FusedScan):
            fused = _fused_scan(schema, state, r.scan, params, limit=1,
                                want_ids=False, mode=fused_mode)
        mask = (fused[2] if fused is not None
                else _match_mask(schema, state, where, params))
        return reduce(state["cols"][column] if column is not None else None,
                      mask)

    def probe_route(r):
        safe, ok = _probe_candidates(schema, state, r, params,
                                     mode=probe_mode)
        return reduce(state["cols"][column][safe]
                      if column is not None else None, ok)

    route, forced = _route(schema, where, params, plan)
    if isinstance(route, PL.IndexProbe):
        if forced:
            val = probe_route(route)
        else:
            val = jax.lax.cond(
                index_fresh(state, route.column),
                lambda _: probe_route(route),
                lambda _: scan_route(route.fallback),
                None)
    else:
        val = scan_route(route)
    state = _tick(state)
    return state, val


def expire(schema: TableSchema, state: dict):
    """Automatic expiry — the paper's §4.3 conditions 1 (age) and 2 (rows).

    Condition 3 (op count) is the daemon's trigger for calling this.
    Returns (state, n_expired)."""
    pol = schema.expiry
    valid = state["valid"]
    cols = state["cols"]
    now = state["clock"].astype(jnp.int32)
    expired = jnp.zeros_like(valid)

    # 1. data age: per-row _ttl overrides the table default
    default_ttl = jnp.asarray(pol.ttl, dtype=jnp.int32)
    ttl_eff = jnp.where(cols["_ttl"] > 0, cols["_ttl"], default_ttl)
    aged = (ttl_eff > 0) & ((now - cols["_created"]) > ttl_eff)
    expired = expired | (valid & aged)

    # 2. row-count cap: keep the newest max_rows (stable tie-break by row id).
    # Overflow-safe ordering: rank rows by (created, row_id) via double
    # argsort instead of a keyed multiply (which overflows int32 clocks).
    if pol.max_rows > 0 and pol.max_rows < schema.capacity:
        cap = schema.capacity
        live = valid & ~expired
        order = jnp.lexsort((jnp.arange(cap), cols["_created"]))  # old -> new
        rank = jnp.zeros((cap,), dtype=jnp.int32).at[order].set(
            jnp.arange(cap, dtype=jnp.int32)
        )
        # rank among LIVE rows only: count live rows with strictly lower rank
        live_i = live.astype(jnp.int32)
        # cumulative live count in rank order, mapped back to row order
        live_in_rank = live_i[order]
        cum = jnp.cumsum(live_in_rank) - live_in_rank  # live rows older than me
        older_live = jnp.zeros((cap,), dtype=jnp.int32).at[order].set(cum)
        n_live = jnp.sum(live_i)
        # drop the oldest (n_live - max_rows): live rows whose "younger live
        # count" = n_live - older_live - 1 >= max_rows
        younger = n_live - older_live - 1
        drop = live & (younger >= pol.max_rows)
        expired = expired | drop

    n = jnp.sum(expired.astype(jnp.int32))
    state = dict(state, valid=valid & ~expired)
    state = _tick(state)
    return state, n


def flush(schema: TableSchema, state: dict):
    """Drop every row (memcached's only bulk invalidation mode). Hash
    indexes reset to empty — an empty table's index is trivially exact,
    so FLUSH also recovers from a stale (overflowed) index."""
    n = jnp.sum(state["valid"].astype(jnp.int32))
    state = dict(state, valid=jnp.zeros_like(state["valid"]))
    if schema.indexes:
        nb = HX.n_buckets_for(schema.capacity)
        state["indexes"] = {c: HX.empty_index(nb) for c in schema.indexes}
    state = _tick(state)
    return state, n


def live_count(state: dict) -> jax.Array:
    return jnp.sum(state["valid"].astype(jnp.int32))


def batch_touch(schema: TableSchema, state: dict, res: dict,
                active: jax.Array) -> dict:
    """Fused epilogue for the daemon's micro-batched SELECT (one vmapped
    read over W parameter rows): touch the RETURNED rows and advance the
    clock by the active statement count (padding must not age TTLs).
    ``core/shards.batch_touch`` is the stacked-state twin — the daemon
    calls whichever engine owns the table."""
    now = state["clock"].astype(jnp.int32)
    tgt = jnp.where(res["present"], res["row_ids"], schema.capacity)
    cols = dict(state["cols"])
    cols["_accessed"] = cols["_accessed"].at[tgt.reshape(-1)].set(
        now, mode="drop")
    nact = jnp.sum(active.astype(jnp.int32))
    return dict(state, cols=cols, clock=state["clock"] + nact,
                ops=state["ops"] + nact)
