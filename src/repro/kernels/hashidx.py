"""Pallas TPU hash index: device-resident bucketed hash tables over the
int32 key columns of a RelTable — the O(1) replacement for the O(capacity)
relscan on equality lookups (the companion paper's hash-index engine,
arXiv:0809.3542, re-hosted on an accelerator).

Index layout (one per indexed column, carried inside the table state):

    rid  [n_buckets, bucket_cap] int32   row ids, ``EMPTY`` (-1) = free lane
    key  [n_buckets, bucket_cap] int32   the key value stored at insert time
    stale scalar int32                   >0 -> the index may MISS rows and
                                         every probe must take the scan path

``bucket_cap`` is one lane row (128), so a probe reads exactly one aligned
VMEM tile. Buckets are chosen by a multiplicative (Fibonacci) hash of the
key; all rows sharing a key land in ONE bucket, so an equality probe is
complete by construction — unless an insert ever found its bucket full, in
which case ``stale`` is set and executors fall back to the full scan
*inside the same jitted dispatch* (a ``lax.cond``), with zero host syncs.
``stale`` is sticky (the overflowed rows are simply not in the index);
recovery is explicit — ``REINDEX t`` bulk-rebuilds once the duplicate
burst is gone, ``FLUSH t`` resets to the trivially exact empty index,
and ``EXPLAIN`` surfaces the stale counter so the degradation is
observable from a socket client.

Invariant maintained by the maintenance ops (and assumed by ``probe``):
every row slot appears in at most ONE lane, in the bucket of its *current*
key column value. DELETE/FLUSH/EXPIRE only flip validity bits and never
touch the index — dead entries are masked by the validity gather at probe
time and reclaimed when their slot is reused (the old key is still
readable, exactly like kvpool's page-table trick). UPDATEs that write an
indexed column rebuild that index in the same dispatch.

Build and probe (entry points ``kernels/ops.hash_build/hash_probe``):

``build``   bulk (re)build, XLA only: one sort groups row ids by bucket,
            then gathers pull each bucket's contiguous segment into its
            ``[bucket_cap]`` lane row. The key gather is random access,
            which a TPU kernel could only issue as one DMA per row, so
            there is no Pallas variant.
``probe``   batched lookup, a Pallas kernel (mode selection in ``ops``):
            bucket ids ride in as prefetched scalars so the BlockSpec
            index map DMAs the one aligned tile holding each query's
            bucket; the kernel emits candidate row ids + key-match bits.
            ``probe_ref`` is its jnp oracle and the fast mode on non-TPU
            backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BUCKET_CAP = LANES  # one aligned lane row per bucket
EMPTY = -1          # free-lane sentinel in the rid array
_PRIME = 2654435761  # 2^32 / phi — Fibonacci hashing multiplier


def n_buckets_for(capacity: int) -> int:
    """Bucket count for a table capacity: the next power of two of
    capacity/32 (mean occupancy 32/128 at full capacity — deep headroom
    before any bucket can overflow), floored at 8."""
    target = max(8, -(-capacity // 32))
    nb = 1
    while nb < target:
        nb *= 2
    return nb


def bucket_of(keys: jax.Array, n_buckets: int) -> jax.Array:
    """Multiplicative hash -> bucket id. Uses the TOP bits of the 32-bit
    product (the well-mixed ones), so sequential keys spread."""
    lg = n_buckets.bit_length() - 1
    ku = keys.astype(jnp.uint32) * jnp.uint32(_PRIME)
    return (ku >> jnp.uint32(32 - lg)).astype(jnp.int32)


def empty_index(n_buckets: int, bucket_cap: int = BUCKET_CAP) -> dict:
    """A fresh (all-lanes-free) index for an empty table."""
    return {
        "rid": jnp.full((n_buckets, bucket_cap), EMPTY, dtype=jnp.int32),
        "key": jnp.zeros((n_buckets, bucket_cap), dtype=jnp.int32),
        "stale": jnp.zeros((), dtype=jnp.int32),
    }


# ------------------------------------------------------------------- build

def _build_sorted(keys: jax.Array, valid: jax.Array, n_buckets: int):
    """Build prologue: group row ids by bucket with one XLA sort.

    Returns (order, sb, start, overflow): ``order`` is row ids sorted by
    bucket (invalid rows pushed to the end under sentinel ``n_buckets``),
    ``sb`` the matching sorted bucket ids, ``start[b]`` the first sorted
    position of bucket ``b``, and ``overflow`` the count of valid rows
    whose within-bucket rank fell past ``bucket_cap`` (-> stale)."""
    cap = keys.shape[0]
    b = jnp.where(valid, bucket_of(keys.astype(jnp.int32), n_buckets),
                  n_buckets)
    order = jnp.argsort(b).astype(jnp.int32)
    sb = b[order]
    start = jnp.searchsorted(sb, jnp.arange(n_buckets, dtype=jnp.int32),
                             side="left").astype(jnp.int32)
    rank = jnp.arange(cap, dtype=jnp.int32) - jnp.searchsorted(
        sb, sb, side="left").astype(jnp.int32)
    overflow = jnp.sum(((sb < n_buckets) & (rank >= BUCKET_CAP))
                       .astype(jnp.int32))
    return order, sb, start, overflow


def build(keys: jax.Array, valid: jax.Array, *, n_buckets: int):
    """Bulk (re)build: gather each bucket's sorted segment.

    Returns (rid [nb, cap_b], key [nb, cap_b], stale scalar)."""
    cap = keys.shape[0]
    order, sb, start, overflow = _build_sorted(keys, valid, n_buckets)
    pad = jnp.full((BUCKET_CAP,), cap, dtype=jnp.int32)
    orderp = jnp.concatenate([order, pad])  # safe to over-slice
    sbp = jnp.concatenate([sb, jnp.full((BUCKET_CAP,), n_buckets,
                                        jnp.int32)])
    pos = start[:, None] + jnp.arange(BUCKET_CAP, dtype=jnp.int32)[None, :]
    rid = orderp[pos]
    ok = sbp[pos] == jnp.arange(n_buckets, dtype=jnp.int32)[:, None]
    rid = jnp.where(ok, rid, EMPTY)
    keysp = jnp.concatenate([keys.astype(jnp.int32),
                             jnp.zeros((1,), jnp.int32)])
    key = jnp.where(ok, keysp[jnp.clip(rid, 0, cap)], 0)
    return rid, key, overflow


# ------------------------------------------------------------------- probe

def probe_ref(rid: jax.Array, key: jax.Array, qkeys: jax.Array):
    """jnp probe: gather one bucket row per query key.

    qkeys: [w] int32. Returns (cand [w, cap_b] row ids, hit [w, cap_b]
    bool — lane occupied AND stored key equals the query). Callers still
    AND in validity / residual terms (see table._probe_candidates)."""
    nb = rid.shape[0]
    b = bucket_of(qkeys.astype(jnp.int32), nb)
    cand = rid[b]
    hit = (cand != EMPTY) & (key[b] == qkeys.astype(jnp.int32)[:, None])
    return cand, hit


def _probe_kernel(qk_ref, bid_ref, rid_ref, key_ref, cand_ref, hit_ref):
    """Grid step ``i``: the index map DMA'd the aligned 8-bucket tile
    holding query ``i``'s bucket; pick its row and store it into row ``i``
    of the resident outputs."""
    i = pl.program_id(0)
    r = bid_ref[i] % 8
    cand = rid_ref[pl.ds(r, 1), :]
    hit = (cand != EMPTY) & (key_ref[pl.ds(r, 1), :] == qk_ref[i])
    cand_ref[pl.ds(i, 1), :] = cand
    hit_ref[pl.ds(i, 1), :] = hit.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def probe(rid: jax.Array, key: jax.Array, qkeys: jax.Array, *,
          interpret: bool = False):
    """Pallas batched probe: the bucket id of every query rides in as a
    prefetched scalar, so the BlockSpec index map DMAs exactly the one
    (8, bucket_cap) tile that holds its bucket (the TPU's 32-bit tiling
    forbids a one-row block). Contract of :func:`probe_ref`."""
    nb, cap_b = rid.shape
    w = qkeys.shape[0]
    wp = -(-w // 8) * 8
    qk = qkeys.astype(jnp.int32)
    bids = bucket_of(qk, nb)
    tile = pl.BlockSpec((8, cap_b), lambda i, qk, bid: (bid[i] // 8, 0))
    out = pl.BlockSpec((wp, cap_b), lambda i, qk, bid: (0, 0))
    cand, hit = pl.pallas_call(
        _probe_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(w,),
            in_specs=[tile, tile], out_specs=[out, out]),
        out_shape=[
            jax.ShapeDtypeStruct((wp, cap_b), jnp.int32),
            jax.ShapeDtypeStruct((wp, cap_b), jnp.int32),
        ],
        interpret=interpret,
        name="hashidx_probe",
    )(qk, bids, rid, key)
    return cand[:w], hit[:w] != 0


# ------------------------------------------------- incremental maintenance

def insert_update(idx: dict, slots: jax.Array, old_keys: jax.Array,
                  new_keys: jax.Array, row_mask: jax.Array,
                  valid: jax.Array) -> dict:
    """Fused-into-INSERT index maintenance: for each inserted row, clear
    the overwritten slot's old entry (its pre-insert key names the bucket
    — the kvpool page-table trick) and place the slot in its new key's
    bucket. Sequential over the batch (a ``fori_loop``) because batch
    members may share a bucket; each step is O(bucket_cap).

    ``old_keys`` must be gathered from the PRE-insert column, ``valid``
    and ``new_keys`` from the post-insert state. A full bucket sets
    ``stale`` (probes then take the in-dispatch scan fallback)."""
    nb = idx["rid"].shape[0]
    n = slots.shape[0]
    ob = bucket_of(old_keys.astype(jnp.int32), nb)
    nbk = bucket_of(new_keys.astype(jnp.int32), nb)
    validp = jnp.concatenate([valid, jnp.zeros((1,), dtype=bool)])

    def body(j, carry):
        rid, key, stale = carry
        s = slots[j]
        act = row_mask[j]
        # 1. clear the slot's previous entry (invariant: it can only live
        #    in the bucket of its pre-insert key)
        row = jax.lax.dynamic_slice(rid, (ob[j], 0), (1, BUCKET_CAP))[0]
        row = jnp.where(act & (row == s), EMPTY, row)
        rid = jax.lax.dynamic_update_slice(rid, row[None], (ob[j], 0))
        # 2. place the slot in its new bucket's first free lane (free =
        #    empty, or held by a row that is no longer valid)
        row = jax.lax.dynamic_slice(rid, (nbk[j], 0), (1, BUCKET_CAP))[0]
        krow = jax.lax.dynamic_slice(key, (nbk[j], 0), (1, BUCKET_CAP))[0]
        free = (row == EMPTY) | ~validp[jnp.clip(row, 0, validp.shape[0] - 1)]
        lane = jnp.argmax(free)
        found = jnp.any(free)
        place = act & found
        row = jnp.where(place & (jnp.arange(BUCKET_CAP) == lane), s, row)
        krow = jnp.where(place & (jnp.arange(BUCKET_CAP) == lane),
                         new_keys[j].astype(jnp.int32), krow)
        rid = jax.lax.dynamic_update_slice(rid, row[None], (nbk[j], 0))
        key = jax.lax.dynamic_update_slice(key, krow[None], (nbk[j], 0))
        stale = stale + jnp.where(act & ~found, 1, 0).astype(jnp.int32)
        return rid, key, stale

    rid, key, stale = jax.lax.fori_loop(
        0, n, body, (idx["rid"], idx["key"], idx["stale"]))
    return {"rid": rid, "key": key, "stale": stale}


@jax.named_scope("hashidx_upkeep")
def insert_update_batched(idx: dict, slots: jax.Array, old_keys: jax.Array,
                          new_keys: jax.Array, row_mask: jax.Array,
                          valid: jax.Array) -> dict:
    """Batched twin of :func:`insert_update` — same contract, no serial
    chain. The ``fori_loop`` above costs O(batch) *dependent* steps; this
    re-homes the whole batch in a fixed number of parallel passes:

    1. **clear** — each member looks for its slot in ONE bucket row, the
       bucket of its pre-insert key (``old_keys``): the module's
       invariant says the slot's entry can only live there. One point
       scatter writes ``EMPTY`` at the lane found; a member with no
       entry (a slot never indexed, or an overflow victim) scatters out
       of range and is dropped. Members sharing an old bucket hold
       distinct slots, and a slot holds at most one lane, so the
       scatter's positions are distinct. The work is O(batch x
       bucket_cap), not a pass over every index lane;
    2. **place** — batch members sharing a destination bucket get their
       within-bucket arrival rank (the ``_build_sorted`` argsort +
       searchsorted trick at batch width), and member with rank ``r``
       takes the (r+1)-th free lane of its bucket — distinct ranks map
       to distinct lanes, so the final scatter is conflict-free.

    A member whose rank exceeds its bucket's free-lane count marks the
    index stale, like the sequential path (ranks are monotone within a
    bucket, so the failure set matches arrival order). Lane POSITIONS may
    differ from the sequential path when one member's clear frees a lane
    an earlier member then takes — probes never read lane order, so the
    entry set is what matters (tests/test_hashidx.py compares per-bucket
    entry sets against the loop). Only in a FULL bucket does the entry
    set differ too: a member the loop finds the bucket full for takes a
    lane that a later member's clear frees, so the index holds more rows
    and counts fewer stale (both sound; stale already sends probes to
    the scan). Its ops carry the name scope ``hashidx_upkeep``."""
    nb, cap_b = idx["rid"].shape
    n = slots.shape[0]
    cap = valid.shape[0]
    act = jnp.asarray(row_mask, dtype=bool)
    ob = bucket_of(old_keys.astype(jnp.int32), nb)
    nbk = bucket_of(new_keys.astype(jnp.int32), nb)
    validp = jnp.concatenate([valid, jnp.zeros((1,), dtype=bool)])

    # 1. clear: find each member's slot in its old bucket's row
    held = idx["rid"][ob] == slots[:, None]       # [n, cap_b]
    ci = jnp.where(act & jnp.any(held, axis=1), ob, nb)  # nb -> dropped
    rid0 = idx["rid"].at[ci, jnp.argmax(held, axis=1)].set(EMPTY,
                                                           mode="drop")

    # 2. place: within-bucket arrival rank -> the (rank+1)-th free lane
    b = jnp.where(act, nbk, nb)  # inactive rows sort to the sentinel end
    order = jnp.argsort(b, stable=True).astype(jnp.int32)
    sb = b[order]
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - jnp.searchsorted(
        sb, sb, side="left").astype(jnp.int32)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    rows = rid0[nbk]                              # [n, cap_b]
    free = (rows == EMPTY) | ~validp[jnp.clip(rows, 0, cap)]
    cumfree = jnp.cumsum(free.astype(jnp.int32), axis=1)
    want = rank + 1
    found = cumfree[:, -1] >= want
    lane = jnp.argmax(cumfree == want[:, None], axis=1)
    place = act & found
    bi = jnp.where(place, nbk, nb)  # out-of-range bucket -> dropped
    rid = rid0.at[bi, lane].set(slots, mode="drop")
    key = idx["key"].at[bi, lane].set(new_keys.astype(jnp.int32),
                                      mode="drop")
    stale = idx["stale"] + jnp.sum((act & ~found).astype(jnp.int32))
    return {"rid": rid, "key": key, "stale": stale}
