"""Pallas TPU relscan: fused predicate scan + compaction over RelTable
metadata columns — the ``SELECT/DELETE ... WHERE`` hot path of the daemon.

The daemon's dominant predicates are conjunctions of equality/range terms
over 1..4 integer columns (``seq_id = ?``, ``slot = ? AND pos_block = ?``,
``ts BETWEEN ? AND ?``). Two grid-tiled passes, both fused:

pass 1 (``_scan_kernel``)     load column tiles into VMEM -> evaluate every
                              term against the scalar-prefetched value
                              vector -> AND with the validity tile ->
                              bitmap tile + per-tile match count.
pass 2 (``_compact_kernel``)  a prefix-sum over the tile counts (tiny jnp op
                              between the passes) gives each tile its output
                              offset; the kernel walks its tile in (8, 128)
                              chunks, turns each chunk's bits into global
                              output positions with a shift-and-add
                              row-major prefix sum, and drops the first
                              ``limit`` matching row ids into a resident
                              [limit, 128] accumulator (one lane per slot,
                              summed after the call) — no O(capacity)
                              ``jnp.nonzero`` epilogue. Tiles that hold no
                              wanted match are skipped without a DMA.

At 10^3..10^6 rows a vectorized scan beats pointer chasing on this
hardware (DESIGN.md §2 — the B-tree replacement). Operator codes are
compile-time constants (the prepared-statement cache); comparison values
arrive at runtime, so one compiled kernel serves every execution of a
statement shape. Mode selection (kernel/interpret/ref) lives in
``kernels/ops.predicate_scan``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_TERMS = 4

_CMP = {
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
}


def _scan_kernel(vals_ref, *refs, ops: tuple[str, ...]):
    """refs = (col_ref * nterms, valid_ref, mask_ref, cnt_ref); vals_ref is
    the scalar-prefetched [MAX_TERMS] comparison vector (SMEM)."""
    nt = len(ops)
    valid_ref, mask_ref, cnt_ref = refs[nt], refs[nt + 1], refs[nt + 2]
    m = valid_ref[...] != 0
    for t, op in enumerate(ops):
        m = m & _CMP[op](refs[t][...], vals_ref[t])
    mi = m.astype(jnp.int32)
    mask_ref[...] = mi
    cnt_ref[...] = jnp.full(cnt_ref.shape, jnp.sum(mi), jnp.int32)


def _inclusive_scan(x, axis: int, n: int):
    """Inclusive prefix sum of int32 ``x`` along ``axis`` (length ``n``, a
    power of two) by log2(n) shift-and-add steps (Hillis-Steele): rolls
    and selects only, which Mosaic lowers natively where ``cumsum`` is
    not supported."""
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < n:
        x = x + jnp.where(idx >= s, pltpu.roll(x, s, axis), 0)
        s *= 2
    return x


def _compact_kernel(off_ref, cnt_ref, tidx_ref, mask_ref, acc_ref, *,
                    block: int, rows: int, limitp: int):
    """Scatter this tile's matching row ids into output slots
    off..off+count (row-major order) of the resident [limitp, LANES]
    accumulator: slot ``j`` collects its row id in exactly one lane, so
    the caller's lane sum yields the id. Tiles with no match, or whose
    offset is already past the limit, do nothing (and their index map
    re-points at the previous useful tile, so no DMA is issued)."""
    del tidx_ref  # only the index map reads it
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((cnt_ref[i] > 0) & (off_ref[i] < limitp))
    def _tile():
        jcol = jax.lax.broadcasted_iota(jnp.int32, (limitp, LANES), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)

        def chunk(c, run):
            r0 = pl.multiple_of(c * 8, 8)
            mc = mask_ref[pl.ds(r0, 8), :]                # (8, LANES) 0/1
            tot = jnp.sum(mc)

            @pl.when((tot > 0) & (run < limitp))
            def _scatter():
                lane_inc = _inclusive_scan(mc, 1, LANES)
                row_tot = jnp.broadcast_to(
                    jnp.sum(mc, axis=1, keepdims=True), (8, LANES))
                row_pre = _inclusive_scan(row_tot, 0, 8) - row_tot
                pos = jnp.where(mc != 0, lane_inc - 1 + row_pre + run, -1)
                rid = i * block + (r0 + jax.lax.broadcasted_iota(
                    jnp.int32, (8, LANES), 0)) * LANES + lane
                acc = acc_ref[...]
                for r in range(8):                        # static unroll
                    hit = pos[r:r + 1, :] == jcol          # (limitp, LANES)
                    acc = acc + jnp.where(hit, rid[r:r + 1, :], 0)
                acc_ref[...] = acc

            return run + tot

        jax.lax.fori_loop(0, rows // 8, chunk, off_ref[i])


def _pad_to(x, n, fill):
    if x.shape[0] == n:
        return x
    return jnp.pad(x, (0, n - x.shape[0]), constant_values=fill)


@functools.partial(
    jax.jit,
    static_argnames=("ops", "limit", "block", "interpret", "want_ids"))
def relscan(cols: Sequence[jax.Array], valid: jax.Array, vals: jax.Array, *,
            ops: tuple[str, ...], limit: int, block: int = 32768,
            interpret: bool = False, want_ids: bool = True):
    """Fused conjunction scan over up to MAX_TERMS integer columns.

    cols:  one [cap] int32 array per term (a column may repeat, e.g. for
           BETWEEN ranges); ops: per-term comparison codes (static);
    vals:  [nterms] int32 runtime comparison values;
    valid: [cap] bool validity bitmap, ANDed into the match.

    Returns (ids, present, mask, count):
      ids [limit] int32     first ``limit`` matching row ids in row order
                            (0-padded — same contract as table._compact),
      present [limit] bool  which of those slots hold a real match,
      mask [cap] bool       full match bitmap (for touch/delete fusion),
      count int32 scalar    total matches (unclamped).
    When ``want_ids`` is False pass 2 is skipped and ids/present are None.

    ``block`` rows per grid step (a multiple of 8 x LANES; shrunk to the
    padded capacity for small tables). Masks cross the kernels as int32
    tiles: the TPU's native (8, 128) 32-bit tiling, which every compare
    and select here lowers to without a relayout.
    """
    if not 1 <= len(ops) <= MAX_TERMS or len(cols) != len(ops):
        raise ValueError(f"relscan supports 1..{MAX_TERMS} terms")
    cap = valid.shape[0]
    unit = 8 * LANES
    block = min(max(unit, block // unit * unit), -(-cap // unit) * unit)
    nblk = -(-cap // block)
    capp = nblk * block
    rows = block // LANES

    cols2 = [_pad_to(c.astype(jnp.int32), capp, 0).reshape(-1, LANES)
             for c in cols]
    valid2 = _pad_to(valid.astype(jnp.int32), capp, 0).reshape(-1, LANES)
    vals1 = jnp.zeros((MAX_TERMS,), jnp.int32).at[: len(ops)].set(
        jnp.asarray(vals, jnp.int32)[: len(ops)])

    tile = pl.BlockSpec((rows, LANES), lambda i, v: (i, 0))
    mask2, cnt = pl.pallas_call(
        functools.partial(_scan_kernel, ops=ops),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblk,),
            in_specs=[tile] * (len(ops) + 1),
            out_specs=[
                tile,
                # per-tile count, broadcast over one (8, LANES) int32 tile
                pl.BlockSpec((None, 8, LANES), lambda i, v: (i, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((capp // LANES, LANES), jnp.int32),
            jax.ShapeDtypeStruct((nblk, 8, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="relscan_scan",
    )(vals1, *cols2, valid2)

    cnt = cnt[:, 0, 0]
    count = jnp.sum(cnt)
    mask = mask2.reshape(capp)[:cap] != 0
    if not want_ids:
        return None, None, mask, count

    # tile offsets: exclusive prefix-sum over per-tile counts (nblk-sized)
    offs = (jnp.cumsum(cnt) - cnt).astype(jnp.int32)
    limitp = -(-limit // LANES) * LANES
    useful = (cnt > 0) & (offs < limitp)
    tidx = jax.lax.cummax(
        jnp.where(useful, jnp.arange(nblk, dtype=jnp.int32), 0))
    acc = pl.pallas_call(
        functools.partial(_compact_kernel, block=block, rows=rows,
                          limitp=limitp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nblk,),
            in_specs=[pl.BlockSpec((rows, LANES),
                                   lambda i, o, c, t: (t[i], 0))],
            out_specs=pl.BlockSpec((limitp, LANES),
                                   lambda i, o, c, t: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((limitp, LANES), jnp.int32),
        interpret=interpret,
        name="relscan_compact",
    )(offs, cnt, tidx, mask2)

    ids = jnp.sum(acc, axis=1)[:limit]
    present = jnp.arange(limit, dtype=jnp.int32) < count
    return ids, present, mask, count


def compact(mask, *, limit: int):
    """Bitmap -> first ``limit`` row ids (row order, 0-padded) + presence.

    The jnp twin of the kernel's pass 2, also the compaction of every
    non-fused scan. LIMIT 1 is a single argmax; otherwise one running
    count over the bitmap and ``limit`` binary searches find the row
    where the count first reaches j + 1 — O(capacity) memory, so it
    stays small under vmap (a one-hot ``capacity x limit`` operand does
    not: 4 GiB at 2^22 rows x 256)."""
    n = jnp.sum(mask.astype(jnp.int32))
    if limit == 1:
        ids = jnp.argmax(mask).astype(jnp.int32)[None]
        present = jnp.arange(1, dtype=jnp.int32) < n
        return jnp.where(present, ids, 0), present
    cum = jnp.cumsum(mask.astype(jnp.int32))
    jj = jnp.arange(limit, dtype=jnp.int32)
    ids = jnp.searchsorted(cum, jj + 1).astype(jnp.int32)
    present = jj < n
    return jnp.where(present, ids, 0), present
