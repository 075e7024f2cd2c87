"""Jit'd dispatch for the Pallas kernels: on TPU the compiled kernels run
natively; everywhere else they run interpret=True (correctness) or fall
back to the pure-jnp oracle (speed) — selectable per call site.

The model/serving layers call through here so a single switch flips the
whole system between reference and kernel paths.

The cache-daemon executors call through here too, and since PR 7 they
may be traced UNDER ``shard_map`` (core/shards.py fan-out on a lane
mesh): every op in this module — including ``shard_split``, which the
sharded INSERT path runs on the assembled global batch — must therefore
stay shard-local (no implicit collectives; reductions over the lane
axis happen in the merge AFTER the mapped body returns). The jnp
fallbacks and interpret-mode Pallas calls both satisfy this.
"""
from __future__ import annotations

import functools
import os

import jax

from repro.kernels import hashidx as _hashidx
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.relscan import relscan as _relscan
from repro.kernels.mamba_scan import mamba2_scan as _mamba2


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_mode() -> str:
    """kernel | interpret | ref (env REPRO_KERNELS overrides)."""
    env = os.environ.get("REPRO_KERNELS")
    if env in ("kernel", "interpret", "ref"):
        return env
    return "kernel" if on_tpu() else "ref"


def flash_attention(q, k, v, **kw):
    mode = kernel_mode()
    if mode == "ref":
        kw.pop("block_q", None)
        kw.pop("block_kv", None)
        return ref.flash_attention_ref(q, k, v, **kw)
    return _flash(q, k, v, interpret=(mode == "interpret"), **kw)


def paged_attention(q, arena, pages, lengths, **kw):
    mode = kernel_mode()
    if mode == "ref":
        return ref.paged_attention_ref(q, arena, pages, lengths, **kw)
    return _paged(q, arena, pages, lengths,
                  interpret=(mode == "interpret"), **kw)


def predicate_scan(cols, valid, vals, *, ops, limit, want_ids=True,
                   mode=None, **kw):
    """Fused WHERE scan + compaction for a conjunction of up to 4
    equality/range terms over integer columns (the relscan hot path).

    cols: per-term [cap] int32 column arrays; ops: static comparison codes;
    vals: [nterms] runtime values. Returns (ids, present, mask, count) —
    see kernels/relscan.relscan for the full contract. ``mode`` overrides
    the REPRO_KERNELS selection (the vmapped micro-batch executor pins
    ``ref``: a [batch, cap] broadcast compare IS the fused form there)."""
    mode = mode or kernel_mode()
    if mode == "ref":
        return ref.relscan_ref(cols, valid, vals, ops=ops, limit=limit,
                               want_ids=want_ids)
    return _relscan(tuple(cols), valid, vals, ops=ops, limit=limit,
                    interpret=(mode == "interpret"), want_ids=want_ids, **kw)


def hash_build(keys, valid, *, n_buckets):
    """Bulk (re)build of a bucketed hash index over one int32 key column.
    Returns (rid [nb, cap_b], key [nb, cap_b], overflow scalar) — see
    kernels/hashidx. One implementation on every backend: an XLA sort
    plus gathers (a TPU kernel has nothing to add — the work is a random
    gather, which Mosaic can only issue as one DMA per row)."""
    return _hashidx.build(keys, valid, n_buckets=n_buckets)


def hash_probe(rid, key, qkeys, *, mode=None):
    """Batched hash-index probe: one bucket tile per query key. Returns
    (cand [w, cap_b] row ids, hit [w, cap_b]) — see kernels/hashidx.
    ``mode`` overrides REPRO_KERNELS (the vmapped micro-batch executor
    pins ``ref``: batched gathers ARE the fused form there)."""
    mode = mode or kernel_mode()
    if mode == "ref":
        return _hashidx.probe_ref(rid, key, qkeys)
    return _hashidx.probe(rid, key, qkeys,
                          interpret=(mode == "interpret"))


def shard_split(shard_ids, n_shards: int, row_mask=None):
    """Device-side partition split: one XLA sort routes a [b]-row batch
    to its shards (the same sort+searchsorted machinery as hashidx's
    bulk bucketing, reused at shard granularity). Two callers: the
    sharded-table INSERT path (split a statement batch by the partition
    hash) and ``ALTER TABLE ... RESHARD n`` (``core/shards.reshard``:
    re-split EVERY live row of the flattened old shard stack into the
    new shard layout in one pass).

    shard_ids: [b] int32 target shard per row; row_mask: [b] bool (None =
    all rows live). Returns (rows [n_shards, b], mask [n_shards, b]):
    ``rows[s]`` are original batch indices (clipped), ``mask[s]`` marks
    which of them really belong to shard ``s`` — the per-shard executors
    consume them as a masked fixed-width batch, so ONE dispatch feeds all
    shards. Pure jnp by design: the sort/gather shapes are ones XLA
    already lowers well on every backend."""
    import jax.numpy as jnp

    b = shard_ids.shape[0]
    sid = shard_ids.astype(jnp.int32)
    if row_mask is not None:
        sid = jnp.where(row_mask, sid, n_shards)  # masked rows -> sentinel
    order = jnp.argsort(sid).astype(jnp.int32)    # stable: keeps row order
    ssid = sid[order]
    start = jnp.searchsorted(
        ssid, jnp.arange(n_shards, dtype=jnp.int32)).astype(jnp.int32)
    pos = start[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
    posc = jnp.clip(pos, 0, b - 1)
    rows = order[posc]
    mask = (ssid[posc] == jnp.arange(n_shards, dtype=jnp.int32)[:, None]) \
        & (pos < b)
    return rows, mask


def mamba2_scan(x, dt, dA, B, C, **kw):
    mode = kernel_mode()
    if mode == "ref":
        import jax.numpy as jnp
        b, s, nh, dh = x.shape
        h0 = jnp.zeros((b, nh, dh, B.shape[-1]), jnp.float32)
        return ref.mamba2_scan_ref(x.astype(jnp.float32), dt, dA, B, C, h0)
    return _mamba2(x, dt, dA, B, C, interpret=(mode == "interpret"), **kw)
