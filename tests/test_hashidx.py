"""kernels/hashidx parity and invariants: the Pallas probe kernel
(interpret mode) against the jnp reference, the bulk build's
unique-entry invariant, plus the incremental insert maintenance
contract (stale marking)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import hashidx as H


def _mk(cap, seed, key_lo=-50, key_hi=50, p_valid=0.8):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(key_lo, key_hi, cap), jnp.int32)
    valid = jnp.asarray(rng.random(cap) < p_valid)
    return rng, keys, valid


@pytest.mark.parametrize("cap", [64, 300, 1024])
@pytest.mark.parametrize("seed", [0, 1, 7])
def test_build_complete_and_unique(cap, seed):
    _, keys, valid = _mk(cap, seed)
    nb = H.n_buckets_for(cap)
    rid, _, overflow = H.build(keys, valid, n_buckets=nb)
    assert int(overflow) == 0
    rid = np.asarray(rid)
    buckets = np.asarray(H.bucket_of(keys, nb))
    for row in range(cap):
        locs = np.argwhere(rid == row)
        if bool(valid[row]):
            assert len(locs) == 1 and locs[0][0] == buckets[row]
        else:
            assert len(locs) == 0


def test_probe_kernel_matches_ref():
    rng, keys, valid = _mk(512, 3)
    nb = H.n_buckets_for(512)
    rid, key, _ = H.build(keys, valid, n_buckets=nb)
    q = jnp.asarray(rng.integers(-60, 60, 33), jnp.int32)
    c1, h1 = H.probe_ref(rid, key, q)
    c2, h2 = H.probe(rid, key, q, interpret=True)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    # completeness: every valid row with a probed key is among the hits
    for i, qq in enumerate(np.asarray(q)):
        want = set(np.nonzero(np.asarray(valid)
                              & (np.asarray(keys) == qq))[0])
        got = set(np.asarray(c1[i])[np.asarray(h1[i])])
        assert want <= got


def test_overflow_sets_stale():
    cap = 512
    keys = jnp.full((cap,), 3, jnp.int32)  # all rows in ONE bucket
    valid = jnp.ones((cap,), dtype=bool)
    nb = H.n_buckets_for(cap)
    _, _, overflow = H.build(keys, valid, n_buckets=nb)
    assert int(overflow) == cap - H.BUCKET_CAP


def test_insert_update_matches_rebuild():
    rng, keys, valid = _mk(300, 5)
    nb = H.n_buckets_for(300)
    r, k, o = H.build(keys, valid, n_buckets=nb)
    idx = {"rid": r, "key": k, "stale": o}
    slots = jnp.asarray([0, 5, 299, 17, 42], jnp.int32)
    newk = jnp.asarray([7, -7, 7, 1000, 7], jnp.int32)
    mask = jnp.asarray([True, True, True, True, False])
    keys2 = keys.at[jnp.where(mask, slots, 300)].set(newk, mode="drop")
    valid2 = valid.at[jnp.where(mask, slots, 300)].set(True, mode="drop")
    idx2 = H.insert_update(idx, slots, keys[slots], keys2[slots], mask,
                           valid2)
    assert int(idx2["stale"]) == 0
    want_r, _, _ = H.build(keys2, valid2, n_buckets=nb)
    ra, rb = np.asarray(idx2["rid"]), np.asarray(want_r)
    va = np.asarray(valid2)
    for b in range(nb):  # same live membership per bucket (lane order may
        A = {x for x in ra[b] if x >= 0 and va[x]}       # legally differ)
        B = {x for x in rb[b] if x >= 0 and va[x]}
        assert A == B
    # unique-entry invariant: no slot appears twice anywhere
    live = ra[ra >= 0]
    assert len(live) == len(set(live.tolist()))


def _bucket_sets(rid, valid):
    """Per-bucket LIVE entry sets (lane order is not part of the
    contract — the batched re-home may place members in different lanes
    than the slot-by-slot loop)."""
    rid, valid = np.asarray(rid), np.asarray(valid)
    return [{x for x in row if x >= 0 and valid[x]} for row in rid]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_insert_update_batched_matches_loop(seed):
    """The batched clear + rank-place pass (table.insert's
    below-BULK_INDEX_THRESHOLD path) must agree with the sequential
    per-slot loop on per-bucket membership and the stale count."""
    cap = 300
    rng, keys, valid = _mk(cap, seed)
    nb = H.n_buckets_for(cap)
    r, k, o = H.build(keys, valid, n_buckets=nb)
    idx = {"rid": r, "key": k, "stale": o}
    n = 48  # a mid-size batch: > trivial, < BULK_INDEX_THRESHOLD region
    slots = jnp.asarray(rng.choice(cap, n, replace=False), jnp.int32)
    newk = jnp.asarray(rng.integers(-50, 50, n), jnp.int32)
    mask = jnp.asarray(rng.random(n) < 0.9)
    keys2 = keys.at[jnp.where(mask, slots, cap)].set(newk, mode="drop")
    valid2 = valid.at[jnp.where(mask, slots, cap)].set(True, mode="drop")
    seq = H.insert_update(idx, slots, keys[slots], keys2[slots], mask,
                          valid2)
    bat = H.insert_update_batched(idx, slots, keys[slots], keys2[slots],
                                  mask, valid2)
    assert int(bat["stale"]) == int(seq["stale"])
    assert _bucket_sets(bat["rid"], valid2) == _bucket_sets(
        seq["rid"], valid2)
    live = np.asarray(bat["rid"])
    live = live[live >= 0]
    assert len(live) == len(set(live.tolist()))


def test_insert_update_batched_overflow_stale_matches_loop():
    """Re-homing into an already-overflowing bucket: members whose old
    entry was IN the bucket reuse their freed lane, overflow victims
    fail and count stale — identically in both implementations."""
    cap = 512
    keys = jnp.full((cap,), 3, jnp.int32)  # every row in ONE bucket
    valid = jnp.ones((cap,), dtype=bool)
    nb = H.n_buckets_for(cap)
    r, k, o = H.build(keys, valid, n_buckets=nb)
    assert int(o) == cap - H.BUCKET_CAP
    idx = {"rid": r, "key": k, "stale": o}
    # build fills the bucket with rows 0..BUCKET_CAP-1; mix slots
    # that hold a lane with slots that were overflow victims
    slots = jnp.asarray([0, 5, 100, 200, 400, 510], jnp.int32)
    newk = jnp.full((6,), 3, jnp.int32)    # same full bucket again
    mask = jnp.ones((6,), dtype=bool)
    seq = H.insert_update(idx, slots, keys[slots], newk, mask, valid)
    bat = H.insert_update_batched(idx, slots, keys[slots], newk, mask,
                                  valid)
    # 3 in-bucket members reuse their own freed lanes; 3 victims stay out
    assert int(seq["stale"]) == int(o) + 3
    assert int(bat["stale"]) == int(seq["stale"])
    assert _bucket_sets(bat["rid"], valid) == _bucket_sets(
        seq["rid"], valid)
