"""kernels/hashidx parity and invariants: the Pallas probe kernel
(interpret mode) against the jnp reference, the bulk build's
unique-entry invariant, plus the incremental insert maintenance
contract (stale marking)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import predicate as P
from repro.core import table as T
from repro.core.schema import make_schema
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


def _parity_case(case, cap=300):
    """One INSERT batch against an index: (valid before the insert, the
    index as the insert finds it, keys before it, slots, new keys, row
    mask). An int is a random batch from that seed; a name is a corner
    of the clear step."""
    nb = H.n_buckets_for(cap)
    if isinstance(case, int):
        rng, keys, valid = _mk(cap, case)
        n = 48  # a mid-size batch: > trivial, < BULK_INDEX_THRESHOLD region
        slots = rng.choice(cap, n, replace=False)
        newk = rng.integers(-50, 50, n)
        mask = rng.random(n) < 0.9
        idx_valid = valid
    elif case == "shared_old_bucket":
        # every row of the fullest bucket is re-inserted, with others
        rng, keys, valid = _mk(cap, 11)
        ob = np.asarray(H.bucket_of(keys, nb))
        rows = np.flatnonzero(ob == np.bincount(ob).argmax())
        assert 2 <= len(rows) <= 40
        others = rng.choice(np.setdiff1d(np.arange(cap), rows), 8,
                            replace=False)
        slots = rng.permutation(np.concatenate([rows, others]))
        newk = rng.integers(-50, 50, len(slots))
        mask = np.arange(len(slots)) != 3
        idx_valid = valid
    elif case == "shared_new_bucket":
        # most members move into the bucket of one key
        rng, keys, valid = _mk(cap, 12)
        slots = rng.choice(cap, 40, replace=False)
        newk = np.where(rng.random(40) < 0.75, 7, rng.integers(-50, 50, 40))
        mask = rng.random(40) < 0.9
        idx_valid = valid
    elif case == "overflow_victim":
        # the first 200 rows share one key, so 72+ of them were left out
        # of the full bucket and have no entry to clear. The new keys
        # leave that bucket: a batch that frees lanes in a full bucket
        # AND places into it may index other rows than the loop (see
        # test_insert_update_batched_refills_lanes_freed_in_a_full_bucket)
        rng, keys, valid = _mk(cap, 13, p_valid=1.0)
        keys = keys.at[:200].set(3)
        slots = rng.choice(200, 30, replace=False)
        ks = np.arange(-50, 50)
        kb = np.asarray(H.bucket_of(jnp.asarray(ks, jnp.int32), nb))
        newk = rng.choice(ks[kb != kb[ks == 3]], 30)
        mask = np.ones(30, dtype=bool)
        idx_valid = valid
    elif case == "deleted_slot":
        # rows DELETEd after the build keep their key and their entry;
        # the INSERT reuses them
        rng, keys, idx_valid = _mk(cap, 14)
        dead = rng.choice(np.flatnonzero(np.asarray(idx_valid)), 30,
                          replace=False)
        valid = idx_valid.at[dead].set(False)
        free = np.flatnonzero(~np.asarray(valid))
        slots = np.concatenate([dead[:20], rng.choice(
            np.setdiff1d(free, dead), 10, replace=False)])
        newk = rng.integers(-50, 50, 30)
        mask = np.ones(30, dtype=bool)
    else:
        raise ValueError(case)
    r, k, o = H.build(keys, idx_valid, n_buckets=nb)
    if case == "overflow_victim":  # both kinds of member are there
        assert 0 < np.isin(slots, np.asarray(r)).sum() < len(slots)
    idx = {"rid": r, "key": k, "stale": o}
    return (valid, idx, keys, jnp.asarray(slots, jnp.int32),
            jnp.asarray(newk, jnp.int32), jnp.asarray(mask))


@pytest.mark.parametrize("case", [0, 1, 2, 3, "shared_old_bucket",
                                  "shared_new_bucket", "overflow_victim",
                                  "deleted_slot"])
def test_insert_update_batched_matches_loop(case):
    """The batched clear + rank-place pass (table.insert's
    below-BULK_INDEX_THRESHOLD path) must agree with the sequential
    per-slot loop on per-bucket membership and the stale count."""
    valid, idx, keys, slots, newk, mask = _parity_case(case)
    cap = keys.shape[0]
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


def test_insert_update_batched_refills_lanes_freed_in_a_full_bucket():
    """Where the loop and the batched pass part: members that move into
    a full bucket ahead of members whose clears free lanes there. The
    loop finds the bucket full and counts them stale; the batched pass
    clears the whole batch first and gives them the freed lanes. Both
    indexes stay sound (no slot twice, every live entry in its key's
    bucket); the batched one holds more rows and counts fewer stale."""
    cap = 512
    keys = jnp.full((cap,), 3, jnp.int32)  # every row in ONE bucket
    valid = jnp.ones((cap,), dtype=bool)
    nb = H.n_buckets_for(cap)
    r, k, o = H.build(keys, valid, n_buckets=nb)
    idx = {"rid": r, "key": k, "stale": o}
    slots = jnp.asarray([400, 510, 0, 5], jnp.int32)  # victims, then held
    newk = jnp.asarray([3, 3, 9, 9], jnp.int32)
    assert int(H.bucket_of(jnp.int32(9), nb)) != int(
        H.bucket_of(jnp.int32(3), nb))
    mask = jnp.ones((4,), dtype=bool)
    keys2 = keys.at[slots].set(newk)
    seq = H.insert_update(idx, slots, keys[slots], newk, mask, valid)
    bat = H.insert_update_batched(idx, slots, keys[slots], newk, mask,
                                  valid)
    assert int(seq["stale"]) == int(o) + 2
    assert int(bat["stale"]) == int(o)
    buckets = np.asarray(H.bucket_of(keys2, nb))
    for out in (seq, bat):
        rid = np.asarray(out["rid"])
        held = rid[rid >= 0]
        assert len(held) == len(set(held.tolist()))
        for b, row in enumerate(rid):
            assert all(buckets[x] == b for x in row if x >= 0)
    assert {400, 510} <= set(np.asarray(bat["rid"]).ravel().tolist())


def test_insert_sequence_keeps_index_equal_to_build():
    """Through ``table.insert``'s narrow-batch path at a capacity where
    the index holds 4 x capacity lanes: INSERT, DELETE, re-INSERT into
    the freed slots, INSERT with LRU eviction at capacity, an UPDATE of
    the indexed column, and an INSERT after it. After each step the
    index holds the same live rows per bucket as a build over the
    columns, and no slot appears in two lanes."""
    cap = 256
    nb = H.n_buckets_for(cap)
    assert nb * H.BUCKET_CAP == 4 * cap
    sch = make_schema("t", [("k", "INT"), ("u", "INT")], capacity=cap,
                      indexes=("k",))
    rng = np.random.default_rng(21)
    n = 40
    ins = jax.jit(lambda st, k, u: T.insert(sch, st, {"k": k, "u": u}))

    def insert(st):
        k = jnp.asarray(rng.integers(0, 24, n), jnp.int32)
        u = jnp.asarray(rng.integers(0, 8, n), jnp.int32)
        return ins(st, k, u)

    def check(st):
        idx = st["indexes"]["k"]
        want, _, overflow = H.build(st["cols"]["k"], st["valid"],
                                    n_buckets=nb)
        assert int(overflow) == 0 and int(idx["stale"]) == 0
        assert _bucket_sets(idx["rid"], st["valid"]) == _bucket_sets(
            want, st["valid"])
        held = np.asarray(idx["rid"])
        held = held[held >= 0]
        assert len(held) == len(set(held.tolist()))

    st = T.init_state(sch)
    for _ in range(5):                       # 200 of 256 slots
        st, _, evicted = insert(st)
        assert int(evicted) == 0
        check(st)
    st, n_del = T.delete(sch, st, P.BinOp("=", P.Col("u"), P.Const(3)))
    assert int(n_del) > 0
    check(st)
    dead = set(np.flatnonzero(~np.asarray(st["valid"][:200])).tolist())
    st, slots, evicted = insert(st)          # reuses the deleted slots
    assert int(evicted) == 0 and dead & set(np.asarray(slots).tolist())
    check(st)
    for _ in range(3):                       # fills up, then evicts
        st, _, evicted = insert(st)
        check(st)
    assert int(evicted) == n
    st, n_upd = T.update(sch, st, P.BinOp("=", P.Col("u"), P.Const(5)),
                         {"k": P.BinOp("+", P.Col("k"), P.Const(100))})
    assert int(n_upd) > 0
    check(st)
    st, _, evicted = insert(st)
    assert int(evicted) == n
    check(st)
