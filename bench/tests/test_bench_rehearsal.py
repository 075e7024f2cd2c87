"""CPU rehearsal of both mixes at CAPACITY 4096: the in-process server,
the JAX-free generator child, and the comparison with the reference.
The daemon's answers must come out correct; the control, a reference
that leaves out one acknowledged write, must not: once where the write's
own connection reads the key next, once where another connection does."""
from __future__ import annotations

import pytest

from bench import check as CHK
from bench import harness
from bench.tests._small import deadline, drive, small_env  # noqa: F401


def _check(d, **kw):
    return CHK.check(d["stmts"], d["recs"], harness.reference(d),
                     route_col=harness._route_col(d["mix"]), **kw)


@pytest.mark.parametrize("workload", ["cms.page_reads", "cms.user_activity"])
def test_mix_correct_and_controls_fail(small_env, workload):
    with deadline(240):
        d = drive(workload, seed=2**31 + 7, seconds=3.0,
                  rate={"rate_per_s": 200})
    assert len(d["stmts"]) == 700      # 200/s over 0.5 s + 3 s
    assert all(r["r"] is not None for r in d["recs"].values())
    sched = d["totals"]["scheduler"]           # every checked statement
    assert sched["singles"] > 4    # past the 4 SHOW statements: single path
    assert sched["grouped_statements"] > 0     # the grouped, vmapped path
    res = _check(d)
    assert res["compared"] == len(d["stmts"])
    assert res["foreign_compared"] > 20
    assert (res["wrong"], res["unanswered"]) == (0, 0), res["examples"]

    kinds = {s["id"]: s["kind"] for s in d["stmts"]}
    for foreign in (False, True):
        write, read = CHK.dropped_write(d["stmts"], d["recs"], foreign=foreign)
        assert kinds[write] in CHK.WRITES
        st = {s["id"]: s for s in d["stmts"]}[read]
        assert (st["conn"] != st["owner"]) is foreign
        res = _check(d, skip=frozenset([write]))
        assert res["wrong"] > 0 and read in res["wrong_ids"]
