"""The comparison that decides ``correct``.

Every statement the generator sent, warm-up traffic and window alike, is
replayed through the plain reference (``bench/reference.py``) in the
order the generator wrote them, and every answer is compared with the
reference's.

Every write goes to the connection that owns its key, and one
connection's statements take effect in the order it sent them. So a
key's writes have one known order, and a statement on the owner's
connection has one exact answer.

A foreign read (a read of a key sent from another connection) is
ordered against the owner's writes only by the clock. The generator
numbers every send and every answer on one counter, so the read must
see every write of its key whose answer came back before the read was
sent, and none sent after the read's answer came back; the writes in
between form a prefix, in the owner's order, of which any may be seen.
The reference keeps the key's rows as they were before each write, and
the read must agree exactly with the state after one admissible prefix.

The numbers compared, each against its limit:

    wrong      answers that differ from the reference, and errors (limit 0)
    unanswered statements with no answer a minute past the window (limit 0)
"""
from __future__ import annotations

import collections
import json

import numpy as np

from bench.reference import Reference, mismatch

WRITES = ("delete", "update", "insert")


def parse_answer(rec: dict) -> dict:
    """A generator record as the reference's answer format."""
    return {"count": rec.get("c", 0), "value": rec.get("v"),
            "rows": [json.loads(r) for r in rec.get("rows") or []],
            "error": rec.get("e")}


def check(stmts: list, recs: dict, ref: Reference, *, route_col: str,
          skip: frozenset = frozenset()) -> dict:
    """Compare every answered statement with ``ref`` (mutated). ``recs``
    maps statement id to its generator record; statements in ``skip``
    are left out of the reference, as if never acknowledged. Returns the
    numbers compared, the ids that differed and a few examples of why."""
    limit = ref.max_select
    sent = sorted((s for s in stmts if recs[s["id"]]["ss"] is not None),
                  key=lambda s: recs[s["id"]]["ss"])
    wrong_ids, why = [], []
    unanswered = sum(1 for s in stmts if recs[s["id"]]["rs"] is None)
    # per key: [(ss, rs, rows of the key before the write)], owner's order
    history: dict[int, list] = collections.defaultdict(list)
    pending: list = []            # foreign reads, by answer number
    foreign = 0

    def fail(st, msg):
        wrong_ids.append(st["id"])
        if len(why) < 5:
            why.append(f"{st['sql']} {[str(p)[:24] for p in st['params']]}:"
                       f" {msg}")

    def evaluate(st):
        """A foreign read, once every write sent before its answer came
        back has been applied to the reference."""
        r = recs[st["id"]]
        hist = history[st["key"]]
        low = sum(1 for h in hist if h[1] is not None and h[1] < r["ss"])
        got = parse_answer(r)
        msgs = []
        for j in range(low, len(hist) + 1):
            state = (hist[j][2] if j < len(hist)
                     else ref.key_state(route_col, st["key"]))
            msg = mismatch(st, got, ref.read(st, state), limit)
            if msg is None:
                return
            msgs.append(msg)
        fail(st, f"no admissible state of the key agrees ({msgs[-1]}; "
                 f"{len(hist) + 1 - low} states)")

    for st in sent:
        r = recs[st["id"]]
        while pending and recs[pending[0]["id"]]["rs"] < r["ss"]:
            evaluate(pending.pop(0))
        if st["id"] in skip:
            continue
        if r["rs"] is not None and r.get("e") is not None:
            fail(st, f"error {r['e']}")
            if st["kind"] not in WRITES:
                continue
        if st["conn"] != st["owner"]:
            if r["rs"] is not None:
                foreign += 1
                pending.append(st)
                pending.sort(key=lambda s: recs[s["id"]]["rs"])
            continue
        if st["kind"] in WRITES:
            history[st["key"]].append(
                (r["ss"], r["rs"], ref.key_state(route_col, st["key"])))
        want = ref.apply(st)
        if r["rs"] is None or r.get("e") is not None:
            continue
        msg = mismatch(st, parse_answer(r), want, limit)
        if msg is not None:
            fail(st, msg)
    for st in pending:
        evaluate(st)
    return {"wrong": len(wrong_ids), "unanswered": unanswered,
            "compared": len(sent) - unanswered, "foreign_compared": foreign,
            "wrong_ids": wrong_ids, "examples": why}


def dropped_write(stmts: list, recs: dict, *, foreign: bool = False
                  ) -> tuple[int, int] | None:
    """The control of a broken guarantee: an acknowledged write whose
    effect a later read of its key must show, as (write id, read id).
    The read is sent after the write's answer and answered before the
    key's next write is sent, so it sees the write and nothing after it
    (a DELETE where the mix has one); with ``foreign``, a read from
    another connection. Leaving the write out of the reference must
    make the run not correct."""
    sent = sorted((s for s in stmts if recs[s["id"]]["rs"] is not None),
                  key=lambda s: recs[s["id"]]["ss"])
    for kinds in (("delete",), WRITES):
        for i, w in enumerate(sent):
            if w["kind"] not in kinds or (recs[w["id"]].get("c") or 0) < 1:
                continue
            later = [s for s in sent[i + 1:] if s["key"] == w["key"]]
            nxt = next((recs[s["id"]]["ss"] for s in later
                        if s["kind"] in WRITES), None)
            for s in later:
                r = recs[s["id"]]
                if nxt is not None and r["ss"] > nxt:
                    break
                shows = w["kind"] != "update" or w["set"][0] in s.get(
                    "cols", ())
                if (shows and s["kind"] not in WRITES
                        and (s["conn"] != s["owner"]) == foreign
                        and r["ss"] > recs[w["id"]]["rs"]
                        and (nxt is None or r["rs"] < nxt)):
                    return w["id"], s["id"]
    return None


def mean_rows(stmts: list, recs: dict) -> float:
    """Mean rows returned per SELECT (for the log)."""
    n = [len(recs[s["id"]].get("rows") or []) for s in stmts
         if s["kind"] == "select" and recs[s["id"]]["rs"] is not None]
    return float(np.mean(n)) if n else 0.0
