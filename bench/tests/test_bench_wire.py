"""The generator's copy of the wire protocol answers exactly as the
program's own client does, on the same statements: one statement at a
time (``wire.Client`` against ``SQLCachedClient``) and pipelined over
tagged connections (``loadgen.drive``)."""
from __future__ import annotations

import json
import socket
import time

from bench import harness, loadgen, traffic, wire
from bench.tests._small import SMALL, deadline, small_env  # noqa: F401

TEXT = traffic.fragment(12345, 1024)
STATEMENTS = [
    ("SELECT * FROM {t} WHERE page_id = ?", [3]),
    ("SELECT page_id FROM {t} WHERE user_id = ?", [4]),
    ("SELECT COUNT(*) FROM {t} WHERE user_id = ?", [4]),
    ("UPDATE {t} SET data = ? WHERE page_id = ?", [TEXT, 3]),
    ("INSERT INTO {t} (page_id, user_id, data) VALUES (?, ?, ?)",
     [3, 4, TEXT]),
    ("SELECT * FROM {t} WHERE page_id = ?", [3]),
    ("DELETE FROM {t} WHERE user_id = ?", [4]),
    ("SELECT COUNT(*) FROM {t} WHERE user_id = ?", [4]),
    ("SELECT * FROM {t} WHERE page_id = ?", [-5]),
]


def _norm(res: dict) -> tuple:
    rows = sorted(tuple(sorted(r.items())) for r in res["rows"])
    return res["count"], res["value"], rows


def test_wire_copy_matches_program_client(small_env):
    from repro.core.daemon import SQLCached
    from repro.core.protocol import SQLCachedClient, ThreadedServer
    cfg = {**harness.load_json("configs", "cms_fragments"), **SMALL}
    rows = traffic.make_rows(cfg, 3)
    db = SQLCached()
    for t in ("a", "b", "c"):          # three identical tables
        harness.load_table(db, cfg, rows, t)
    with deadline(120), ThreadedServer(db=db) as srv:
        prog = SQLCachedClient(*srv.addr, timeout=60)
        ours = wire.Client(*srv.addr, timeout=60)
        want = []
        try:
            for sql, params in STATEMENTS:
                want.append(_norm(prog.execute(sql.format(t="a"), params)))
                got = ours.execute(sql.format(t="b"), params)
                assert _norm(got) == want[-1], sql
        finally:
            prog.close()
            ours.close()
        # the same statements pipelined on one tagged connection
        sqls = [sql.format(t="c") for sql, _ in STATEMENTS]
        sched = [[i, 0, 0.0, i, p] for i, (_, p) in enumerate(STATEMENTS)]
        recs = loadgen.drive([socket.create_connection(srv.addr)], sqls,
                             sched, time.monotonic())
    assert [r["e"] for r in recs] == [None] * len(STATEMENTS)
    assert all(r["ss"] < r["rs"] for r in recs)
    got = [_norm({"count": r["c"], "value": r["v"],
                  "rows": [json.loads(x) for x in r["rows"]]}) for r in recs]
    assert got == want
    assert want[2][1] > 0 and want[7][1] == 0   # the DELETE took effect
    assert TEXT in json.dumps(want[5])          # the UPDATE and the INSERT
