"""Open-loop load generator: a web tier's connections to one daemon.

Runs as its own process and never imports JAX or the daemon, so the
server's interpreter does none of the client's work. It reads a whole
schedule on stdin, opens the connections, prints ``READY``, waits for
``START <t0>`` (a ``time.monotonic()`` value, the same clock in every
process of the machine), then writes each statement at ``t0 + due`` on
its connection without waiting for earlier answers (tagged pipelining).
When every statement is answered, or 60 s after the last was due, it
prints one JSON line per statement and exits.

stdin:   {"host":..., "port":..., "connections": n, "sqls": [...]}
         [id, conn, due_s, sql_index, [params...]]   one per statement
         END
         START <t0>
stdout:  READY
         {"i": id, "s": sent - t0, "r": answered - t0 | null,
          "ss": send order, "rs": answer order | null,
          "c": count, "v": value, "rows": [json text...], "e": error}

``ss`` and ``rs`` number the sends and answers on one counter, so the
checker knows exactly which answers came back before which statements
were written.

Run by ``bench/harness.py``; ``python bench/loadgen.py < schedule``.
"""
from __future__ import annotations

import gc
import json
import pathlib
import selectors
import socket
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import wire  # noqa: E402

GRACE_S = 60.0


def connect(header: dict) -> list[socket.socket]:
    socks = []
    for _ in range(header["connections"]):
        s = socket.create_connection((header["host"], header["port"]))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        socks.append(s)
    return socks


def drive(socks: list, sqls: list, stmts: list, t0: float) -> list[dict]:
    sel = selectors.DefaultSelector()
    out_buf = [bytearray() for _ in socks]
    in_buf = [b""] * len(socks)
    partial = [dict() for _ in socks]
    for i, s in enumerate(socks):
        s.setblocking(False)
        sel.register(s, selectors.EVENT_READ, i)
    recs = {}
    seq = 0
    nxt = 0
    outstanding = 0
    last_due = t0 + (stmts[-1][2] if stmts else 0.0)
    writing: set[int] = set()

    def flush(ci: int) -> None:
        buf = out_buf[ci]
        try:
            sent = socks[ci].send(buf)
        except BlockingIOError:
            sent = 0
        del buf[:sent]
        want = bool(buf)
        if want != (ci in writing):
            ev = selectors.EVENT_READ | (selectors.EVENT_WRITE if want else 0)
            sel.modify(socks[ci], ev, ci)
            (writing.add if want else writing.discard)(ci)

    while True:
        now = time.monotonic()
        touched = set()
        while nxt < len(stmts) and t0 + stmts[nxt][2] <= now:
            sid, ci, _due, q, params = stmts[nxt]
            out_buf[ci] += wire.frame(str(sid), sqls[q], params)
            recs[sid] = {"i": sid, "s": now - t0, "r": None, "ss": seq,
                         "rs": None}
            seq += 1
            outstanding += 1
            touched.add(ci)
            nxt += 1
        for ci in touched:
            flush(ci)
        if nxt == len(stmts) and (outstanding == 0
                                  or now > last_due + GRACE_S):
            break
        wait = (t0 + stmts[nxt][2] - now) if nxt < len(stmts) else 0.05
        for key, mask in sel.select(max(0.0, min(wait, 0.05))):
            ci = key.data
            if mask & selectors.EVENT_WRITE:
                flush(ci)
            if not mask & selectors.EVENT_READ:
                continue
            try:
                chunk = socks[ci].recv(1 << 20)
            except BlockingIOError:
                continue
            if not chunk:
                raise ConnectionError(f"connection {ci} closed by server")
            data = in_buf[ci] + chunk
            *lines, in_buf[ci] = data.split(b"\n")
            for raw in lines:
                tag, done = wire.feed_line(
                    partial[ci], raw.decode().rstrip("\r"), parse_rows=False)
                if not done:
                    continue
                res = partial[ci].pop(tag)
                rec = recs[int(tag)]
                rec.update(r=time.monotonic() - t0, rs=seq, c=res["count"],
                           v=res["value"], rows=res["rows"], e=res["error"])
                seq += 1
                outstanding -= 1
    for s in socks:
        s.close()
    return [recs.get(st[0], {"i": st[0], "s": None, "r": None, "ss": None,
                             "rs": None}) for st in stmts]


def main() -> int:
    header = json.loads(sys.stdin.readline())
    stmts = []
    for line in sys.stdin:
        if line.strip() == "END":
            break
        stmts.append(json.loads(line))
    stmts.sort(key=lambda st: st[2])
    # the schedule is tens of thousands of long-lived objects: keep the
    # collector's full passes off them, so that no pass over them makes
    # the generator late
    gc.collect()
    gc.freeze()
    socks = connect(header)
    print("READY", flush=True)
    cmd, _, t0 = sys.stdin.readline().partition(" ")
    if cmd != "START":
        raise SystemExit(f"loadgen: expected START, got {cmd!r}")
    recs = drive(socks, header["sqls"], stmts, float(t0))
    w = sys.stdout.write
    for rec in recs:
        w(json.dumps(rec) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
