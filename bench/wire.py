"""The tagged SQLcached wire dialect, standard library only.

A copy of what the load generator needs from ``repro.core.protocol``:
that module imports the daemon and JAX, and the generator runs in a
process that must never load either (a web tier is a separate process,
and sharing the server's interpreter would bias every timing).

    client:  EXEC#<tag> <sql>  /  ARG#<tag> I|F|S <v>  /  GO#<tag>
    server:  COUNT#<tag> <n>  [VALUE#<tag> <json>]  ROW#<tag> <json>...
             END#<tag>        or  ERR#<tag> <message>

Responses come back in submission order on each connection.
"""
from __future__ import annotations

import base64
import json
import socket
from typing import Any, Sequence


def encode_arg(v: Any) -> str:
    if isinstance(v, bool):
        return f"I {int(v)}"
    if isinstance(v, int):
        return f"I {v}"
    if isinstance(v, float):
        return f"F {v!r}"
    if isinstance(v, str):
        return "S " + base64.b64encode(v.encode()).decode()
    raise TypeError(f"unsupported arg type {type(v)!r}")


def frame(tag: str | None, sql: str, params: Sequence[Any] = ()) -> bytes:
    """One statement's EXEC/ARG.../GO lines (untagged when ``tag`` is
    None)."""
    sfx = "" if tag is None else f"#{tag}"
    out = [f"EXEC{sfx} {sql}"]
    out += [f"ARG{sfx} {encode_arg(p)}" for p in params]
    out.append(f"GO{sfx}")
    return ("\r\n".join(out) + "\r\n").encode()


def new_result() -> dict:
    return {"count": 0, "value": None, "rows": [], "error": None}


def feed_line(acc: dict, line: str,
              parse_rows: bool = True) -> tuple[str | None, bool]:
    """Fold one response line into ``acc`` (tag -> partial result).
    Returns (tag, done): ``done`` when the line ended that tag's
    response (END or ERR). Unknown verbs raise: a desynced stream must
    never pass for an empty answer. ``parse_rows=False`` keeps each ROW
    as its JSON text, for a caller that decodes later."""
    verb, _, rest = line.partition(" ")
    verb, _, tag = verb.partition("#")
    tag = tag or None
    if verb not in ("COUNT", "VALUE", "ROW", "END", "ERR"):
        raise RuntimeError(f"protocol desync: unexpected {line!r}")
    res = acc.setdefault(tag, new_result())
    if verb == "COUNT":
        res["count"] = int(rest)
    elif verb == "VALUE":
        try:
            res["value"] = json.loads(rest)
        except json.JSONDecodeError:
            res["value"] = rest
    elif verb == "ROW":
        res["rows"].append(json.loads(rest) if parse_rows else rest)
    elif verb == "ERR":
        res["error"] = rest
        return tag, True
    return tag, verb == "END"


class Client:
    """Blocking one-statement-at-a-time client (untagged dialect)."""

    def __init__(self, host: str, port: int, timeout: float = 600.0):
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._buf = b""

    def _readline(self) -> str:
        while b"\n" not in self._buf:
            chunk = self._sock.recv(1 << 16)
            if not chunk:
                raise ConnectionError("server closed the connection")
            self._buf += chunk
        line, _, self._buf = self._buf.partition(b"\n")
        return line.decode().rstrip("\r")

    def execute(self, sql: str, params: Sequence[Any] = ()) -> dict:
        """The statement's result; a server error raises RuntimeError."""
        self._sock.sendall(frame(None, sql, params))
        acc: dict = {}
        while True:
            tag, done = feed_line(acc, self._readline())
            if tag is not None:
                raise RuntimeError(f"protocol desync: tag {tag!r}")
            if done:
                res = acc[None]
                if res["error"] is not None:
                    raise RuntimeError(f"server error: {res['error']}")
                return res

    def close(self) -> None:
        try:
            self._sock.sendall(b"QUIT\r\n")
        except OSError:
            pass
        self._sock.close()

    def __enter__(self) -> "Client":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
