"""One general generator: table rows from a configuration, statement
schedules from a traffic mix. Everything is drawn from ``--seed``.

A mix file (``bench/mixes/<name>.json``) is data only:

- ``connections``: web-tier connections. Every statement names a key
  (``route``), and every write goes to the connection that owns the key
  (key modulo connections, keys being a seeded permutation of
  popularity ranks): sticky routing, as a load balancer pins a page or a
  session. A key's writes are then ordered by one connection, which is
  what lets the checker know the order the daemon had to follow.
- ``foreign_reads``: the share of reads (select, count) sent from a
  connection other than the key's owner, as a web tier's other workers
  read what one worker wrote.
- ``draws``: named random variables. ``zipf`` over a key space of the
  configuration (``of``: ``pages`` or ``users``) with exponent ``s``;
  ``uniform_key``; ``fragment`` (a new fragment text); ``recent`` (a key
  that an earlier DELETE removed, among the last ``depth``; else the
  ``fallback`` draw).
- ``statements``: each with a ``share``, a ``kind`` (select, count,
  delete, update, insert), the ``sql`` (``{table}`` is filled in), the
  draws it ``bind``s in order, the ``where`` terms as
  ``[column, op, bind index]``, ``cols`` (select), ``set`` (update:
  ``[column, bind index]``) and ``route`` (the bind index of the key).

Every seed gives the same work: the arrival times, the order of the
statement kinds and which reads are foreign come from a fixed stream
(``SHAPE``), and the seed draws the keys and values. The number of each
kind is exact (share x count, rounded). Where a stall lands among the
arrivals sets the tail, so arrivals drawn from the seed made p99 move by
a third from seed to seed while two runs of one seed agreed to 1%.
"""
from __future__ import annotations

import numpy as np

SHAPE = 20091001        # the seed of the arrival times and kind order
READS = ("select", "count")


def fragment(fid: int, nbytes: int) -> str:
    """The text of fragment ``fid``: its 16 hex digits, repeated to
    ``nbytes`` characters. Distinct ids give distinct texts, and the id
    reads back from the first 16 characters."""
    h = f"{fid:016x}"
    return (h * (nbytes // 16 + 1))[:nbytes]


def fragment_id(text: str) -> int:
    return int(text[:16], 16)


def make_rows(cfg: dict, seed: int) -> dict:
    """The configuration's rows: page and user uniform over their key
    spaces, and a fragment id each (the text is ``fragment(id)``)."""
    n = cfg["rows"]
    rng = np.random.default_rng([seed, 0])
    return {
        "page_id": rng.integers(0, cfg["pages"], n, dtype=np.int32),
        "user_id": rng.integers(0, cfg["users"], n, dtype=np.int32),
        "data": rng.integers(0, 1 << 62, n, dtype=np.int64),
    }


class _Zipf:
    """Zipf(s) over ranks 1..n mapped to keys by a seeded permutation
    (YCSB's scrambled Zipfian: hot keys are spread over the key space)."""

    def __init__(self, rng, n: int, s: float):
        p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
        self.cdf = np.cumsum(p / p.sum())
        self.keys = rng.permutation(n).astype(np.int64)

    def draw(self, rng, k: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(k), side="right")
        return self.keys[np.minimum(r, len(self.keys) - 1)]


def schedule(cfg: dict, mix: dict, seed: int, table: str, *, rate: float,
             seconds: float, start: float = 0.0, stream: int = 0,
             first_id: int = 0) -> list[dict]:
    """``round(rate x seconds)`` statements due at sorted uniform times in
    [start, start + seconds) (a Poisson stream given its count), each a
    dict with id, conn, due, sql, params, kind, key and what the checker
    needs (terms, cols, set). ``stream`` separates the warm-up traffic
    from the window's."""
    d = cfg
    shape = np.random.default_rng([SHAPE, stream])
    rng = np.random.default_rng([seed, 1, stream])
    n = int(round(rate * seconds))
    specs = mix["statements"]
    counts = _exact_counts([s["share"] for s in specs], n)
    order = shape.permutation(np.repeat(np.arange(len(specs)), counts))
    due = start + np.sort(shape.random(n)) * seconds
    n_conn = mix["connections"]
    foreign = shape.random(n) < mix.get("foreign_reads", 0.0)
    shift = 1 + shape.integers(0, max(1, n_conn - 1), n)
    zipfs = {}
    for name, dr in mix["draws"].items():
        if "zipf" in dr:
            zipfs[name] = _Zipf(np.random.default_rng([seed, 2]),
                                d[dr["of"]], dr["zipf"])
    recent: dict[str, list] = {}
    out = []

    def draw(name: str):
        dr = mix["draws"][name]
        if "zipf" in dr:
            return int(zipfs[name].draw(rng, 1)[0])
        if "uniform_key" in dr:
            return int(rng.integers(0, d[dr["uniform_key"]]))
        if "fragment" in dr:
            return fragment(int(rng.integers(0, 1 << 62)),
                            d["fragment_bytes"])
        if "recent" in dr:
            pool = recent.get(dr["recent"], [])[-dr["depth"]:]
            if pool:
                return pool[int(rng.integers(len(pool)))]
            return draw(dr["fallback"])
        raise ValueError(f"unknown draw {name!r}: {dr}")

    for i, q in enumerate(order):
        sp = specs[q]
        params = [draw(b) for b in sp["bind"]]
        key = params[sp["route"]]
        owner = key % n_conn
        st = {"id": first_id + i, "due": float(due[i]), "kind": sp["kind"],
              "sql": sp["sql"].format(table=table), "params": params,
              "terms": [(c, op, params[j]) for c, op, j in sp.get("where", [])],
              "key": key, "owner": owner,
              "conn": (int(owner + shift[i]) % n_conn
                       if sp["kind"] in READS and foreign[i] else owner)}
        if sp["kind"] == "select":
            st["cols"] = (tuple(d["columns"]) if sp["cols"] == "*"
                          else tuple(sp["cols"]))
        if sp["kind"] == "update":
            st["set"] = (sp["set"][0], params[sp["set"][1]])
        if sp["kind"] == "delete":
            recent.setdefault("delete", []).append(key)
        out.append(st)
    return out


def _exact_counts(shares: list[float], n: int) -> np.ndarray:
    """Largest-remainder rounding: counts that sum to ``n``."""
    raw = np.asarray(shares, np.float64) / sum(shares) * n
    counts = np.floor(raw).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(counts - raw)[:rest]] += 1
    return counts
