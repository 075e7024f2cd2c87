"""The plain reference: a numpy model of the fragment table.

It imports nothing of the program. Rows live in numpy columns with a
validity mask; a fragment's text is kept as its id (``traffic.fragment``
makes the text). Lookups by ``page_id`` or ``user_id`` go through a
sorted index built once from the seeded rows, plus the rows inserted
since. Each statement kind has the semantics the daemon documents:

    select  the matching rows (the first MAX_SELECT of them), and their count
    count   COUNT(*) of the matches
    delete  clears the matches; answers their count
    update  sets one column of the matches; answers their count
    insert  adds one row; answers 1
"""
from __future__ import annotations

import collections

import numpy as np

from bench.traffic import fragment, fragment_id

KEY_COLS = ("page_id", "user_id")
OPS = {"=": np.equal}


class _KeyIndex:
    """Row ids by key: a sorted copy of the seeded column plus a dict of
    rows inserted since (ids may point at rows deleted since)."""

    def __init__(self, col: np.ndarray):
        self.order = np.argsort(col, kind="stable").astype(np.int64)
        keys = col[self.order]
        self.uniq, self.start = np.unique(keys, return_index=True)
        self.end = np.append(self.start[1:], len(keys))
        self.extra: dict[int, list[int]] = {}

    def ids(self, key: int) -> np.ndarray:
        i = np.searchsorted(self.uniq, key)
        base = (self.order[self.start[i]:self.end[i]]
                if i < len(self.uniq) and self.uniq[i] == key
                else np.zeros(0, np.int64))
        more = self.extra.get(key)
        return base if not more else np.concatenate([base, more])


class Reference:
    def __init__(self, rows: dict, capacity: int, max_select: int,
                 fragment_bytes: int):
        n = len(rows["page_id"])
        self.max_select = max_select
        self.nbytes = fragment_bytes
        self.columns = tuple(rows)
        self.cols = {c: np.zeros(capacity, rows[c].dtype) for c in rows}
        for c in rows:
            self.cols[c][:n] = rows[c]
        self.valid = np.zeros(capacity, bool)
        self.valid[:n] = True
        self.n = n
        self.index = {c: _KeyIndex(rows[c]) for c in KEY_COLS}

    @staticmethod
    def _value(col: str, v):
        return fragment_id(v) if col == "data" else v

    def _text(self, col: str, v):
        return fragment(int(v), self.nbytes) if col == "data" else int(v)

    def key_state(self, col: str, key: int) -> dict:
        """A copy of every row that has ``col = key`` (valid or not)."""
        ids = self.index[col].ids(key)
        st = {c: self.cols[c][ids].copy() for c in self.columns}
        st["valid"] = self.valid[ids].copy()
        return st

    def read(self, st: dict, state: dict) -> dict:
        """The answer of read ``st`` over the rows of one ``key_state``."""
        m = state["valid"].copy()
        for col, op, v in st["terms"]:
            m &= OPS[op](state[col], self._value(col, v))
        if st["kind"] == "count":
            return {"value": int(m.sum())}
        cols = st["cols"]
        return {"count": int(m.sum()), "rows": collections.Counter(
            tuple(self._text(c, state[c][i]) for c in cols)
            for i in np.flatnonzero(m))}

    def _where(self, terms) -> np.ndarray:
        """Ids of the valid rows matching every term; the first term is
        an equality on a key column."""
        col, _, key = terms[0]
        ids = np.sort(self.index[col].ids(key))
        m = self.valid[ids]
        for c, op, v in terms:
            m &= OPS[op](self.cols[c][ids], self._value(c, v))
        return ids[m]

    def apply(self, st: dict) -> dict:
        """The answer to statement ``st``, after applying its effect."""
        kind = st["kind"]
        if kind == "insert":
            i = self.n
            for c, v in zip(self.columns, st["params"]):
                self.cols[c][i] = self._value(c, v)
            self.valid[i] = True
            self.n += 1
            for c in KEY_COLS:
                self.index[c].extra.setdefault(int(self.cols[c][i]),
                                               []).append(i)
            return {"count": 1}
        if kind in ("select", "count"):
            col, _, key = st["terms"][0]
            return self.read(st, self.key_state(col, key))
        idx = self._where(st["terms"])
        if kind == "delete":
            self.valid[idx] = False
        elif kind == "update":
            col, v = st["set"]
            self.cols[col][idx] = self._value(col, v)
        else:
            raise ValueError(kind)
        return {"count": len(idx)}


# ------------------------------------------------------------ comparison

def mismatch(st: dict, got: dict, want: dict, limit: int) -> str | None:
    """None when the daemon's answer agrees with the reference's, else
    why. Rows are compared as multisets; past ``limit`` matches only the
    first ``limit`` come back, and each must be one of the matches."""
    kind = st["kind"]
    if kind == "select":
        if got["count"] != want["count"]:
            return f"count {got['count']} != {want['count']}"
        rows = collections.Counter(tuple(r[c] for c in st["cols"])
                                   for r in got["rows"])
        if sum(rows.values()) != min(want["count"], limit):
            return f"{sum(rows.values())} rows for count {want['count']}"
        if rows - want["rows"]:
            return "rows not in the table: " + str(
                [tuple(str(v)[:24] for v in r)
                 for r in list(rows - want["rows"])[:3]])
        return None
    if kind == "count":
        return (None if got["value"] == want["value"]
                else f"value {got['value']} != {want['value']}")
    if kind in ("delete", "update", "insert"):
        return (None if got["count"] == want["count"]
                else f"count {got['count']} != {want['count']}")
    raise ValueError(kind)
