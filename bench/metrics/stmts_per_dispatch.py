"""Statements per scheduler dispatch in the window: (single statements +
grouped statements) / dispatches. The scheduler's ``batches`` counter
counts every dispatch, singles included (SHOW STATS deltas)."""


def read(ctx):
    s = ctx["delta"]["scheduler"]
    n = s.get("batches", 0)
    return (s.get("singles", 0) + s.get("grouped_statements", 0)) / n \
        if n else None
