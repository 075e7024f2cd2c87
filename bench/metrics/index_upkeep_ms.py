"""Device time of the hash index's INSERT upkeep per INSERT answered in
the traced window, in ms: the two sweeps of
``kernels/hashidx.insert_update_batched`` over every index lane (found
by their shapes, ``bench/devtrace.upkeep_op``), summed over the traced
window, over the INSERTs whose answers came back in it."""
from bench import devtrace


def read(ctx):
    tr, traced = ctx.get("trace"), ctx.get("traced")
    if not tr or not traced:
        return None
    calls = devtrace.upkeep_calls(tr)
    lo, hi = traced["start"], traced["start"] + traced["window_s"]
    inserts = sum(1 for st in ctx["stmts"] if st["kind"] == "insert"
                  and ctx["recs"][st["id"]]["r"] is not None
                  and lo <= ctx["recs"][st["id"]]["r"] < hi)
    if not calls or not inserts:
        return None
    return sum(calls) * 1e-6 / inserts
