"""Executor-cache compiles between the two counter readings that frame
the window (SHOW STATS ``executors.compiles`` delta). Every shape the
window uses is planned in set-up, so this should read 0."""


def read(ctx):
    return ctx["delta"]["executors"].get("compiles", 0)
