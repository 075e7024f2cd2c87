"""Mean ``respond_wait`` span per statement in the window, in ms: from
the end of execute to the start of the statement's own item in the
render burst (the in-order wait on a pipelined connection and the
hops), a child of ``render`` (telemetry stage totals from SHOW METRICS,
differenced across the window)."""


def read(ctx):
    total_us, n = ctx["delta"]["stages"].get("respond_wait", (0.0, 0))
    return total_us / n / 1e3 if n else None
