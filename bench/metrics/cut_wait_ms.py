"""Mean ``cut_wait`` span per statement in the window, in ms: from
admission to the scheduler loop cutting the statement off its queue, a
child of ``queue`` (telemetry stage totals from SHOW METRICS,
differenced across the window)."""


def read(ctx):
    total_us, n = ctx["delta"]["stages"].get("cut_wait", (0.0, 0))
    return total_us / n / 1e3 if n else None
