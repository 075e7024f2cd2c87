"""Mean ``wave_wait`` span per statement in the window, in ms: from the
cut to its group's dispatch start, behind earlier conflicting waves, a
child of ``queue`` (telemetry stage totals from SHOW METRICS,
differenced across the window)."""


def read(ctx):
    total_us, n = ctx["delta"]["stages"].get("wave_wait", (0.0, 0))
    return total_us / n / 1e3 if n else None
