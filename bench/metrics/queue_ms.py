"""Mean ``queue`` span per statement in the window, in ms (telemetry
stage totals from SHOW METRICS, differenced across the window)."""


def read(ctx):
    total_us, n = ctx["delta"]["stages"].get("queue", (0.0, 0))
    return total_us / n / 1e3 if n else None
