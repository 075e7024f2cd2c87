"""Mean ``device_wait`` span per statement in the window, in ms: time
blocked in the device->host transfer of the statement's result, a child
of ``render`` (telemetry stage totals from SHOW METRICS, differenced
across the window)."""


def read(ctx):
    total_us, n = ctx["delta"]["stages"].get("device_wait", (0.0, 0))
    return total_us / n / 1e3 if n else None
