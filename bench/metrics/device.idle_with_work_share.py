"""Share of the device's idle time in the traced window during which at
least one statement was in flight (between its admission and the end of
its ``sqlcached.dispatch`` span), in %: the program's profiler spans
against the gaps between device ops (``bench/hostspans.py``, which also
logs the idle time by span and the busy time by XLA module)."""
from bench import hostspans


def read(ctx):
    att = hostspans.for_run(ctx)
    if att is None or att["idle_s"] <= 0:
        return None
    return 100.0 * att["idle_with_work_s"] / att["idle_s"]
