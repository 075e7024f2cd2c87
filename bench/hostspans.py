"""Put the device's idle time down to what the host was doing.

The daemon writes its per-statement stages and their child spans as
profiler annotations named ``sqlcached.<stage>`` (``core/telemetry.py``),
on the clock of the device ops in the same trace. Here only those host
events count; the Python tracer's events and every other host event are
left out. A statement is *in flight* from its admission to the end of
its ``sqlcached.dispatch`` span: while one is, the host holds work the
device could be running.

Idle time is the gaps between the device's operations (the union of the
``XLA Ops`` intervals of the first device that ran any). Each instant of
it goes to the first span in ``ORDER`` that some statement has open
then (a child comes before its parent), or to ``NOTHING``.
"""
from __future__ import annotations

import re
import sys

from bench import devtrace

PREFIX = "sqlcached."
# the spans between a statement's admission and the end of its dispatch
IN_FLIGHT = ("queue", "cut_wait", "wave_wait", "lock", "execute", "dispatch")
ORDER = ("dispatch", "cut_wait", "wave_wait", "lock", "respond_wait",
         "device_wait", "render", "execute", "queue", "parse", "wire")
NOTHING = "no statement in flight"
MODULES_LINE = "XLA Modules"
# a device_wait span this long should overlap device work: an op, or a
# device-to-host transfer, which the TPU runtime marks on a host thread
LONG_WAIT_NS = 0.5e6
TRANSFER = "tpu::System::TransferFromDevice"


def host_spans(planes: list[dict]) -> dict[str, list[tuple[float, float]]]:
    """(start, end) in ns of every ``sqlcached.*`` host event, by stage,
    and of the runtime's device-to-host transfers under ``TRANSFER``."""
    out: dict[str, list] = {}
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            for name, s, d, _ in ln["events"]:
                if name.startswith(PREFIX):
                    out.setdefault(name[len(PREFIX):], []).append((s, s + d))
                elif name.startswith(TRANSFER):
                    out.setdefault(TRANSFER, []).append((s, s + d))
    return out


def _device(planes: list[dict]) -> dict | None:
    for p in planes:
        if not p["name"].startswith(devtrace.DEVICE_PREFIX):
            continue
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        if lines.get(devtrace.OPS_LINE):
            return lines
    return None


def _split(gaps: list, spans: dict) -> dict[str, float]:
    """ns of ``gaps`` under each label: at each instant the first label in
    ``ORDER`` (then any other, by name) with a span open, else NOTHING."""
    labels = [s for s in ORDER if s in spans] + sorted(set(spans) - set(ORDER))
    pts = sorted((t, step, k) for k, lab in enumerate(labels)
                 for s, e in spans[lab] for t, step in ((s, 1), (e, -1)))
    open_ = [0] * len(labels)
    out: dict[str, float] = {}
    i = 0
    for gs, ge in sorted(gaps):
        while i < len(pts) and pts[i][0] <= gs:
            open_[pts[i][2]] += pts[i][1]
            i += 1
        t = gs
        while True:
            nxt = pts[i][0] if i < len(pts) and pts[i][0] < ge else ge
            if nxt > t:
                k = next((k for k, n in enumerate(open_) if n > 0), None)
                lab = NOTHING if k is None else labels[k]
                out[lab] = out.get(lab, 0.0) + (nxt - t)
            if nxt >= ge:
                break
            open_[pts[i][2]] += pts[i][1]
            i += 1
            t = nxt
    return out


def _runs(intervals: list) -> list[list[float]]:
    """The merged, disjoint runs of (start, end) intervals, in order."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlaps(spans: list, busy: list) -> int:
    """How many of ``spans`` overlap some interval of ``busy`` (both
    sorted (start, end) lists; ``busy`` disjoint)."""
    n, j = 0, 0
    for s, e in sorted(spans):
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        if j < len(busy) and busy[j][0] < e:
            n += 1
    return n


def attribute(planes: list[dict]) -> dict | None:
    """Idle time by innermost open program span, the share of it with a
    statement in flight, device busy time by ``XLA Modules`` name, and
    how many long ``device_wait`` spans overlap device work. None when
    the trace holds no device ops or no program spans."""
    dev = _device(planes)
    spans = host_spans(planes)
    transfers = [list(iv) for iv in spans.pop(TRANSFER, [])]
    if dev is None or not spans:
        return None
    busy = _runs([(s, s + d) for _, s, d, _ in dev[devtrace.OPS_LINE]])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    busy_ns = sum(e - s for s, e in busy)
    idle_ns = sum(e - s for s, e in gaps)
    by_span = _split(gaps, spans)
    flight = [iv for name in IN_FLIGHT for iv in spans.get(name, [])]
    with_work = _split(gaps, {"in_flight": flight}).get("in_flight", 0.0)
    modules: dict[str, float] = {}
    for name, _, d, _ in dev.get(MODULES_LINE, []):
        key = re.sub(r"\(\d+\)$", "", name)
        modules[key] = modules.get(key, 0.0) + d * 1e-9
    waits = [iv for iv in spans.get("device_wait", [])
             if iv[1] - iv[0] > LONG_WAIT_NS]
    return {"busy_s": busy_ns * 1e-9, "idle_s": idle_ns * 1e-9,
            "idle_with_work_s": with_work * 1e-9,
            "idle_by_span": {k: v * 1e-9 for k, v in by_span.items()},
            "busy_by_module": modules,
            "long_device_waits": len(waits),
            "long_device_waits_on_device": _overlaps(waits, busy),
            "long_device_waits_on_device_or_transfer": _overlaps(
                waits, _runs(busy + transfers))}


def log_table(att: dict) -> None:
    def say(msg):
        print(f"bench: {msg}", file=sys.stderr, flush=True)
    idle = att["idle_s"] or 1.0
    say(f"host spans: device idle {att['idle_s']:.6f} s between ops, "
        f"{att['idle_with_work_s']:.6f} s of it with a statement in flight")
    for name, t in sorted(att["idle_by_span"].items(), key=lambda kv: -kv[1]):
        say(f"  idle under {name:<24} {t:.6f} s  {100 * t / idle:6.2f}%")
    busy = att["busy_s"] or 1.0
    say(f"host spans: device busy {att['busy_s']:.6f} s by XLA module")
    for name, t in sorted(att["busy_by_module"].items(),
                          key=lambda kv: -kv[1]):
        say(f"  busy in {name:<40} {t:.6f} s  {100 * t / busy:6.2f}%")
    say(f"host spans: {att['long_device_waits_on_device']} of "
        f"{att['long_device_waits']} device_wait spans over "
        f"{LONG_WAIT_NS * 1e-6:g} ms overlap a device op, "
        f"{att['long_device_waits_on_device_or_transfer']} an op or a "
        "device-to-host transfer")


def for_run(ctx: dict) -> dict | None:
    """:func:`attribute` of the traced run ``ctx`` describes, read from the
    directory the harness traces into, with its tables logged."""
    if not ctx.get("traced"):
        return None
    from bench import harness
    try:
        path = devtrace.find_xplane(harness.OUT / f"trace-{ctx['workload']}")
    except FileNotFoundError:
        return None
    att = attribute(devtrace.load(path))
    if att is not None:
        log_table(att)
    return att
