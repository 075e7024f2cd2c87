"""Reduce a JAX profiler trace to device busy time, kernel times and the
longest idle gaps.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Each accelerator is a plane named ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per operation that ran on it, with a start
and a duration in nanoseconds. Busy time is the union of those
intervals (operations can overlap), and a kernel's time is the sum of
the durations of the events that carry its name. Host planes
(``/host:CPU``) hold the host threads' events; an idle gap on the
device is labelled by the host event that covers most of it.
"""
from __future__ import annotations

import pathlib
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str | pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path) -> list[dict]:
    """The trace's planes as plain dicts: {name, lines: [{name,
    events: [(name, start_ns, dur_ns, stats)]}]}. Stats are the event's
    metadata as a dict (operand shapes and the like, where present)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = []
            for ev in line.events:
                try:
                    stats = dict(ev.stats)
                except Exception:  # noqa: BLE001 — stats are optional
                    stats = {}
                evs.append((ev.name, float(ev.start_ns), float(ev.duration_ns),
                            stats))
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total covered length of [start, end) intervals, and the gaps
    between the merged runs as (start, end)."""
    total, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def reduce(planes: list[dict], window_s: float, top: int = 10,
           ignore: tuple[str, ...] = ()) -> dict:
    """busy_s (averaged over the devices that ran anything), per-op
    totals, each op's events, and the ``top`` longest idle gaps of the
    first busy device, labelled by what the host was doing. Host threads
    with an event whose name contains one of ``ignore`` are left out of
    the labels (the benchmark's own thread, asleep while it traces)."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    busy, ops, events = [], {}, {}
    first_gaps = None
    for p in devices:
        evs = [e for ln in p["lines"] if ln["name"] == OPS_LINE
               for e in ln["events"]]
        if not evs:
            continue
        total, gaps = _union([(s, s + d) for _, s, d, _ in evs])
        busy.append(total * 1e-9)
        if first_gaps is None:
            first_gaps = gaps
        for name, s, d, stats in evs:
            o = ops.setdefault(name, [0, 0.0])
            o[0] += 1
            o[1] += d * 1e-9
            events.setdefault(name, []).append((s, d, stats))
    host = [e for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"]
            if not any(s in e[0] for e in ln["events"] for s in ignore)
            for e in ln["events"]]
    gaps = sorted(first_gaps or [], key=lambda g: g[0] - g[1])[:top]
    return {
        "devices": len(busy),
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": window_s,
        "ops": ops,
        "events": events,
        "idle_gaps": [[_host_label(host, s, e), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def _host_label(host: list, s: float, e: float) -> str:
    """The name of the host event that overlaps [s, e) the most."""
    best, best_len = "no host event", 0.0
    for name, hs, hd, _ in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov > best_len:
            best, best_len = name, ov
    return best


def top_ops(red: dict, top: int = 10, width: int = 300) -> list:
    """The ``top`` operations by device time: [HLO text cut to ``width``
    characters, seconds]."""
    return [[name[:width], t] for name, (_, t) in
            sorted(red["ops"].items(), key=lambda kv: -kv[1][1])[:top]]


_CALL = re.compile(r"^%\S+ = (.*?) custom-call\((.*?)\), "
                   r'custom_call_target="tpu_custom_call"')
# an int32 array in HLO text: s32[dims]{layout}; "S(n)" in the layout
# names its memory space (absent: 0, the chip's HBM; 1: VMEM)
_S32 = re.compile(r"s32\[([\d,]*)\](?:\{([^}]*)\})?")
_SPACE = re.compile(r"S\((\d+)\)")


def _arrays(text: str) -> list[tuple[tuple[int, ...], int]]:
    out = []
    for dims, layout in _S32.findall(text):
        sp = _SPACE.search(layout or "")
        out.append((tuple(int(x) for x in dims.split(",") if x),
                    int(sp[1]) if sp else 0))
    return out


def custom_call(name: str) -> tuple[list, list] | None:
    """(outputs, operands) of a TPU custom call's HLO text, int32 arrays
    only, each as (dims, memory space); None for any other op."""
    m = _CALL.match(name)
    if m is None:
        return None
    return _arrays(m[1]), _arrays(m[2])


def _dims(arrays):
    return [d for d, _ in arrays]


def _scan(outs, ops):
    """relscan pass 1: operands ([4] values, [R,128] x (terms + 1)) and
    outputs ([R,128] mask, [blocks,8,128] counts)."""
    o, i = _dims(outs), _dims(ops)
    tiles = i[1:]
    if (len(i) >= 3 and i[0] == (4,) and len(set(tiles)) == 1
            and len(tiles[0]) == 2 and len(o) == 2
            and o[0] == tiles[0] and len(o[1]) == 3):
        return {"terms": len(tiles) - 1, "rows": tiles[0][0] * tiles[0][1],
                "arrays": ops[1:] + outs}
    return None


def _compact(outs, ops):
    """relscan pass 2: three [blocks] scalar vectors and the [R,128]
    mask in; the [limit,128] accumulator out."""
    o, i = _dims(outs), _dims(ops)
    if (len(i) == 4 and i[0] == i[1] == i[2] and len(i[0]) == 1
            and len(i[3]) == 2 and len(o) == 1 and len(o[0]) == 2):
        return {"blocks": i[0][0], "rows": i[3][0] * i[3][1],
                "limit": o[0][0]}
    return None


def _probe(outs, ops):
    """hashidx probe: two [queries] scalar vectors and the [buckets,cap]
    row-id and key tables in; [padded queries,cap] candidates and hits
    out."""
    o, i = _dims(outs), _dims(ops)
    if (len(i) == 4 and i[0] == i[1] and len(i[0]) == 1
            and i[2] == i[3] and len(i[2]) == 2 and len(o) == 2
            and o[0] == o[1] and o[0][1] == i[2][1]):
        return {"queries": i[0][0], "bucket_cap": i[2][1],
                "tables": ops[2:], "outputs": outs}
    return None


# Pallas kernels carry no stable name in the trace (the HLO instruction is
# named after whatever jitted function inlined it), so each is known by
# the shapes of its operands and outputs, keyed by its kernel function.
KERNELS = {"_scan_kernel": _scan, "_compact_kernel": _compact,
           "_probe_kernel": _probe}


# The hash index's INSERT upkeep (``kernels/hashidx.insert_update_batched``)
# is plain XLA: its "clear" sweep is a gather of a pred[capacity + 1] mask
# of inserted slots at every one of the index's N row-id lanes
# (pred[N] out of pred[M], s32[N]), then a select over the [buckets,
# lanes] row-id table with that pred[N] (s32[B, L] out of s32[B, L],
# pred[B * L]).
_GATHER = re.compile(r"^%\S+ = pred\[(\d+)\]\S* fusion\(pred\[(\d+)\]\S* "
                     r"%\S+, s32\[(\d+)\]\S* %\S+\)")
_CLEAR = re.compile(r"^%\S+ = s32\[(\d+),(\d+)\]\S* fusion\("
                    r"s32\[(\d+),(\d+)\]\S* %\S+, pred\[(\d+)\]\S* %\S+\)")


def upkeep_op(name: str) -> bool:
    """Whether the HLO text ``name`` is one of the index upkeep's two
    sweeps over every index lane."""
    m = _GATHER.match(name)
    if m is not None:
        return m[1] == m[3] and int(m[2]) < int(m[1])
    m = _CLEAR.match(name)
    return (m is not None and m.group(1, 2) == m.group(3, 4)
            and int(m[5]) == int(m[1]) * int(m[2]))


def upkeep_calls(red: dict) -> list[float]:
    """Duration in ns of every device event of the index upkeep."""
    return [d for name, evs in red["events"].items() if upkeep_op(name)
            for _, d, _ in evs]


def kernel_calls(red: dict, kernel: str) -> list[tuple[float, dict]]:
    """(duration in ns, parameters) of every device event of ``kernel``
    (a key of ``KERNELS``)."""
    match = KERNELS[kernel]
    out = []
    for name, evs in red["events"].items():
        cc = custom_call(name)
        params = match(*cc) if cc is not None else None
        if params is not None:
            out += [(d, params) for _, d, _ in evs]
    return out
