"""The device's idle time put down to the program's host spans, on planes
built as ``devtrace.load`` returns them, and the reader of
``device.idle_with_work_share`` on them."""
from __future__ import annotations

import pathlib

import pytest

from bench import devtrace, harness, hostspans

MS = 1e6   # ns
TRACE = pathlib.Path(__file__).parent / "data" / "trace_small.xplane.pb"


def _ev(name, a, b, **stats):
    return (name, a * MS, (b - a) * MS, stats)


def _planes(with_spans: bool = True) -> list[dict]:
    """Device ops at [0, 1), [5, 6) and [10, 11) ms: idle [1, 5) and
    [6, 10). On the host, one statement waits for its cut, then for its
    dispatch, which runs into the second op; another waits for its turn
    to answer, then for the device (the runtime's device-to-host
    transfer), then formats; the Python tracer's events cover the whole
    trace and count for nothing."""
    ops = [_ev("%fusion = s32[8] fusion(s32[8] %a)", 0, 1),
           _ev("%fusion.1 = s32[8] fusion(s32[8] %b)", 5, 6),
           _ev("%fusion.1 = s32[8] fusion(s32[8] %b)", 10, 11)]
    modules = [_ev("jit_sqlcached_select_probe(123)", 0, 1),
               _ev("jit_sqlcached_insert_batch(456)", 5, 6),
               _ev("jit_sqlcached_insert_batch(789)", 10, 11)]
    host = [_ev("$builtins isinstance", 1, 11), _ev("__unknown__get", 1, 11),
            _ev("tpu::System::TransferFromDevice=>IssueEvent=>Done", 8.5, 8.55)]
    if with_spans:
        host += [_ev("sqlcached.queue", 1.5, 4, id=1, kind="insert"),
                 _ev("sqlcached.cut_wait", 1.5, 3, id=1, kind="insert"),
                 _ev("sqlcached.wave_wait", 3, 4, id=1, kind="insert"),
                 _ev("sqlcached.dispatch", 4, 5.5, id=1, group=1),
                 _ev("sqlcached.render", 6, 9, id=2, kind="select"),
                 _ev("sqlcached.respond_wait", 6, 8, id=2, kind="select"),
                 _ev("sqlcached.device_wait", 7, 7.1, id=2, kind="select"),
                 _ev("sqlcached.device_wait", 8, 8.6, id=2, kind="select"),
                 _ev("sqlcached.device_wait", 10.2, 10.9, id=3)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host}]},
    ]


def test_idle_gaps_go_to_the_innermost_open_span():
    att = hostspans.attribute(_planes())
    got = {k: v * 1e3 for k, v in att["idle_by_span"].items()}   # ms
    assert got == pytest.approx({
        hostspans.NOTHING: 0.5 + 1.0, "cut_wait": 1.5, "wave_wait": 1.0,
        "dispatch": 1.0, "respond_wait": 2.0, "device_wait": 0.6,
        "render": 0.4})
    assert att["idle_s"] == pytest.approx(8e-3)
    assert att["busy_s"] == pytest.approx(3e-3)
    # admission to the end of the dispatch: [1.5, 5) of the idle time
    assert att["idle_with_work_s"] == pytest.approx(3.5e-3)
    assert att["busy_by_module"] == pytest.approx(
        {"jit_sqlcached_select_probe": 1e-3,
         "jit_sqlcached_insert_batch": 2e-3})
    # of the two device waits over 0.5 ms, one lies on a device op, the
    # other on a device-to-host transfer
    assert (att["long_device_waits"], att["long_device_waits_on_device"],
            att["long_device_waits_on_device_or_transfer"]) == (2, 1, 2)


def test_a_gap_with_no_statement_in_flight():
    planes = _planes()
    planes[1]["lines"][0]["events"] = [
        _ev("sqlcached.render", 0.2, 0.8, id=4, kind="select")]
    att = hostspans.attribute(planes)
    assert att["idle_by_span"] == {hostspans.NOTHING: pytest.approx(8e-3)}
    assert att["idle_with_work_s"] == 0.0


def test_reader_on_a_traced_run(tmp_path, monkeypatch, capsys):
    """The reader finds the run's trace where the harness writes it and
    logs the two tables; a trace with no program spans (a program
    without them, like the one recorded on the chip) reads nothing."""
    xplane = tmp_path / "trace-w" / "plugins" / "profile" / "r" / "h.xplane.pb"
    xplane.parent.mkdir(parents=True)
    xplane.write_bytes(b"")
    monkeypatch.setattr(harness, "OUT", tmp_path)
    planes = _planes()
    monkeypatch.setattr(devtrace, "load", lambda path: planes)
    ctx = {"workload": "w", "traced": {"start": 0.0, "window_s": 1.0}}
    read = harness.metric_reader("device.idle_with_work_share")
    assert read(ctx) == pytest.approx(100 * 3.5 / 8)
    err = capsys.readouterr().err
    assert "host spans: device idle" in err
    assert "jit_sqlcached_insert_batch" in err and "cut_wait" in err
    planes[:] = _planes(with_spans=False)
    assert read(ctx) is None
    assert read({"workload": "w", "traced": None}) is None
    monkeypatch.setattr(harness, "OUT", tmp_path / "none")
    assert read(ctx) is None


def test_chip_trace_without_program_spans_reads_nothing():
    assert hostspans.attribute(devtrace.load(TRACE)) is None
