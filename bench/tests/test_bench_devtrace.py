"""The trace reduction and the peaks table, on a small trace recorded on
a TPU v5e (``bench/record_trace.py``) and on hand-made intervals."""
from __future__ import annotations

import pathlib

import pytest

from bench import devtrace, harness, peaks

TRACE = pathlib.Path(__file__).parent / "data" / "trace_small.xplane.pb"


def test_union_and_gaps():
    total, gaps = devtrace._union([(0, 10), (5, 12), (20, 25), (24, 30),
                                   (40, 41)])
    assert total == 12 + 10 + 1
    assert gaps == [(12, 20), (30, 40)]


def test_reduce_labels_gaps_by_host_event():
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("a", 0.0, 1e6, {}), ("b", 3e6, 1e6, {}), ("a", 9e6, 1e6, {})]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("render", 1e6, 2e6, {}), ("parse", 4e6, 5e6, {})]}]},
    ]
    red = devtrace.reduce(planes, window_s=0.01)
    assert red["busy_s"] == pytest.approx(3e-3)
    assert red["ops"]["a"] == [2, pytest.approx(2e-3)]
    assert red["idle_gaps"] == [["parse", pytest.approx(5e-3)],
                                ["render", pytest.approx(2e-3)]]
    ctx = {"trace": red}
    assert harness.metric_reader("device.idle_share")(ctx) == \
        pytest.approx(70.0)


def test_peaks_table():
    assert peaks.for_device("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.for_device("TPU v9 imaginary")
    # two term columns and validity read, the mask and 128 count tiles
    # written; the column in VMEM (space 1) moves nothing over HBM
    col = ((32768, 128), 0)
    arrays = [col, ((32768, 128), 1), col, col, ((128, 8, 128), 0)]
    assert peaks.scan_bytes(arrays) == (3 * (1 << 22) + 128 * 1024) * 4
    # one query: an (8, 128) tile of each table read, two rows written
    assert peaks.probe_bytes(1, 128, [((1 << 17, 128), 0)] * 2,
                             [((8, 128), 0)] * 2) == 4 * 8 * 128 * 4


def test_kernels_found_by_shape_in_chip_trace():
    """The trace recorded on the chip (the benchmark's table, 5 rounds of
    a page read, a user list, a user count and an INSERT) holds 10
    one-term scans, 5 compactions, 5 probes and the INSERT upkeep's two
    sweeps; every share stays under 100% of the HBM roofline."""
    red = devtrace.reduce(devtrace.load(TRACE), window_s=1.0)
    scans = devtrace.kernel_calls(red, "_scan_kernel")
    assert [p["terms"] for _, p in scans] == [1] * 10
    assert all(p["rows"] == 131072 for _, p in scans)
    assert len(devtrace.kernel_calls(red, "_compact_kernel")) == 5
    probes = devtrace.kernel_calls(red, "_probe_kernel")
    assert [p["queries"] for _, p in probes] == [1] * 5
    upkeep = [n for n in red["events"] if devtrace.upkeep_op(n)]
    assert len(upkeep) == 2 and len(devtrace.upkeep_calls(red)) >= 8
    ctx = {"trace": red, "peaks": peaks.for_device("TPU v5 lite")}
    for name in ("relscan_roofline", "hashidx_probe_roofline"):
        share = harness.metric_reader(name)(ctx)
        assert 0 < share <= 100
    assert 0 < red["busy_s"] < 0.05
    idle = harness.metric_reader("device.idle_share")(ctx)
    assert 95 < idle < 100


UPKEEP = [   # the upkeep's two sweeps, as the trace names them at 2^22 slots
    "%fusion = pred[16777216]{0:T(1024)(128)(4,1)S(1)} fusion(pred[4194305]"
    "{0:T(1024)(128)(4,1)S(1)} %dynamic-update-slice.21, s32[16777216]"
    "{0:T(1024)} %fusion.3), kind=kCustom, calls=%fused_computation",
    "%fusion.4 = s32[131072,128]{1,0:T(8,128)} fusion(s32[131072,128]"
    "{1,0:T(8,128)} %state__indexes____page_id____rid__.1, pred[16777216]"
    "{0:T(1024)(128)(4,1)S(1)} %fusion), kind=kLoop, calls=%fused_computation",
]
NOT_UPKEEP = [
    "%fusion.3 = s32[256]{0:T(256)} fusion(s32[4194304]{0:T(1024)} "
    "%state__cols____user_id__.1, s32[1024]{0:T(1024)S(1)} %pad), kind=kCustom",
    "%fusion.9 = pred[4096]{0} fusion(pred[8192]{0} %a, s32[4096]{0} %b)",
]


def test_upkeep_found_by_shape():
    assert [devtrace.upkeep_op(n) for n in UPKEEP + NOT_UPKEEP] == \
        [True, True, False, False]
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
               "events": [(UPKEEP[0], 0.0, 3e6, {}), (UPKEEP[1], 3e6, 1e6, {}),
                          (NOT_UPKEEP[0], 5e6, 1e6, {})]}]}]
    red = devtrace.reduce(planes, window_s=1.0)
    stmts = [{"id": i, "kind": k} for i, k in
             enumerate(["insert", "insert", "select", "insert"])]
    recs = {0: {"r": 2.1}, 1: {"r": 2.9}, 2: {"r": 2.5}, 3: {"r": 3.5}}
    ctx = {"trace": red, "traced": {"start": 2.0, "window_s": 1.0},
           "stmts": stmts, "recs": recs}
    # 4 ms of upkeep over the two INSERTs answered in [2, 3)
    assert harness.metric_reader("index_upkeep_ms")(ctx) == \
        pytest.approx(2.0)


def test_reader_finds_nothing_returns_nothing():
    red = devtrace.reduce([], window_s=1.0)
    ctx = {"trace": red, "peaks": peaks.for_device("TPU v5 lite")}
    ctx.update(traced={"start": 0.0, "window_s": 1.0}, stmts=[], recs={})
    for name in ("relscan_roofline", "hashidx_probe_roofline",
                 "device.idle_share", "index_upkeep_ms"):
        assert harness.metric_reader(name)(ctx) is None
