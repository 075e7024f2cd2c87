"""BENCHMARK.json keeps to the benchmark's contract, and everything it
names is found by name: configurations, mixes, cells and per-layer
metric readers. A new mix is a data file and nothing else."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from bench import harness, traffic

SPEC = harness.spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_and_budget():
    assert set(SPEC) == KEYS
    assert (harness.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    rs = SPEC["run_seconds"]
    # the full check with 24 cells must fit
    assert 24 * (14 * (rs + 60) + 2 * 90) + 2 * (rs + 60) + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config(entry):
    assert NAME.match(entry["name"])
    cfg = json.loads((harness.ROOT / entry["file"]).read_text())
    assert entry["file"].startswith("bench/")
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    assert all(NAME.match(k) and k in cfg for k in entry["reduced"])
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])
    assert 1 <= len(entry["why"]) <= 200 and 1 <= len(entry["source"]) <= 200


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload(w):
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    c = harness.cell(w["name"], SPEC)
    assert c["config"]["name"] == w["config"]
    lim = c["cell"]["limits"]
    assert lim["wrong"] == 0 and lim["unanswered"] == 0
    assert c["cell"]["rate_per_s"] > 0 and c["cell"]["p99_limit_ms"] > 0
    assert traffic.schedule(c["config"], c["mix"], 1, "t", rate=50,
                            seconds=1.0)


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if "bound" in m:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    else:
        e2e = {e["name"] for e in SPEC["end_to_end"]}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        assert callable(harness.metric_reader(m["name"]))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_setup_metric():
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25


def test_new_mix_is_found_without_an_edit(tmp_path, monkeypatch):
    """A later PR adds a mix as one data file: the harness lists it and
    the one generator draws a schedule from it."""
    shutil.copytree(harness.BENCH / "mixes", tmp_path / "mixes")
    mix = json.loads((tmp_path / "mixes" / "page_reads.json").read_text())
    mix["statements"] = mix["statements"][:1]
    mix["connections"] = 4
    (tmp_path / "mixes" / "dummy_reads.json").write_text(json.dumps(mix))
    monkeypatch.setattr(harness, "BENCH", tmp_path)
    assert "dummy_reads" in harness.mixes()
    cfg = json.loads((harness.ROOT / "bench/configs/cms_fragments.json")
                     .read_text())
    sched = traffic.schedule(cfg, harness.load_json("mixes", "dummy_reads"),
                             9, "t", rate=100, seconds=2.0)
    assert len(sched) == 200
    assert {s["kind"] for s in sched} == {"select"}
    assert {s["conn"] for s in sched} <= set(range(4))


def test_schedule_is_the_same_work_for_every_seed():
    """Seeds change the keys and values, never the arrivals or the
    order of the kinds; one seed always gives the same schedule."""
    cfg = json.loads((harness.ROOT / "bench/configs/cms_fragments.json")
                     .read_text())
    mix = harness.load_json("mixes", "user_activity")
    runs = []
    for seed in (1, 2**33 + 5):
        s = traffic.schedule(cfg, mix, seed, "t", rate=200, seconds=5.0)
        assert s == traffic.schedule(cfg, mix, seed, "t", rate=200,
                                     seconds=5.0)
        runs.append(s)
    assert [(a["due"], a["kind"]) for a in runs[0]] == \
        [(b["due"], b["kind"]) for b in runs[1]]
    assert [a["params"] for a in runs[0]] != [b["params"] for b in runs[1]]


def test_foreign_reads_are_reads_from_another_connection():
    """Writes always go to the key's owner; the mix's share of reads
    comes from another connection."""
    cfg = harness.load_json("configs", "cms_fragments")
    for name in ("page_reads", "user_activity"):
        mix = harness.load_json("mixes", name)
        s = traffic.schedule(cfg, mix, 5, "t", rate=400, seconds=10.0)
        n = mix["connections"]
        assert all(st["owner"] == st["key"] % n for st in s)
        moved = [st for st in s if st["conn"] != st["owner"]]
        assert {st["kind"] for st in moved} <= set(traffic.READS)
        reads = sum(st["kind"] in traffic.READS for st in s)
        assert abs(len(moved) / reads - mix["foreign_reads"]) < 0.03


def test_imports_load_no_accelerator_library():
    """The generator and the harness's modules import without JAX."""
    import subprocess
    import sys
    code = ("import sys; sys.path.insert(0, %r); "
            "import bench.loadgen, bench.wire, bench.traffic, "
            "bench.reference, bench.check, bench.devtrace, bench.peaks, "
            "bench.harness; assert 'jax' not in sys.modules, 'jax loaded'"
            % str(harness.ROOT))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
