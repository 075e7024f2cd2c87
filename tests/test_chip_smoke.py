"""chip_smoke.py's logic on the CPU at a tiny size: its phases run the
served path end to end with the Pallas kernels in interpret mode, every
answer agrees with the numpy reference, the comparison catches a wrong
answer, and ``main()`` refuses to run without a TPU."""
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")


@pytest.mark.parametrize("phase", ["single_chip", "four_chips"])
def test_phases_match_reference(smoke, interpret, phase):
    import jax
    out = getattr(smoke, phase)(seed=3, capacity=4096, n_rows=3000,
                                batch=1024, n_stmts=60)
    assert len(out) == 2
    for name, info in out:
        assert info["rows"] == 3000
        assert info["answered"] == 64, name
        bad = info["mismatches"]
        if name == "fragments_s4_mesh" and jax.device_count() < 4:
            # fewer than four devices: the lanes cannot be placed one per
            # chip, and the placement check must say so
            assert len(bad) == 1 and bad[0].startswith("lanes on devices")
            bad = []
        assert bad == [], (name, bad[:3])
        assert info["matched"] == info["answered"]
        assert info["stats"]["executors"]["warmup_errors"] == []


def test_traffic_covers_every_statement_kind(smoke):
    rows = smoke.make_rows(0, 3000)
    kinds = {st["kind"] for st in smoke.make_traffic(0, rows, "t", 200)}
    assert kinds == {"select", "count", "avg", "delete", "update",
                     "insert", "explain"}


def test_reference_comparison_catches_wrong_answers(smoke):
    rows = smoke.make_rows(1, 3000)
    ref = smoke.Reference(rows, 4096)
    u = int(rows["user_id"][0])
    st = {"kind": "select", "terms": [("user_id", "=", u)],
          "cols": ("page_id", "weight")}
    want = ref.apply(st)
    good = {"count": want["count"],
            "rows": [{"page_id": p, "weight": w}
                     for (p, w) in want["rows"].elements()]}
    assert smoke.mismatch(st, good, want, 256) is None
    assert smoke.mismatch(st, dict(good, count=want["count"] + 1), want,
                          256) is not None
    wrong = [dict(r) for r in good["rows"]]
    wrong[0]["weight"] += 0.5
    assert smoke.mismatch(st, dict(good, rows=wrong), want, 256) is not None
    assert smoke.mismatch(st, dict(good, rows=good["rows"][1:]), want,
                          256) is not None
    avg = {"kind": "avg", "terms": [("user_id", "=", u)]}
    w = ref.apply(avg)["value"]
    assert smoke.mismatch(avg, {"value": w * (1 + 1e-6)},
                          {"value": w}, 256) is None
    assert smoke.mismatch(avg, {"value": w + 0.01}, {"value": w},
                          256) is not None


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err
