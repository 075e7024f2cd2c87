"""A whole run, with the chip check skipped and the timed path broken
underneath, must come out not correct: once for each fault this cell
can have."""
from __future__ import annotations

import re
import time

import pytest

from bench import harness
from bench.tests._small import MIX, RATE, SMALL, deadline, small_env  # noqa: F401


def _run(workload, fault):
    with deadline(240):
        return harness.run(workload, 11, 2.0, False,
                           process_start=time.monotonic(), require_chip=False,
                           config_override=SMALL, cell_override=RATE,
                           mix_override=MIX, fault=fault)


def _writes_leave_state_unchanged(server):
    """DELETE and UPDATE answer their match count but change nothing:
    each is rewritten into the SELECT of its WHERE clause."""
    submit = server.scheduler.submit

    def patched(sql, params=(), trace=None):
        m = re.match(r"DELETE FROM (\S+) WHERE (.*)", sql)
        if m:
            return submit(f"SELECT * FROM {m[1]} WHERE {m[2]}", params, trace)
        m = re.match(r"UPDATE (\S+) SET \S+ = \? WHERE (.*)", sql)
        if m:
            return submit(f"SELECT * FROM {m[1]} WHERE {m[2]}", params[1:],
                          trace)
        return submit(sql, params, trace)
    server.scheduler.submit = patched


def _altered_answer(monkeypatch):
    """Every 25th rendered answer reports one match too many."""
    from repro.core import protocol
    render = protocol._render_result
    n = [0]

    def patched(res, tag):
        n[0] += 1
        out = render(res, tag)
        if n[0] % 25 == 0:
            head, sep, rest = out.partition(b"\r\n")
            verb, _, count = head.rpartition(b" ")
            out = verb + b" " + str(int(count) + 1).encode() + sep + rest
        return out
    monkeypatch.setattr(protocol, "_render_result", patched)


@pytest.mark.parametrize("workload", ["cms.page_reads", "cms.user_activity"])
def test_state_left_unchanged_is_not_correct(small_env, workload):
    res = _run(workload, _writes_leave_state_unchanged)
    assert res["correct"] is False
    assert res["checks"]["wrong"]["value"] > 0


def test_altered_answer_is_not_correct(small_env, monkeypatch):
    _altered_answer(monkeypatch)
    res = _run("cms.page_reads", None)
    assert res["correct"] is False
    assert res["checks"]["wrong"]["value"] > 0
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in harness.spec()["end_to_end"]
            if "cms.page_reads" in m.get("workloads", ["cms.page_reads"])}
    assert set(res["metrics"]) == want
