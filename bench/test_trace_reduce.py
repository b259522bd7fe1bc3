"""The trace reduction on a small trace recorded on a TPU v5e
(``testdata/tiny.xplane.pb``: three steps of two jitted programs, each
step a ``session.step`` span, a ``loadgen.wait`` span of 3 ms after it,
all inside the ``bench.window`` span)."""
import os

import pytest

import trace_reduce

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                    "tiny.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(trace_reduce.load(TINY))


def test_timeline_nests():
    spans = [("bench.window", 0, 100), ("session.step", 10, 50),
             ("$array.py _value", 20, 30), ("loadgen.wait", 60, 70)]
    assert trace_reduce.timeline(spans[1:]) == [
        (10, 20, "session.step"), (20, 30, "session.step: $array.py _value"),
        (30, 50, "session.step"), (60, 70, "loadgen.wait")]


def test_union():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == \
        [[0, 3], [5, 9]]


def test_program_name():
    assert trace_reduce.program_name("jit__catchup_impl(42)") == \
        "_catchup_impl"
    assert trace_reduce.program_name("jit_step") == "step"


def test_window_and_busy(summary):
    assert summary["n_devices"] == 1
    assert 0.009 < summary["window_s"] < 1.0
    assert 0 < summary["busy_s"] < summary["window_s"]


def test_programs_share_the_busy_time(summary):
    progs = summary["programs"]      # both programs are jitted lambdas
    assert list(progs) == ["_lambda"] and progs["_lambda"] > 0
    assert progs["_lambda"] >= summary["busy_s"] * 0.99


def test_breakdown(summary):
    ops = summary["device_ops"]
    assert 0 < len(ops) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = dict(summary["idle_gaps"])
    # three sleeps of 3 ms (the OS wakes a little late) in loadgen.wait
    assert 0.009 <= gaps["loadgen.wait: $time sleep"] < 0.0125
    assert any(k.startswith("session.step") for k in gaps)
    idle = summary["window_s"] - summary["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
