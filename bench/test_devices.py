"""The readers that know the device count: the shares of a peak over
``chips`` chips, the catch-up counts over every device plane, and the
share of rounds a chip runs for the other chips' rows.  On one device each
reads what it read before (``test_program_spans.py``,
``test_trace_reduce.py``)."""
import os
import types
from collections import Counter

import pytest

import harness
import layers
import program_spans
import trace_reduce
import work
from peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))
CATCHUP = os.path.join(HERE, "testdata", "catchup.xplane.pb")
HIDDEN = 64


def _ctx(chips):
    sz, mod = harness.load_config("granite-8b-chip")
    steps = [{"pos": k, "n": 32,
              "replays": [(i, k - 2, k) for i in range(0, 32, 3)]
              if k % 2 else []} for k in range(2, 40)]
    return {"summary": {"programs": {"_catchup_impl": 0.5,
                                     "_step_masked_impl": 0.01},
                        "window_s": 1.0},
            "steps": steps, "sz": sz, "mod": mod, "mon": sz["monitor"],
            "work": work, "peaks": peaks_for("TPU v5 lite"), "chips": chips}


def test_shares_of_a_peak_are_over_every_chip():
    """The same work and device time on 4 chips reads a quarter of the
    share on one."""
    one, four = _ctx(1), _ctx(4)
    r1 = layers.roofline(one, layers.CATCHUP, layers.catchup_work(one))
    r4 = layers.roofline(four, layers.CATCHUP, layers.catchup_work(four))
    assert r1[0] / r4[0] == pytest.approx(4.0, rel=1e-12)
    assert r1[1] == r4[1]
    e1 = layers.roofline(one, layers.EDGE, layers.edge_work(one))
    e4 = layers.roofline(four, layers.EDGE, layers.edge_work(four))
    assert e1[0] / e4[0] == pytest.approx(4.0, rel=1e-12)
    assert layers.step_mfu(one) / layers.step_mfu(four) == \
        pytest.approx(4.0, rel=1e-12)


# -- the counts over a hand-built four-device reduction ---------------------

def _call(rounds, rows):
    return {"rounds": rounds, "shapes": Counter({(rows, HIDDEN): 5,
                                                 (rows, 2 * HIDDEN): 9})}


def _four(rounds=(3, 2), rows=2):
    """Two catch-up calls of 3 and 2 rounds over 8 rows, 2 on each of 4
    devices, the spans reporting the whole batch."""
    return {"catchups": [{"rounds": 3, "rows": 8, "replayed": 6},
                         {"rounds": 2, "rows": 8, "replayed": 3}],
            "devices": [{"calls": [_call(k, rows) for k in rounds],
                         "runs": len(rounds)} for _ in range(4)]}


def test_counts_sum_rows_over_devices():
    c = program_spans.counts(_four(), HIDDEN)
    assert c["devices"] == 4 and c["calls"] == 2 and c["rounds"] == 5
    assert c["by_call"] == [{"rounds": 3, "rows": [2, 2, 2, 2]},
                            {"rounds": 2, "rows": [2, 2, 2, 2]}]
    assert c["rows_decoded"] == 3 * 8 + 2 * 8
    assert c["replayed"] == 9
    assert program_spans.useful_row_share(
        dict(_four(), loop_s=1.0, catchup_s=2.0), HIDDEN) == \
        pytest.approx(100.0 * 9 / 40)


def test_counts_read_nothing_where_devices_disagree():
    r = _four()
    r["devices"][2]["calls"][0] = _call(4, 2)     # one device ran 4 rounds
    assert program_spans.counts(r, HIDDEN) is None
    r = _four()
    r["devices"][1]["calls"][1] = _call(2, 3)     # 9 rows in all, not 8
    assert program_spans.counts(r, HIDDEN) is None
    r = _four()
    r["devices"][3] = {"calls": r["devices"][3]["calls"][:1], "runs": 1}
    assert program_spans.counts(r, HIDDEN) is None


def test_shard_idle_rounds_by_hand():
    """Call 1: the devices' own rows need 3, 2, 1 and 0 rounds of the 3
    run (6 of 12 idle); call 2: 1, 0, 0 and 2 of 2 (5 of 8)."""
    steps = [{"n": 8, "replays": [(0, 5, 7), (3, 6, 7), (5, 7, 7)]},
             {"n": 8, "replays": []},
             {"n": 8, "replays": [(1, 9, 9), (7, 8, 9)]}]
    value, extra = program_spans.shard_idle_rounds(_four(), HIDDEN, steps)
    assert value == pytest.approx(100.0 * 11 / 20)
    assert extra == {"rounds": 2.5, "needed": pytest.approx(9 / 8)}
    # a call whose longest backlog is not the rounds the devices ran
    late = [dict(steps[0], replays=[(0, 6, 7)])] + steps[1:]
    assert program_spans.shard_idle_rounds(_four(), HIDDEN, late) is None
    # more calls than steps with a replay
    assert program_spans.shard_idle_rounds(_four(), HIDDEN,
                                           steps[:2]) is None


def test_shard_idle_rounds_is_zero_on_one_device():
    """On one device the loop runs to its own rows' longest backlog."""
    r = program_spans.load(CATCHUP)
    c = program_spans.counts(r, HIDDEN)
    steps, k = [], 0
    for span in r["catchups"]:                  # one step per call
        steps.append({"n": 4, "replays": [(0, k, k + span["rounds"] - 1)]})
        k += span["rounds"]
    value, extra = program_spans.shard_idle_rounds(r, HIDDEN, steps)
    assert value == 0.0
    assert extra["rounds"] == extra["needed"] == c["rounds"] / c["calls"]


# -- reduce over four device planes -----------------------------------------

def _ev(ev, name=None):
    return types.SimpleNamespace(name=ev.name if name is None else name,
                                 start_ns=ev.start_ns,
                                 duration_ns=ev.duration_ns, stats=ev.stats)


def _four_planes(data, order=(3, 1, 0, 2)):
    """The recorded one-device trace as four devices, each holding one of
    its 4 rows (``[4,64]`` operations become ``[1,64]``), the planes out
    of order."""
    planes = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            planes.append(plane)
            continue
        lines = [types.SimpleNamespace(name=ln.name, events=[
            _ev(ev, ev.name.replace("[4,64]", "[1,64]"))
            for ev in ln.events]) for ln in plane.lines]
        planes += [types.SimpleNamespace(name=f"/device:TPU:{i}", lines=lines)
                   for i in order]
    return types.SimpleNamespace(planes=planes)


def test_reduce_reads_every_device_plane():
    data = trace_reduce.load(CATCHUP)
    one = program_spans.reduce(data)
    four = program_spans.reduce(_four_planes(data))
    assert len(four["devices"]) == 4
    c1, c4 = (program_spans.counts(one, HIDDEN),
              program_spans.counts(four, HIDDEN))
    assert c4["rounds"] == c1["rounds"] and c4["calls"] == c1["calls"]
    assert c4["rows_decoded"] == c1["rows_decoded"]
    assert all(b["rows"] == [1, 1, 1, 1] for b in c4["by_call"])
    for key in ("catchup_s", "loop_s", "idle_s"):
        assert four[key] == pytest.approx(one[key], rel=1e-12)
    assert four["idle_by_span"] == pytest.approx(one["idle_by_span"])
    assert four["loop_ops"] == pytest.approx(dict(one["loop_ops"]))
    assert program_spans.ms_per_round(four, HIDDEN) == pytest.approx(
        program_spans.ms_per_round(one, HIDDEN), rel=1e-12)
    # the spans report the whole batch, 4 rows: one device's plane alone
    # holds a quarter of it
    only = _four_planes(data, order=(0,))
    assert program_spans.counts(program_spans.reduce(only), HIDDEN) is None
