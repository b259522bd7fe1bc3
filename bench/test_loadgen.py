"""The traffic generator: a gap mix keeps every backlog inside its gaps
from the cohort's first tick, every seed gets the same per-tick work, and
the knee rule reads growth of lateness over the window."""
import numpy as np
import pytest

import knee
import loadgen


def _backlogs(pattern):
    """Per tick, the backlogs of the rows that trigger there."""
    server = np.zeros(pattern.shape[1], int)
    out = []
    for pos, row in enumerate(pattern):
        out.append(sorted(pos - server[row] + 1))
        server[row] = pos + 1
    return out


def test_gap_mix_bounds_every_backlog():
    t = loadgen.load_traffic("sparse")
    lo, hi = t["trigger_gap"]
    p = loadgen.triggers(t, 2**31 + 5, loadgen.COHORT)
    assert p.shape == (t["max_len"], t["streams"])
    every = [b for bl in _backlogs(p) for b in bl]
    assert min(every) >= lo and max(every) <= hi
    rate = p.mean()
    assert abs(rate - 2 / (lo + hi)) < 0.005


@pytest.mark.parametrize("traffic", ["storm", "sparse"])
def test_seeds_permute_the_same_work(traffic):
    t = loadgen.load_traffic(traffic)
    a = loadgen.triggers(t, 3, loadgen.COHORT)
    b = loadgen.triggers(t, 2**31 + 11, loadgen.COHORT)
    assert not (a == b).all()
    assert _backlogs(a) == _backlogs(b)


def test_a_mix_names_one_trigger_law(tmp_path, monkeypatch):
    t = loadgen.load_traffic("sparse")
    t["trigger_rate"] = 0.05
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "both.json").write_text(__import__("json").dumps(t))
    monkeypatch.setattr(loadgen, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="trigger_rate or trigger_gap"):
        loadgen.load_traffic("both")


def test_knee_is_the_last_rate_before_growth():
    swept = [(1.0, [0.0, 1.0, 2.0]),        # period 1000 ms: flat
             (2.0, [5.0, 8.0, 40.0]),       # 500 ms: +35 ms, flat
             (3.0, [10.0, 90.0, 400.0]),    # 333 ms: grows
             (3.5, [0.0, 0.0, 1.0])]        # flat again, above a growth
    assert knee.knee(swept) == 2.0
    assert knee.knee(swept[2:3]) is None
