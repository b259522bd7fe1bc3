"""The control fails the comparison: the reference on weights rounded to
fp8 e4m3, in the program's place, exceeds a limit of every cell's
configuration (here at a small size on the CPU; the readings on the chip
at the cells' own sizes are in PERF.md)."""
import pytest

import check
import control
from conftest import tiny_overrides


@pytest.mark.parametrize("workload,config", [
    ("granite-8b.storm", "granite-8b-chip"),
    ("zamba2-7b.storm", "zamba2-7b-chip"),
    ("granite-8b.storm.data4", "granite-8b-chip")])
@pytest.mark.parametrize("seed", [3, 2**31 + 1, 5_000_000_019])
def test_control_fails(workload, config, seed):
    got = control.readings(workload, seed, 24, allow_cpu=True,
                           overrides=tiny_overrides(config))
    over = [k for k in got["limits"] if got[k] > check.LIMITS[k]]
    assert over, got
