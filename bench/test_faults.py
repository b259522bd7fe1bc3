"""A whole run at a small size on the CPU, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken underneath,
``correct`` comes out false, once for each fault the cells can have."""
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import tiny_overrides

CELLS = {"granite-8b.storm": "granite-8b-chip",
         "zamba2-7b.storm": "zamba2-7b-chip",
         "granite-8b.sparse": "granite-8b-chip"}


def _run(workload, patch=None, seed=2**31 + 7, keep_steps=False):
    return harness.run_cell(workload, seed, 30.0, False, allow_cpu=True,
                            overrides=tiny_overrides(CELLS[workload]),
                            max_steps=30, patch=patch, keep_steps=keep_steps)


def _state_unchanged(eng):
    """The catch-up returns the server cache it was given."""
    fn = eng._catchup

    def broken(params, cache, *a):
        _, v, fhat = fn(params, cache, *a)
        return cache, v, fhat
    eng._catchup = broken


def _half_batch(eng):
    """Only the first half of the triggered rows are caught up."""
    fn = eng._catchup

    def broken(params, cache, history, server_pos, t, triggered, u):
        half = jnp.arange(triggered.shape[0]) < triggered.shape[0] // 2
        return fn(params, cache, history, server_pos, t, triggered & half, u)
    eng._catchup = broken


def _token_altered(eng):
    """The token recorded for stream 0 is not the one that arrived."""
    fn = eng._record_at

    def broken(history, tokens_t, pos, active):
        return fn(history, tokens_t.at[0].add(1), pos, active)
    eng._record_at = broken


def _answer_altered(eng):
    """Stream 0's corrected fhat is off by a hundredth of s."""
    fn = eng._catchup

    def broken(*a):
        cache, v, fhat = fn(*a)
        return cache, v, fhat.at[0].add(-0.002)
    eng._catchup = broken


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(workload):
    r = _run(workload, keep_steps=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 30 * 4 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    # the trigger decision (the program's edge.trigger span) lies inside
    # its step, on the harness's clock
    assert all(s["issue"] <= s["decide"] <= s["ret"] for s in r["steps"])
    assert any(s["replays"] for s in r["steps"])


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered, _answer_altered])
@pytest.mark.parametrize("workload", ["granite-8b.storm", "zamba2-7b.storm"])
def test_fault_is_caught(workload, fault):
    r = _run(workload, patch=fault)
    assert not r["correct"], r["checks"]
