"""Traffic: seeded token streams and the schedule on which their ticks are
issued.  One general generator reads every mix's parameter file
(``bench/traffic/<name>.json``).

A stream is a session of ``max_len`` tokens.  The pool is one cohort:
every slot holds a stream of the same age, and when the cohort reaches
``max_len`` all slots detach and a fresh seeded cohort attaches.  Cohort
``c`` of seed ``s`` is always the same tokens, so two runs of one seed
serve the same streams.

The token stream is a copy of the repository's Zipf/bigram generator
(``src/repro/data/tokens.py``): the benchmark owns its inputs.

Which tokens trigger is part of the mix too, drawn once from the mix's own
``pattern_seed``: either each (token, stream) of a cohort triggers with
the mix's ``trigger_rate``, or each stream triggers after gaps drawn
uniformly from the mix's ``trigger_gap`` ``[lo, hi]`` tokens, its first
trigger after one such gap, so every backlog is ``lo`` to ``hi`` tokens
from the cohort's first tick on.  A run's seed only permutes which stream
gets which column of the pattern.  The catch-up's work follows the
pattern (a round per token of the longest triggered backlog), so every
seed gets the same work, tick by tick, in another order of streams: the
seeds do not vary the trigger trace.  With random weights the edge tower's u
carries no meaning to set the pattern: it drifts with the position and
differs from stream to stream, and a threshold read off it gave each seed
a different amount of work.  The benchmark sets each stream's trigger
point so that u (finite, checked against the reference) crosses it
exactly where the pattern says.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# purposes a seed is split into; cohort c of the window uses COHORT + c,
# warm-up cohort j WARM + j
WEIGHTS, WARM, COHORT, PERMUTE = 0, 1 << 20, 1 << 21, 1 << 22
# trigger points that u never reaches / always passes
NEVER, ALWAYS = 1e9, -1e9


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        t = json.load(f)
    if t["loop"] not in ("closed", "open"):
        raise ValueError(f"traffic {name}: loop must be closed or open")
    if t["loop"] == "open" and not t.get("tick_rate"):
        raise ValueError(f"traffic {name}: an open loop needs tick_rate")
    if ("trigger_rate" in t) == ("trigger_gap" in t):
        raise ValueError(f"traffic {name}: give trigger_rate or trigger_gap")
    return t


def rng_for(seed: int, purpose: int) -> np.random.Generator:
    """An independent generator per (seed, purpose); any size of seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), purpose]))


def jax_seed(seed: int, purpose: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey`` from (seed, purpose)."""
    st = np.random.SeedSequence([int(seed), purpose]).generate_state(1)
    return int(st[0]) & 0x7FFFFFFF


def zipf_tokens(rng: np.random.Generator, shape, vocab: int,
                a: float = 1.2) -> np.ndarray:
    """Zipf-ish token ids in [0, vocab) via inverse-CDF on a power law."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** (-a)
    probs /= probs.sum()
    return rng.choice(vocab, size=shape, p=probs).astype(np.int32)


def markov_stream(rng: np.random.Generator, batch: int, seq: int, vocab: int,
                  order_mix: float = 0.5, a: float = 1.2) -> np.ndarray:
    """Mix of Zipf draws and a deterministic bigram successor
    (t+1 = 7t+3 mod V)."""
    base = zipf_tokens(rng, (batch, seq), vocab, a)
    succ = (7 * base[:, :-1] + 3) % vocab
    use_succ = rng.uniform(size=(batch, seq - 1)) < order_mix
    out = base.copy()
    out[:, 1:] = np.where(use_succ, succ, base[:, 1:])
    return out


def streams(traffic: dict, vocab: int, seed: int,
            purpose: int) -> np.ndarray:
    """(streams, max_len) token ids for one cohort."""
    return markov_stream(rng_for(seed, purpose), traffic["streams"],
                         traffic["max_len"], vocab, traffic["order_mix"],
                         traffic["zipf_a"])


def triggers(traffic: dict, seed: int, purpose: int) -> np.ndarray:
    """(max_len, streams) bool: where the cohort ``purpose`` triggers.  The
    pattern is the mix's own; the seed permutes its columns."""
    B, L = traffic["streams"], traffic["max_len"]
    rng = rng_for(traffic["pattern_seed"], purpose)
    if "trigger_rate" in traffic:
        base = rng.random((L, B)) < traffic["trigger_rate"]
    else:
        lo, hi = traffic["trigger_gap"]
        gaps = rng.integers(lo, hi + 1, size=(L // lo + 1, B))
        at = np.cumsum(gaps, axis=0) - 1         # a gap of g: backlog g
        base = np.zeros((L, B), bool)
        for b in range(B):
            base[at[at[:, b] < L, b], b] = True
    return base[:, rng_for(seed, PERMUTE + purpose).permutation(B)]


def trigger_points(pattern_t: np.ndarray) -> np.ndarray:
    """(B,) float32 trigger points that make exactly ``pattern_t`` trigger."""
    return np.where(pattern_t, np.float32(ALWAYS), np.float32(NEVER))


class Schedule:
    """When each tick is due.  Closed loop: a tick is due when it is
    issued (the previous step has returned).  Open loop: tick k is due at
    ``t0 + k / tick_rate`` whatever the system does; a late tick is issued
    at once and its latency counts from its due time."""

    def __init__(self, traffic: dict, t0: float):
        self.open = traffic["loop"] == "open"
        self.period = 1.0 / traffic["tick_rate"] if self.open else 0.0
        self.t0 = t0

    def due(self, k: int, now: float) -> float:
        return self.t0 + k * self.period if self.open else now
