"""The comparison that decides ``correct``: what the timed window produced,
against the plain float32 reference (``bench/reference.py`` and the
configuration's server tower), once the window has closed.

Each step of the window gave, for every attached stream, u (the edge
tower's score), the trigger decision, and fhat (u, or u - s*sigma(v) where
the stream triggered and the server tower caught up on its backlog).  The
reference runs each stream's tokens through both towers in one causal
pass, so its v at a position is what a catch-up that replayed the backlog
correctly must give there.  Numbers compared:

- ``u_rms`` / ``u_max``: the gap of u from the reference's, rms and
  largest, over every token of the window, as a share of rms(u_ref);
- ``v_rms`` / ``v_max``: the same for v at every triggered token, v read
  back from the corrector the session applied,
  v = logit((u - fhat) / s);
- ``trigger_wrong``: tokens whose trigger decision is not u > the
  stream's trigger point at that step;
- ``fhat_wrong``: untriggered tokens whose fhat is not u;
- ``fhat_above_u``: tokens with fhat > u (the safety property).
"""
from __future__ import annotations

import jax
import numpy as np

import reference as ref

# Limits, each between the largest reading of sound runs over a dozen seeds
# and more and the smallest reading of the control (the reference on
# weights rounded to fp8 e4m3, the precision below the configuration's
# bfloat16); the readings are in PERF.md.
LIMITS = {"u_rms": 2.0 ** -4, "u_max": 2.0 ** -2,
          "v_rms": 0.08, "v_max": 2.0 ** -2,
          "trigger_wrong": 0, "fhat_wrong": 0, "fhat_above_u": 0}
T_BUCKET = 512
ROWS = 8


def _rms(x) -> float:
    return float(np.sqrt(np.mean(np.square(np.asarray(x, np.float64)))))


def _gap(got, want):
    want = np.asarray(want, np.float64)
    err = np.asarray(got, np.float64) - want
    scale = _rms(want)
    return _rms(err) / scale, float(np.max(np.abs(err))) / scale


class Reference:
    """The plain reference of one configuration, its programs jitted once:
    u and v (B, T) over tokens (B, T), a block of streams at a time,
    every matrix product at Precision.HIGHEST."""

    def __init__(self, sz, mod):
        mon = sz["monitor"]
        self._u = jax.jit(lambda p, t: ref.edge_u(p, t, mon))
        self._v = jax.jit(lambda p, t: ref.v_head(
            p, mod.server_hidden(p, t, sz)))

    def u(self, params, tokens):
        return ref.by_blocks(lambda t: self._u(params, t), [tokens], ROWS)

    def uv(self, params, tokens):
        return (self.u(params, tokens),
                ref.by_blocks(lambda t: self._v(params, t), [tokens], ROWS))


def padded(n: int, max_len: int) -> int:
    return min(-(-n // T_BUCKET) * T_BUCKET, max_len)


def v_from(u, fhat, s):
    c = np.clip((np.asarray(u, np.float64) - fhat) / s, 1e-12, 1 - 1e-12)
    return np.log(c) - np.log1p(-c)


def numbers(u, v, u_ref, v_ref, trig):
    """The four toleranced numbers; v and v_ref at triggered cells only."""
    u_rms, u_max = _gap(u, u_ref)
    v_rms, v_max = _gap(v, v_ref) if trig.any() else (0.0, 0.0)
    return {"u_rms": u_rms, "u_max": u_max, "v_rms": v_rms, "v_max": v_max}


def compare(reference, params, sz, traffic, steps, u, fhat, trig, tau,
            cohort_tokens) -> dict:
    """Every number compared, each with its limit.  u, fhat, trig and the
    trigger points tau are (steps, B) as the window had them."""
    s = sz["monitor"]["s"]
    exact = {"trigger_wrong": int(np.sum(trig != (u > tau))),
             "fhat_wrong": int(np.sum(~trig & (fhat != u))),
             "fhat_above_u": int(np.sum(fhat > u))}
    cohort = np.asarray([st["cohort"] for st in steps])
    pos = np.asarray([st["pos"] for st in steps])
    u_ref = np.empty_like(u, np.float64)
    v_ref = np.empty_like(u, np.float64)
    for c in np.unique(cohort):
        rows = np.flatnonzero(cohort == c)
        T = int(pos[rows].max()) + 1
        toks = cohort_tokens(int(c))[:, :padded(T, traffic["max_len"])]
        ur, vr = reference.uv(params, toks)
        u_ref[rows] = ur[:, pos[rows]].T
        v_ref[rows] = vr[:, pos[rows]].T
    got = numbers(u, v_from(u, fhat, s)[trig], u_ref, v_ref[trig], trig)
    got.update(exact)
    return {k: {"value": got[k], "limit": LIMITS[k]} for k in LIMITS}


def control(reference, params, tokens, trig):
    """The control's numbers: the reference on fp8-rounded weights put in
    the program's place, over the same tokens (B, T), against the
    reference; v at the tokens that trigger, ``trig`` (B, T)."""
    u_ref, v_ref = reference.uv(params, tokens)
    lo = ref.round_weights(params)
    u_c, v_c = reference.uv(lo, tokens)
    del lo
    return numbers(u_c, v_c[trig], u_ref, v_ref[trig], trig)
