"""Pallas fused monitor combine — the paper-central op, fused:

    fhat = u - s * sigmoid(v)
    mask = u > gamma - margin          (server-trigger mask)
    fp/fn indicator accumulators       (safety telemetry, Eq. 3/4)

On a (B, S) score grid during batched serving this is 3-4 elementwise HBM
round-trips if left to XLA fusion across jit boundaries; one VMEM pass here.

TPU legality: flat (N,) score vectors are reshaped to 2D (rows, 128) tiles
(the VPU lane width; f32 tiles are (8, 128)), padded with "quiet" values
(u = gamma - margin, so the padding neither triggers nor counts as a safety
violation).  The [n_triggered, n_violations] counters accumulate across the
sequential TPU grid in SMEM.  The kernel compiles for a TPU; the Pallas
interpreter runs only when a caller passes ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128      # VPU lane width: last dim of every block
SUBLANES = 8     # f32 min sublane tile


def _combine_kernel(u_ref, v_ref, f_ref, fhat_ref, mask_ref, count_ref, *,
                    s: float, threshold: float, margin: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        count_ref[0] = 0.0
        count_ref[1] = 0.0

    u = u_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    f = f_ref[...].astype(jnp.float32)
    corr = s * jax.nn.sigmoid(v)
    fhat = u - corr
    trig = (u > threshold - margin).astype(jnp.float32)
    fhat_ref[...] = fhat
    mask_ref[...] = trig
    viol = (f > u).astype(jnp.float32)  # safety violations u < f
    count_ref[0] += jnp.sum(trig)
    count_ref[1] += jnp.sum(viol)


def monitor_combine(u: jnp.ndarray, v: jnp.ndarray, f: jnp.ndarray, *,
                    s: float, threshold: float = 0.0, margin: float = 0.25,
                    block: int = 1024, interpret: bool = False):
    """u, v, f: (N,) flat score vectors -> (fhat, mask, counts[2]).

    ``block`` is the number of lanes processed per grid step (rounded to a
    TPU-legal (rows, 128) tile).  ``interpret=True`` runs the Pallas
    interpreter instead of compiling for a TPU.
    """
    N = u.shape[0]
    rows = max(block // LANES, 1)
    if not interpret:
        rows = max(rows, SUBLANES)  # compiled path: full f32 tile
    tile = rows * LANES
    n_pad = (-N) % tile
    quiet = jnp.float32(threshold - margin)  # no trigger, no violation
    uf = jnp.concatenate([u.astype(jnp.float32), jnp.full((n_pad,), quiet)]) \
        if n_pad else u.astype(jnp.float32)
    vf = jnp.concatenate([v.astype(jnp.float32), jnp.zeros((n_pad,))]) \
        if n_pad else v.astype(jnp.float32)
    ff = jnp.concatenate([f.astype(jnp.float32), jnp.full((n_pad,), quiet)]) \
        if n_pad else f.astype(jnp.float32)
    n_rows_total = (N + n_pad) // LANES
    u2, v2, f2 = (x.reshape(n_rows_total, LANES) for x in (uf, vf, ff))
    nb = n_rows_total // rows
    kernel = functools.partial(_combine_kernel, s=s, threshold=threshold,
                               margin=margin)
    blk2 = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    fhat, mask, counts = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[blk2, blk2, blk2],
        out_specs=[blk2, blk2,
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((n_rows_total, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((n_rows_total, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((2,), jnp.float32)],
        interpret=interpret,
    )(u2, v2, f2)
    return fhat.reshape(-1)[:N], mask.reshape(-1)[:N], counts
