"""jit'd dispatch wrappers: one call site per kernel, selecting between the
Pallas TPU kernel and the XLA path.  The model code takes these as its
``attn_fn`` / ``scan_fn`` injection points.

Selection.  With no override, ``monitor_combine`` follows the platform:
the compiled Pallas kernel on a TPU, the XLA path elsewhere.  The
attention and SSD kernels keep the XLA path until they are chosen by
platform too (ROADMAP A4).  ``set_impl`` overrides every op at once:

  * ``"xla"``              — the XLA path (the dry-run keeps it so
                             Pallas lowering does not pollute the roofline);
  * ``"pallas"``           — the compiled kernel; raises off a TPU rather
                             than silently interpreting;
  * ``"pallas_interpret"`` — the Pallas interpreter, the only way interpret
                             mode runs (kernel tests on the CPU);
  * ``None``               — back to the per-op platform default.
"""
from __future__ import annotations

from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dk
from repro.kernels import flash_attention as _fk
from repro.kernels import monitor_combine as _mk
from repro.kernels import ssm_scan as _sk
from repro.nn.attention import chunked_attention as _xla_attention
from repro.nn.attention import decode_attention as _xla_decode

IMPLS = ("xla", "pallas", "pallas_interpret")

_IMPL: Optional[str] = None  # None: each op's platform default


def set_impl(impl: Optional[Literal["xla", "pallas", "pallas_interpret"]]
             ) -> None:
    global _IMPL
    if impl is not None and impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}: valid are {IMPLS} "
                         "or None (platform default)")
    _IMPL = impl


def get_impl() -> str:
    """The impl the attention / SSD ops use (XLA unless overridden)."""
    return _IMPL or "xla"


def combine_impl() -> str:
    """The impl ``monitor_combine`` uses: the override, else Pallas on a
    TPU and XLA elsewhere."""
    if _IMPL is not None:
        return _IMPL
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _interp(impl: str) -> bool:
    if impl == "pallas_interpret":
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"kernel impl 'pallas' compiles for a TPU, and the default "
            f"backend is {jax.default_backend()!r}: use 'pallas_interpret' "
            "to run the Pallas interpreter, or 'xla'")
    return False


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0, **kw):
    impl = get_impl()
    if impl == "xla":
        return _xla_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    return _fk.flash_attention(q, k, v, causal=causal, window=window,
                               interpret=_interp(impl), **kw)


def decode_attention(q, k_cache, v_cache, pos, *, window=0, **kw):
    impl = get_impl()
    if impl == "xla":
        return _xla_decode(q, k_cache, v_cache, pos, window=window)
    return _dk.decode_attention(q, k_cache, v_cache, pos, window=window,
                                interpret=_interp(impl), **kw)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128, h0=None):
    """Signature-compatible with nn.ssm.ssd_chunked (the XLA path)."""
    from repro.nn.ssm import ssd_chunked
    impl = get_impl()
    if impl == "xla":
        return ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk, h0=h0)
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    la = dt.astype(jnp.float32) * A[None, None, :]
    y = _sk.ssd_scan(xdt, la, Bm, Cm, chunk=chunk, interpret=_interp(impl))
    return y, None  # kernel path does not export the final state


def monitor_combine(u, v, f, *, s, threshold=0.0, margin=0.25):
    impl = combine_impl()
    if impl == "xla":
        from repro.kernels.ref import monitor_combine_ref
        return monitor_combine_ref(u, v, f, s=s, threshold=threshold,
                                   margin=margin)
    return _mk.monitor_combine(u, v, f, s=s, threshold=threshold,
                               margin=margin, interpret=_interp(impl))
