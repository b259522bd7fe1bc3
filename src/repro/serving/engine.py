"""Serving engine: batched autoregressive decode over the uniform backbone
API, with greedy/temperature sampling.  Prefill is cache-building: prompt
tokens are scanned through ``decode_step`` (shape-static, jit-once).

Besides the uniform-position ``decode``, the engine exposes a PER-ELEMENT
decode (``decode_at`` / ``step_at_fn``): every batch element carries its own
cache position and an active mask, so independent streams at heterogeneous
depths advance in one SPMD call (inactive elements' cache rows are left
bit-untouched).  This is the primitive the collaborative serving protocol
uses for per-stream server catch-up.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import api as model_api


def cache_batch_axes(cfg: ArchConfig, batch: int, max_len: int):
    """Pytree of ints: the batch axis of every cache leaf (structural
    discovery — no family-specific layout knowledge).

    Cache layouts differ per family: transformers stack (layers, B, S,
    heads, d); hybrids stack doubly (super-blocks x inner layers) and mix
    KV pages with SSM state rows; xLSTM carries (layers, B, heads, d, d)
    matrix memories with no sequence axis at all.  Rather than teach this
    module every layout, the batch axis is found structurally: build the
    cache tree twice under ``jax.eval_shape`` (abstract — no allocation) at
    batch sizes ``batch`` and ``batch+1``, and for each leaf take the FIRST
    axis whose extent differs.  Probing with a delta of exactly 1 makes the
    discovery unambiguous even when a leaf's other axes happen to equal the
    batch size (e.g. batch == n_heads): those axes don't grow.

    A leaf with no differing axis (per-layer scalars broadcast over the
    batch) raises — such a leaf has no rows to mask or shard.

    The result is the axis map of the row selects in ``zero_cache_rows``
    and ``ServeEngine._step_masked_impl``.
    """
    a = jax.eval_shape(lambda: model_api.init_cache(cfg, batch, max_len))
    b = jax.eval_shape(lambda: model_api.init_cache(cfg, batch + 1, max_len))

    def find(x, y):
        for i, (p, q) in enumerate(zip(x.shape, y.shape)):
            if p != q:
                return i
        raise ValueError(f"no batch axis in cache leaf {x.shape}")

    return jax.tree.map(find, a, b)


def zero_cache_rows(cache, axes, rows: jnp.ndarray, *, shardings=None):
    """Zero the selected batch rows of every cache leaf.

    ``rows``: (B,) bool mask along each leaf's discovered batch axis
    (``cache_batch_axes``).  Used when a slot is re-leased to a new
    stream (correction-server session turnover, ``MonitorSession``
    attach): the new tenant must see bit-cold cache rows, exactly as if
    the cache had just been built, while co-resident rows stay
    bit-untouched.

    ``shardings``: a NamedSharding tree matching ``cache`` — SPEC-AWARE
    reset for mesh-sharded caches (``serving/mesh.py``).  The select is
    elementwise, so each device only ever rewrites its own rows; the
    explicit re-placement pins the result to the input shardings so a
    reset can never silently gather a super-batch cache onto one device
    (the eager-mode default when sharding propagation loses the
    committed placement).  Asserted in tests/test_mesh.py.
    """
    rows = jnp.asarray(rows, bool)

    def z(a, ax):
        shape = [1] * a.ndim
        shape[ax] = rows.shape[0]
        return jnp.where(jnp.reshape(rows, shape), jnp.zeros((), a.dtype), a)

    out = jax.tree.map(z, cache, axes)
    if shardings is not None:
        out = jax.tree.map(jax.device_put, out, shardings)
    return out


def make_step_at(cfg: ArchConfig, *, with_logits: bool = True):
    """Pure per-element decode step with vector positions and active mask.

    Returns ``step_at(params, cache, tokens_t, pos, active)`` where
    tokens_t: (B,[K]), pos: (B,) int32 per-element positions, active: (B,)
    bool.  This is the primitive the collaborative protocol builds on:
    independent streams at heterogeneous cache depths advance in ONE
    shape-static SPMD call.

    * every element is DECODED (dense, discarded compute — the standard
      SPMD masked-semantics trick; there is no data-dependent shape, so
      the function is jit/scan/fori_loop-safe and compiles once);
    * each element decodes at its own position as a batch of one
      (``nn.module.rowwise``), so its numerics do not depend on the batch
      or its sharding, while the cache is read and written in place, one
      slot a row (``nn.attention.write_slot``);
    * ``active`` is the row mask of those cache writes
      (``model_api.decode_step``): an inactive element's cache rows come
      back bit-unchanged, and ``hidden[i]`` for it is garbage — callers
      gate on ``active`` before use, as the collaborative catch-up loop
      does;
    * ``pos`` is NOT validated here: an active element's position lies in
      [0, max_len); an inactive lane may carry any dummy position (its
      slot index is clamped and the slot written back unchanged, see
      ``collaborative.CollaborativeEngine._catchup_impl``).

    ``with_logits=False`` skips the unembed projection (monitoring-only
    decode — the protocol consumes hidden scores, not next-token logits).
    """

    def step_at(params, cache, tokens_t, pos, active):
        return model_api.decode_step(
            params, cfg, cache, tokens_t, jnp.asarray(pos, jnp.int32),
            active=jnp.asarray(active, bool), with_logits=with_logits)

    return step_at


class ServeEngine:
    """Holds params + cache for one batched decode session."""

    def __init__(self, params, cfg: ArchConfig, batch: int, max_len: int,
                 rng: Optional[jax.Array] = None):
        self.params, self.cfg = params, cfg
        self.batch, self.max_len = batch, max_len
        self.cache = model_api.init_cache(cfg, batch, max_len)
        self.pos = 0
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._step = jax.jit(self._step_impl)
        self._step_masked = jax.jit(self._step_masked_impl)
        self._prefill = jax.jit(self._prefill_impl)
        self._step_at = {}  # built lazily (per-element decode), per variant
        self._axes = None   # cache_batch_axes, built lazily
        # NamedSharding tree for the cache when the engine is mesh-sharded
        # (set by serving.mesh.shard_engine); row resets preserve it
        self._cache_shardings = None

    @property
    def axes(self):
        """Batch-axis tree of the cache leaves (``cache_batch_axes``)."""
        if self._axes is None:
            self._axes = cache_batch_axes(self.cfg, self.batch, self.max_len)
        return self._axes

    def jitted_paths(self):
        """Name -> jit wrapper for every jitted path this engine drives —
        the watch list for ``analysis.recompile.RecompileGuard`` (each
        must compile exactly once per shape signature)."""
        paths = {"step": self._step, "step_masked": self._step_masked,
                 "prefill": self._prefill}
        for variant, fn in self._step_at.items():
            paths[f"step_at[with_logits={variant}]"] = fn
        return paths

    # -- jitted kernels ----------------------------------------------------
    def _step_impl(self, params, cache, tokens, pos):
        return model_api.decode_step(params, self.cfg, cache, tokens, pos)

    def _step_masked_impl(self, params, cache, tokens, pos, mask):
        """Dense decode at one scalar position with a batch mask: every
        element is decoded (discarded compute), but elements with
        ``mask[i] == False`` get their cache rows back bit-unchanged.

        Unlike ``make_step_at`` (each row decoded alone as a batch of one,
        which rounds differently from the batched matmul), this is the SAME dense
        ``decode_step`` subgraph with a leafwise select epilogue — masked
        rows are bitwise identical to the plain batched ``decode``
        (asserted in tests).  It is the cohort primitive the
        ``MonitorSession`` slot pool uses: streams admitted at different
        times share one engine by decoding each same-position cohort in
        one dense masked call.
        """
        logits, hidden, new_cache = model_api.decode_step(
            params, self.cfg, cache, tokens, pos)

        def merge(new, old, ax):
            shape = [1] * new.ndim
            shape[ax] = mask.shape[0]
            return jnp.where(jnp.reshape(mask, shape), new, old)

        cache = jax.tree.map(merge, new_cache, cache, self.axes)
        return logits, hidden, cache

    def _prefill_impl(self, params, cache, tokens, pos0):
        """tokens: (B, S0) (or (B,S0,K) audio); scans decode_step over S0."""
        time_axis = 1

        def body(carry, tok_t):
            cache, pos = carry
            logits, hidden, cache = model_api.decode_step(
                params, self.cfg, cache, tok_t, pos)
            return (cache, pos + 1), (logits, hidden)

        toks = jnp.moveaxis(tokens, time_axis, 0)
        (cache, pos), (logits, hidden) = jax.lax.scan(body, (cache, pos0), toks)
        return cache, pos, logits[-1], jnp.moveaxis(hidden, 0, 1)

    # -- public API ----------------------------------------------------------
    def prefill(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """Feed the prompt; returns last-position logits."""
        self.cache, pos, logits, _ = self._prefill(
            self.params, self.cache, tokens, jnp.asarray(self.pos, jnp.int32))
        self.pos = int(pos)
        return logits

    def decode(self, tokens_t: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """One step; returns (logits, hidden)."""
        logits, hidden, self.cache = self._step(
            self.params, self.cache, tokens_t, jnp.asarray(self.pos, jnp.int32))
        self.pos += 1
        return logits, hidden

    def decode_masked(self, tokens_t: jnp.ndarray, pos: int,
                      mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """One dense decode at scalar ``pos`` where only ``mask`` rows
        commit their cache writes (masked-out rows bit-untouched; their
        logits/hidden are garbage — callers gate on ``mask``).  The
        engine's scalar ``self.pos`` is NOT advanced: cohort callers
        (``MonitorSession``) track per-slot positions themselves."""
        logits, hidden, self.cache = self._step_masked(
            self.params, self.cache, tokens_t, jnp.asarray(pos, jnp.int32),
            jnp.asarray(mask, bool))
        return logits, hidden

    def zero_rows(self, rows) -> None:
        """Reset the selected batch rows of the cache to bit-cold zeros
        (``rows``: (B,) bool).  Slot-pool hygiene: a re-leased slot must
        start exactly as a fresh engine would.  On a mesh-sharded engine
        the reset preserves the cache placement (spec-aware)."""
        self.cache = zero_cache_rows(self.cache, self.axes,
                                     jnp.asarray(rows, bool),
                                     shardings=self._cache_shardings)

    def get_step_at(self, with_logits: bool = True) -> Callable:
        """Pure per-element decode fn (params, cache, tokens, pos(B,),
        active(B,)) -> (logits, hidden, cache); see ``make_step_at``.
        Exposed so callers (collaborative catch-up) can embed it in their
        own jitted loops."""
        if with_logits not in self._step_at:
            self._step_at[with_logits] = jax.jit(make_step_at(
                self.cfg, with_logits=with_logits))
        return self._step_at[with_logits]

    @property
    def step_at_fn(self) -> Callable:
        return self.get_step_at(True)

    def decode_at(self, tokens_t: jnp.ndarray, pos: jnp.ndarray,
                  active: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Per-element decode step: element i writes/reads its cache at
        pos[i]; elements with active[i]=False are untouched.  The engine's
        scalar ``self.pos`` is NOT advanced — per-element positions are the
        caller's to track."""
        logits, hidden, self.cache = self.step_at_fn(
            self.params, self.cache, tokens_t, pos, active)
        return logits, hidden

    def sample(self, logits: jnp.ndarray, temperature: float = 0.0) -> jnp.ndarray:
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.rng, k = jax.random.split(self.rng)
        return jax.random.categorical(k, logits / temperature, axis=-1).astype(jnp.int32)

    def generate(self, prompt: jnp.ndarray, n_new: int,
                 temperature: float = 0.0) -> jnp.ndarray:
        """prompt: (B, S0[,K]) -> generated ids (B, n_new[,K])."""
        logits = self.prefill(prompt)
        outs = []
        tok = self.sample(logits, temperature)
        for _ in range(n_new):
            outs.append(tok)
            logits, _ = self.decode(tok)
            tok = self.sample(logits, temperature)
        return jnp.stack(outs, axis=1)
