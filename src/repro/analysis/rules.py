"""Static-verification rule registry + report + mutation self-test.

``tools/check_static.py`` (CI's ``static-analysis`` job) drives these:

* ``sign-safety`` — ``analysis.signs`` certificates (``corr >= 0``,
  ``fhat <= u``) for every registry arch x sigma kind, on both the
  training forward and the serving catch-up.
* ``collective-free`` / ``no-host-transfer`` / ``no-dynamic-shapes`` —
  ``analysis.hlo`` rules over every arch's compiled monitor path
  (unsharded lowering; the mesh path re-checks at shard time).
* ``recompile-once`` — a real churn episode on the paper serving config
  with a ``RecompileGuard`` armed after warmup.

The mutation self-test seeds one violation per rule (corrector sign
flip, injected ``psum``, host callback, bounded-dynamic dim, forced
retrace) and asserts the rule FIRES — a rule that cannot catch its own
seeded violation is reported as broken.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo as ahlo
from repro.analysis import signs
from repro.analysis.recompile import RecompileGuard


@dataclasses.dataclass
class RuleResult:
    rule: str
    target: str
    ok: bool
    detail: str = ""


def _engine_for(cfg, batch: int = 2, max_len: int = 8):
    """A CollaborativeEngine over fully abstract params (ShapeDtypeStruct
    leaves) — construction does no math, lowering needs only avals."""
    from repro.serving.collaborative import CollaborativeEngine
    params = signs.abstract_params(cfg)
    return CollaborativeEngine(params, cfg, batch=batch, max_len=max_len)


def run_sign_rules(arch_names: Optional[Sequence[str]] = None
                   ) -> List[RuleResult]:
    from repro.configs import registry
    names = list(arch_names) if arch_names else registry.names()
    out = []
    for name in names:
        cfg = registry.get_smoke(name)
        for cert in signs.verify_arch(cfg, arch=name):
            out.append(RuleResult(
                "sign-safety", f"{name}/{cert.target}[{cert.sigma}]",
                cert.ok, "" if cert.ok else cert.detail))
    return out


def run_hlo_rules(arch_names: Optional[Sequence[str]] = None
                  ) -> List[RuleResult]:
    from repro.configs import registry
    names = list(arch_names) if arch_names else registry.names()
    out = []
    for name in names:
        eng = _engine_for(registry.get_smoke(name))
        for kernel, rule, hits in ahlo.check_monitor_path(eng):
            out.append(RuleResult(
                rule, f"{name}/{kernel}", not hits,
                "" if not hits else "\n".join(h.brief() for h in hits[:8])))
    return out


def run_recompile_rule() -> List[RuleResult]:
    """Arm a guard over a REAL churn episode (attach/detach on the paper
    serving config, threshold forced low so every step triggers the
    catch-up) and assert exactly-once compilation per jitted path after
    warmup covers both the uniform (scalar-t) and ragged (vector-t)
    pools."""
    from repro.configs.paper_synthetic import SERVING
    from repro.core import decomposition as deco
    from repro.data import tokens as tok
    cfg = SERVING.replace(monitor=SERVING.monitor.__class__(
        **{**SERVING.monitor.__dict__, "threshold": -1e9,
           "trigger_margin": 0.0}))
    params = deco.init_collab_lm(jax.random.PRNGKey(0), cfg)
    stream = next(tok.lm_batches(0, cfg, 3, 16))["tokens"]
    from repro.serving.collaborative import CollaborativeEngine
    eng = CollaborativeEngine(params, cfg, batch=3, max_len=32)
    session = eng.session(streams=["a", "b", "c"])

    def step(t, sids):
        session.step({sid: stream[i % 3, t] for i, sid in enumerate(sids)})

    # warmup: uniform pool (scalar-t catch-up), then ragged pool
    # (vector-t catch-up) — both legitimate compile entries
    for t in range(2):
        step(t, ("a", "b", "c"))
    session.detach("b")
    step(2, ("a", "c"))
    session.attach("d")
    step(3, ("a", "c", "d"))

    guard = session.arm_recompile_guard()
    # the churn episode under guard: more steps, another detach/attach
    step(4, ("a", "c", "d"))
    session.detach("d")
    step(5, ("a", "c"))
    session.attach("e")
    for t in range(6, 10):
        step(t, ("a", "c", "e"))
    bad = guard.violations()
    return [RuleResult(
        "recompile-once", "paper-synthetic-serving/churn", not bad,
        "" if not bad else "; ".join(bad))]


# ---------------------------------------------------------------------------
# Mutation self-test: each rule must catch its seeded violation
# ---------------------------------------------------------------------------


def _mutate_sign() -> RuleResult:
    from repro.configs import registry
    cfg = registry.get_smoke("granite-8b")
    s = cfg.monitor.s
    cert = signs.verify_forward(cfg, arch="granite-8b", s=-abs(s))
    fired = not cert.ok
    return RuleResult("sign-safety", "mutation: corrector sign flipped",
                      fired, "" if fired else
                      "flipped-sign corrector was NOT refuted")


def _mutate_policy_sign() -> RuleResult:
    """A runaway threshold policy can at most drive every stream to the
    always-trigger extreme (thresholds only select WHEN the server is
    consulted) — so the certificate that must hold there is still the
    corrector's sign.  Verify the catch-up at that extreme operating
    point with the sign flipped: the rule must refute it, proving the
    sign certificates cover every operating point a policy can reach."""
    from repro.configs import registry
    cfg = registry.get_smoke("granite-8b")
    mon = cfg.monitor
    cfg = cfg.replace(monitor=mon.__class__(
        **{**mon.__dict__, "threshold": -1e9, "trigger_margin": 0.0}))
    cert = signs.verify_catchup(cfg, arch="granite-8b", s=-abs(mon.s))
    fired = not cert.ok
    return RuleResult("sign-safety",
                      "mutation: sign flipped at policy always-trigger",
                      fired, "" if fired else
                      "flipped-sign catch-up at the policy extreme was "
                      "NOT refuted")


def _mutate_collective() -> RuleResult:
    if jax.device_count() >= 2:
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("d",))
        f = jax.shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                          in_specs=P("d"), out_specs=P())
        txt = jax.jit(f).lower(
            jax.ShapeDtypeStruct((2, 4), jnp.float32)).compile().as_text()
        src = "injected psum (shard_map over 2 devices)"
    else:  # single-device fallback: a real all-reduce instruction line
        txt = ("ENTRY %e {\n  %x = f32[4]{0} parameter(0)\n"
               "  ROOT %ar = f32[4]{0} all-reduce(f32[4]{0} %x)\n}\n")
        src = "synthetic all-reduce (host has 1 device)"
    hits = ahlo.collective_instructions(txt)
    fired = bool(hits)
    return RuleResult("collective-free", f"mutation: {src}", fired,
                      "" if fired else "injected collective NOT flagged")


def _mutate_host_transfer() -> RuleResult:
    def f(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2.0,
            jax.ShapeDtypeStruct((4,), jnp.float32), x)
    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile().as_text()
    hits = ahlo.host_transfer_instructions(txt)
    fired = bool(hits)
    return RuleResult("no-host-transfer", "mutation: pure_callback on path",
                      fired, "" if fired else
                      "host callback custom-call NOT flagged")


def _mutate_dynamic_shape() -> RuleResult:
    txt = ("ENTRY %e {\n  %x = f32[<=8]{0} parameter(0)\n"
           "  ROOT %y = f32[<=8]{0} add(f32[<=8]{0} %x, f32[<=8]{0} %x)\n}\n")
    hits = ahlo.dynamic_shape_instructions(txt)
    fired = bool(hits)
    return RuleResult("no-dynamic-shapes", "mutation: bounded-dynamic dim",
                      fired, "" if fired else "dynamic dim NOT flagged")


def _mutate_retrace() -> RuleResult:
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.zeros((2,)))  # warmup
    guard = RecompileGuard({"f": f}, track_global=False).arm()
    f(jnp.zeros((3,)))  # forced retrace: new shape signature
    fired = bool(guard.violations())
    return RuleResult("recompile-once", "mutation: forced retrace", fired,
                      "" if fired else "forced retrace NOT detected")


def mutation_selftest() -> List[RuleResult]:
    """Seed one violation per rule; ``ok`` means the rule FIRED."""
    return [_mutate_sign(), _mutate_policy_sign(), _mutate_collective(),
            _mutate_host_transfer(), _mutate_dynamic_shape(),
            _mutate_retrace()]


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------


def format_report(results: List[RuleResult], *, verbose: bool = False) -> str:
    w_rule = max([len(r.rule) for r in results] + [4])
    w_tgt = max([len(r.target) for r in results] + [6])
    lines = [f"{'RULE':<{w_rule}}  {'TARGET':<{w_tgt}}  STATUS",
             "-" * (w_rule + w_tgt + 10)]
    for r in results:
        lines.append(f"{r.rule:<{w_rule}}  {r.target:<{w_tgt}}  "
                     f"{'pass' if r.ok else 'FAIL'}")
        if r.detail and (verbose or not r.ok):
            lines += ["    " + d for d in r.detail.splitlines()[:12]]
    n_fail = sum(not r.ok for r in results)
    lines.append(f"{len(results)} checks, {n_fail} failed")
    return "\n".join(lines)


def summarize(results: List[RuleResult]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for r in results:
        key = r.rule + ("" if r.ok else ":failed")
        out[key] = out.get(key, 0) + 1
    return out
