"""Sign/interval abstract interpretation over closed jaxprs.

Proves the paper's safety inequality at COMPILE time: the corrector
``corr = s * sigma(v)`` is elementwise nonnegative (``sigma`` maps into
[0, 1] and ``s >= 0``), hence ``fhat = u - corr <= u`` — the edge
monitor's score is a safe upper bound on the corrected score, for every
registered arch and every ``sigma_kind``, on both the training forward
(``core.decomposition.collab_forward``) and the serving engine's fused
catch-up (``CollaborativeEngine._catchup_impl``).

Two cooperating provers over one producer graph:

* an **interval domain**: every array is abstracted by one scalar
  interval ``[lo, hi]`` covering all its elements.  Transfer functions
  are monotone per primitive (``logistic -> [0,1]``, ``tanh -> [-1,1]``,
  interval arithmetic for ``add``/``sub``/``mul``, elementcount-scaled
  sums for reductions, join for ``select_n``/``concatenate``, ...);
  unknown primitives fall back to ``[-inf, inf]`` (always sound, never
  unsound — precision is the only casualty).  Call-like primitives
  (``pjit``, ``custom_jvp_call``, ``remat``...) are INLINED so the graph
  crosses jit boundaries; ``scan``/``while`` bodies are evaluated once
  with top carries (a sound post-fixpoint, by monotonicity of every
  transfer function); ``cond`` joins its branches.
* a **structural upper-bound prover**: the interval domain is
  non-relational (it cannot see that ``u - corr`` and ``u`` share the
  same ``u``), so ``fhat <= u`` is proved by walking ``fhat``'s producer
  chain: ``sub(a, b)`` proves when ``a <= u`` and ``interval(b) >= 0``;
  ``select_n`` proves when every case proves; ``min`` when either
  operand proves; value-preserving ops (reshape/broadcast/exact
  convert/...) are looked through.  Because calls are inlined, the ``u``
  appearing inside the jnp.where pjit IS the same graph node as the
  outer ``u``.

A failed proof yields the offending primitive chain (the producer path
to the interval that went negative) as the certificate's counterexample.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np

INF = math.inf

# ---------------------------------------------------------------------------
# Interval domain
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Interval:
    """One scalar interval abstracting every element of an array.
    NaN endpoints widen to +-inf (top) so the domain stays sound."""

    lo: float
    hi: float

    def __post_init__(self):
        lo, hi = float(self.lo), float(self.hi)
        if math.isnan(lo) or math.isnan(hi) or lo > hi:
            lo, hi = -INF, INF
        object.__setattr__(self, "lo", lo)
        object.__setattr__(self, "hi", hi)

    def __str__(self) -> str:
        return f"[{self.lo:.6g}, {self.hi:.6g}]"

    @property
    def nonneg(self) -> bool:
        return self.lo >= 0.0

    @property
    def nonpos(self) -> bool:
        return self.hi <= 0.0

    def join(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))


TOP = Interval(-INF, INF)
UNIT = Interval(0.0, 1.0)


def _xmul(x: float, y: float) -> float:
    # extended-real product with the 0 * inf := 0 convention (standard in
    # interval arithmetic: finite products never exceed the cross bounds)
    if x == 0.0 or y == 0.0:
        return 0.0
    return x * y


def iadd(a: Interval, b: Interval) -> Interval:
    return Interval(a.lo + b.lo, a.hi + b.hi)


def isub(a: Interval, b: Interval) -> Interval:
    return Interval(a.lo - b.hi, a.hi - b.lo)


def imul(a: Interval, b: Interval) -> Interval:
    c = [_xmul(x, y) for x in (a.lo, a.hi) for y in (b.lo, b.hi)]
    return Interval(min(c), max(c))


def idiv(a: Interval, b: Interval) -> Interval:
    if b.lo > 0.0 or b.hi < 0.0:  # 0 excluded: monotone in 1/b
        return imul(a, Interval(1.0 / b.hi, 1.0 / b.lo))
    return TOP


def _monotone(fn: Callable[[float], float]) -> Callable[[Interval], Interval]:
    def rule(a: Interval) -> Interval:
        return Interval(fn(a.lo), fn(a.hi))
    return rule


def _sigmoid(x: float) -> float:
    if x >= 0:
        return 1.0 / (1.0 + math.exp(-min(x, 700.0)))
    e = math.exp(max(x, -700.0))
    return e / (1.0 + e)


def _exp(x: float) -> float:
    return math.exp(x) if x < 700.0 else INF


def _log(x: float) -> float:
    if x <= 0.0:
        return -INF
    return math.log(x) if math.isfinite(x) else INF


def _log1p(x: float) -> float:
    if x <= -1.0:
        return -INF
    return math.log1p(x) if math.isfinite(x) else INF


def _sqrt(x: float) -> float:
    return math.sqrt(x) if 0.0 <= x < INF else (INF if x == INF else 0.0)


# ---------------------------------------------------------------------------
# Producer graph
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class Node:
    """One value in the (inlined) producer graph: its interval, the
    primitive that made it, and its operand nodes."""

    ival: Interval
    prim: str
    operands: Tuple["Node", ...] = ()
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    aval: str = ""

    def describe(self) -> str:
        return f"{self.prim} {self.aval}: {self.ival}"


# value-preserving ops: same elements, new layout — transparent to both
# the interval domain and the structural prover
_IDENTITY_PRIMS = frozenset({
    "copy", "reshape", "broadcast_in_dim", "squeeze", "transpose", "rev",
    "expand_dims", "stop_gradient", "slice", "dynamic_slice", "gather",
    "device_put", "sharding_constraint", "optimization_barrier",
})

# bounded-range float unaries.  (Boolean-valued primitives — compares,
# logical ops, is_finite — need no rule: every bool-dtype output is
# clamped to [0, 1] by the interpreter's dtype refinement.)
_RANGE_PRIMS = {
    "logistic": UNIT,
    "erf": Interval(-1.0, 1.0),
    "sin": Interval(-1.0, 1.0),
    "cos": Interval(-1.0, 1.0),
    "atan2": Interval(-math.pi, math.pi),
}

_MONOTONE_PRIMS = {
    "exp": _exp, "exp2": lambda x: _exp(x * math.log(2.0)),
    "log": _log, "log1p": _log1p, "sqrt": _sqrt,
    "cbrt": lambda x: math.copysign(abs(x) ** (1.0 / 3.0), x)
    if math.isfinite(x) else x,
}


def _elem_count(shape: Sequence[int], axes: Sequence[int]) -> int:
    n = 1
    for ax in axes:
        n *= shape[ax]
    return max(n, 1)


def _refine_range(ival: Interval, prim: str) -> Interval:
    rng = _RANGE_PRIMS.get(prim)
    if rng is None:
        return ival
    return Interval(max(ival.lo, rng.lo), min(ival.hi, rng.hi))


class SignAnalysis:
    """Abstract interpretation of one closed jaxpr.  ``in_intervals``
    (optional, per flat invar) refines inputs; default is top."""

    def __init__(self, closed_jaxpr, in_intervals: Optional[Sequence[Interval]] = None):
        self.closed_jaxpr = closed_jaxpr
        jaxpr = closed_jaxpr.jaxpr
        consts = [self._const_node(c) for c in closed_jaxpr.consts]
        if in_intervals is None:
            in_intervals = [TOP] * len(jaxpr.invars)
        self.in_nodes = [
            Node(self._refine_input(iv, v), "input", aval=str(v.aval))
            for iv, v in zip(in_intervals, jaxpr.invars)]
        self.out_nodes = self._eval(jaxpr, consts, self.in_nodes)

    # -- node builders ------------------------------------------------------

    @staticmethod
    def _refine_input(iv: Interval, var) -> Interval:
        dt = getattr(var.aval, "dtype", None)
        if dt is not None and dt == jnp.bool_:
            return Interval(max(iv.lo, 0.0), min(iv.hi, 1.0))
        return iv

    @staticmethod
    def _value_interval(val) -> Interval:
        try:
            arr = np.asarray(val)
            if arr.size == 0:
                return Interval(0.0, 0.0)
            if arr.dtype == np.bool_:
                arr = arr.astype(np.float64)
            return Interval(float(arr.min()), float(arr.max()))
        except (TypeError, ValueError, OverflowError):
            return TOP

    def _const_node(self, val) -> Node:
        return Node(self._value_interval(val), "const",
                    aval=f"{getattr(val, 'dtype', '?')}{getattr(val, 'shape', '')}")

    def _read(self, env: Dict, v) -> Node:
        if isinstance(v, jex_core.Literal):
            return Node(self._value_interval(v.val), "literal", aval=str(v.aval))
        return env[v]

    # -- interpreter --------------------------------------------------------

    def _eval(self, jaxpr, const_nodes: List[Node],
              arg_nodes: List[Node]) -> List[Node]:
        env: Dict[Any, Node] = {}
        for var, node in zip(jaxpr.constvars, const_nodes):
            env[var] = node
        for var, node in zip(jaxpr.invars, arg_nodes):
            env[var] = node
        for eqn in jaxpr.eqns:
            ins = [self._read(env, v) for v in eqn.invars]
            outs = self._eval_eqn(eqn, ins)
            for var, node in zip(eqn.outvars, outs):
                env[var] = node
        return [self._read(env, v) for v in jaxpr.outvars]

    def _sub_jaxpr(self, obj) -> Tuple[Any, List[Node]]:
        if hasattr(obj, "jaxpr"):  # ClosedJaxpr
            return obj.jaxpr, [self._const_node(c) for c in obj.consts]
        return obj, []

    def _eval_eqn(self, eqn, ins: List[Node]) -> List[Node]:
        prim = eqn.primitive.name
        mk_top = lambda: [  # noqa: E731
            Node(self._refine_input(TOP, v), prim, tuple(ins),
                 dict(eqn.params), str(v.aval)) for v in eqn.outvars]

        if prim == "scan":
            return self._eval_scan(eqn, ins)
        if prim == "while":
            return self._eval_while(eqn, ins)
        if prim == "cond":
            return self._eval_cond(eqn, ins)
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = eqn.params.get(key)
            if sub is not None:
                inner, consts = self._sub_jaxpr(sub)
                if len(inner.invars) <= len(ins):
                    # custom_jvp/vjp carry extra non-primal operands first;
                    # primal args are the trailing invars
                    return self._eval(inner, consts, ins[-len(inner.invars):]
                                      if inner.invars else [])
                return mk_top()

        rule = _RULES.get(prim)
        if rule is None:
            return mk_top()
        res = rule(eqn, [n.ival for n in ins])
        if isinstance(res, Interval):
            res = [res] * len(eqn.outvars)
        return [Node(self._refine_input(iv, v), prim, tuple(ins),
                     dict(eqn.params), str(v.aval))
                for iv, v in zip(res, eqn.outvars)]

    def _eval_scan(self, eqn, ins: List[Node]) -> List[Node]:
        inner, consts = self._sub_jaxpr(eqn.params["jaxpr"])
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        carry_top = [Node(TOP, "loop_carry", aval=str(v.aval))
                     for v in inner.invars[nc:nc + nk]]
        # xs operands are time-stacked: per-element interval == stacked one
        body_out = self._eval(inner, consts,
                              ins[:nc] + carry_top + ins[nc + nk:])
        outs = []
        for i, node in enumerate(body_out):
            if i < nk:  # carry out: join with init (covers 0 iterations)
                iv = node.ival.join(ins[nc + i].ival)
            else:  # ys: every slice produced by the top-carry body
                iv = node.ival
            outs.append(Node(iv, "scan", tuple(ins), dict(eqn.params),
                             str(eqn.outvars[i].aval)))
        return outs

    def _eval_while(self, eqn, ins: List[Node]) -> List[Node]:
        inner, consts = self._sub_jaxpr(eqn.params["body_jaxpr"])
        cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
        carry_in = ins[cn + bn:]
        carry_top = [Node(TOP, "loop_carry", aval=str(v.aval))
                     for v in inner.invars[bn:]]
        body_out = self._eval(inner, consts, ins[cn:cn + bn] + carry_top)
        return [Node(node.ival.join(init.ival), "while", tuple(ins),
                     dict(eqn.params), str(v.aval))
                for node, init, v in zip(body_out, carry_in, eqn.outvars)]

    def _eval_cond(self, eqn, ins: List[Node]) -> List[Node]:
        branch_outs = []
        for br in eqn.params["branches"]:
            inner, consts = self._sub_jaxpr(br)
            branch_outs.append(self._eval(inner, consts, ins[1:]))
        outs = []
        for i, v in enumerate(eqn.outvars):
            iv = branch_outs[0][i].ival
            for bo in branch_outs[1:]:
                iv = iv.join(bo[i].ival)
            outs.append(Node(iv, "cond", tuple(ins), dict(eqn.params),
                             str(v.aval)))
        return outs


# -- per-primitive transfer functions (eqn, [Interval]) -> Interval|list ----


def _scaled_sum(a: Interval, n: int) -> Interval:
    """Sum of n elements each in ``a``: exactly [n*lo, n*hi]."""
    return Interval(_xmul(float(n), a.lo), _xmul(float(n), a.hi))


def _rule_reduce_sum(eqn, ivals):
    n = _elem_count(eqn.invars[0].aval.shape, eqn.params.get("axes", ()))
    return _scaled_sum(ivals[0], n)


def _rule_dot(eqn, ivals):
    (lc, _), _ = eqn.params["dimension_numbers"]
    n = _elem_count(eqn.invars[0].aval.shape, lc)
    return _scaled_sum(imul(ivals[0], ivals[1]), n)


def _ipow(x: float, y: int) -> float:
    if not math.isfinite(x):
        return x if y % 2 == 1 or x > 0 else INF
    try:
        return float(x) ** y
    except OverflowError:
        return INF if (x > 0 or y % 2 == 0) else -INF


def _rule_integer_pow(eqn, ivals):
    y = int(eqn.params["y"])
    a = ivals[0]
    if y == 0:
        return Interval(1.0, 1.0)
    if y < 0:
        return idiv(Interval(1.0, 1.0), _rule_integer_pow(
            type("E", (), {"params": {"y": -y}})(), [a]))
    c = [_ipow(a.lo, y), _ipow(a.hi, y)]
    lo, hi = min(c), max(c)
    if y % 2 == 0:
        lo = 0.0 if a.lo <= 0.0 <= a.hi else max(lo, 0.0)
    return Interval(lo, hi)


def _rule_pad(eqn, ivals):
    return ivals[0].join(ivals[1])


def _rule_select_n(eqn, ivals):
    iv = ivals[1]
    for other in ivals[2:]:
        iv = iv.join(other)
    return iv


def _rule_clamp(eqn, ivals):
    lo_b, x, hi_b = ivals
    m = Interval(max(x.lo, lo_b.lo), max(x.hi, lo_b.hi))   # max(x, lo)
    return Interval(min(m.lo, hi_b.hi), min(m.hi, hi_b.hi))  # min(., hi)


def _rule_iota(eqn, ivals):
    shape = eqn.params.get("shape", (1,))
    dim = eqn.params.get("dimension", 0)
    n = shape[dim] if shape else 1
    return Interval(0.0, float(max(n - 1, 0)))


def _rule_scatter_add(eqn, ivals):
    operand, _idx, upd = ivals[0], ivals[1], ivals[2]
    if upd.nonneg:
        return Interval(operand.lo, INF)
    if upd.nonpos:
        return Interval(-INF, operand.hi)
    return TOP


def _rule_sort(eqn, ivals):
    return list(ivals)  # values permuted per operand


def _rule_top_k(eqn, ivals):
    k_dim = eqn.invars[0].aval.shape[-1] if eqn.invars[0].aval.shape else 1
    return [ivals[0], Interval(0.0, float(max(k_dim - 1, 0)))]


def _rule_cumsum(eqn, ivals):
    axis = eqn.params.get("axis", 0)
    n = eqn.invars[0].aval.shape[axis] if eqn.invars[0].aval.shape else 1
    a = ivals[0]
    return Interval(min(_xmul(n, a.lo), a.lo), max(_xmul(n, a.hi), a.hi))


def _simple(fn):
    return lambda eqn, ivals: fn(*ivals)


_RULES: Dict[str, Callable] = {
    "add": _simple(iadd), "sub": _simple(isub), "mul": _simple(imul),
    "div": _simple(idiv),
    "neg": _simple(lambda a: Interval(-a.hi, -a.lo)),
    "abs": _simple(lambda a: Interval(
        0.0 if a.lo <= 0.0 <= a.hi else min(abs(a.lo), abs(a.hi)),
        max(abs(a.lo), abs(a.hi)))),
    "sign": _simple(lambda a: Interval(-1.0, 1.0)),
    "square": _simple(lambda a: Interval(
        0.0 if a.lo <= 0.0 <= a.hi else min(_xmul(a.lo, a.lo),
                                            _xmul(a.hi, a.hi)),
        max(_xmul(a.lo, a.lo), _xmul(a.hi, a.hi)))),
    "integer_pow": _rule_integer_pow,
    "max": _simple(lambda a, b: Interval(max(a.lo, b.lo), max(a.hi, b.hi))),
    "min": _simple(lambda a, b: Interval(min(a.lo, b.lo), min(a.hi, b.hi))),
    "rem": lambda eqn, ivals: TOP,
    "pow": _simple(lambda a, b: Interval(0.0, INF) if a.lo >= 0.0 else TOP),
    "rsqrt": _simple(lambda a: Interval(0.0, INF) if a.lo >= 0.0 else TOP),
    "logistic": _simple(_monotone(_sigmoid)),
    "tanh": _simple(lambda a: Interval(max(math.tanh(min(a.lo, 20.0)), -1.0),
                                       min(math.tanh(min(a.hi, 20.0)), 1.0))
                    if math.isfinite(a.lo) or math.isfinite(a.hi)
                    else Interval(-1.0, 1.0)),
    "convert_element_type": _simple(lambda a: a),
    "reduce_precision": _simple(lambda a: a),
    "reduce_sum": _rule_reduce_sum,
    "reduce_max": _simple(lambda a: a),
    "reduce_min": _simple(lambda a: a),
    "reduce_prod": lambda eqn, ivals: (
        Interval(0.0, INF) if ivals[0].nonneg else TOP),
    "argmax": lambda eqn, ivals: Interval(0.0, INF),
    "argmin": lambda eqn, ivals: Interval(0.0, INF),
    "dot_general": _rule_dot,
    "concatenate": lambda eqn, ivals: _rule_select_n(
        eqn, [None] + list(ivals)),
    "pad": _rule_pad,
    "dynamic_update_slice": lambda eqn, ivals: ivals[0].join(ivals[1]),
    "select_n": _rule_select_n,
    "clamp": _rule_clamp,
    "iota": _rule_iota,
    "scatter": lambda eqn, ivals: ivals[0].join(ivals[2]),
    "scatter-add": _rule_scatter_add,
    "scatter_add": _rule_scatter_add,
    "sort": _rule_sort,
    "top_k": _rule_top_k,
    "cumsum": _rule_cumsum,
    "cummax": _simple(lambda a: a),
    "cummin": _simple(lambda a: a),
    "floor": _simple(lambda a: Interval(a.lo - 1.0, a.hi)),
    "ceil": _simple(lambda a: Interval(a.lo, a.hi + 1.0)),
    "round": _simple(lambda a: Interval(a.lo - 1.0, a.hi + 1.0)),
    "nextafter": _simple(lambda a, b: a.join(b)),
    "split": lambda eqn, ivals: [ivals[0]] * len(eqn.outvars),
}
for _p, _rng in _RANGE_PRIMS.items():
    _RULES.setdefault(_p, (lambda rng: (lambda eqn, ivals: rng))(_rng))
for _p, _fn in _MONOTONE_PRIMS.items():
    _RULES.setdefault(_p, _simple(_monotone(_fn)))
for _p in _IDENTITY_PRIMS:
    _RULES.setdefault(_p, _simple(lambda a, *rest: a))


def analyze_jaxpr(closed_jaxpr, in_intervals=None) -> SignAnalysis:
    """Run the abstract interpreter; returns the analysis (``.in_nodes``,
    ``.out_nodes`` hold the producer graph)."""
    return SignAnalysis(closed_jaxpr, in_intervals)


# ---------------------------------------------------------------------------
# Structural prover + counterexample chains
# ---------------------------------------------------------------------------

# exact value-preserving: safe to look through when proving <=
_LE_TRANSPARENT = _IDENTITY_PRIMS | {"scan_ys_identity"}

_F_WIDTH = {"bfloat16": 8, "float16": 11, "float32": 24, "float64": 53}


def _convert_exact(node: Node) -> bool:
    """True when a convert_element_type cannot round values upward:
    float -> same-or-wider float, or int -> wide-enough float."""
    new = str(node.params.get("new_dtype", ""))
    src = str(getattr(node.operands[0], "aval", ""))
    src_dt = src.split("[")[0] if "[" in src else src
    if new in _F_WIDTH and src_dt in _F_WIDTH:
        return _F_WIDTH[new] >= _F_WIDTH[src_dt]
    if new in ("float32", "float64") and src_dt in ("int8", "uint8", "bool",
                                                    "int16", "uint16"):
        return True
    return False


def prove_nonneg(node: Node) -> Tuple[bool, List[str]]:
    """Interval proof of ``node >= 0`` elementwise; on failure, the
    producer chain that introduced the negative range."""
    if node.ival.nonneg:
        return True, [f"proved: {node.describe()} (interval nonnegative)"]
    return False, _blame_chain(node)


def _blame_chain(node: Node, depth: int = 14) -> List[str]:
    chain = [node.describe()]
    cur = node
    while depth > 0 and cur.operands:
        nxt = None
        for op in cur.operands:  # follow the operand that can go negative
            if not op.ival.nonneg:
                nxt = op
                break
        if nxt is None:
            break
        chain.append(nxt.describe())
        cur = nxt
        depth -= 1
    return chain


def prove_le(f: Node, u: Node, depth: int = 64) -> Tuple[bool, List[str]]:
    """Structural proof of ``f <= u`` elementwise.  Returns (ok, chain):
    the proof steps on success, the refuting producer path on failure."""
    ok, chain = _prove_le(f, u, depth)
    return ok, chain


def _prove_le(f: Node, u: Node, depth: int) -> Tuple[bool, List[str]]:
    here = f.describe()
    if f is u:
        return True, [f"{here} == u (same producer)"]
    if depth <= 0:
        return False, [f"{here}: proof depth exhausted"]
    # numeric fallback: intervals alone can settle it
    if f.ival.hi <= u.ival.lo:
        return True, [f"{here} <= {u.ival} numerically"]
    # look through exact value-preserving u producers
    if u.prim in _LE_TRANSPARENT and u.operands:
        return _prove_le(f, u.operands[0], depth - 1)
    if u.prim == "convert_element_type" and u.operands and _convert_exact(u):
        return _prove_le(f, u.operands[0], depth - 1)
    if f.prim in _LE_TRANSPARENT and f.operands:
        return _prove_le(f.operands[0], u, depth - 1)
    if f.prim == "convert_element_type" and f.operands and _convert_exact(f):
        return _prove_le(f.operands[0], u, depth - 1)
    if f.prim == "sub" and len(f.operands) == 2:
        a, b = f.operands
        ok, sub_chain = _prove_le(a, u, depth - 1)
        if ok and b.ival.nonneg:
            return True, [f"{here} = a - b with b {b.ival} >= 0"] + sub_chain
        if ok:
            return False, [f"{here}: subtrahend may be negative"] + \
                _blame_chain(b)
        return False, [f"{here}: minuend does not prove"] + sub_chain
    if f.prim == "add" and len(f.operands) == 2:
        a, b = f.operands
        for x, y in ((a, b), (b, a)):
            if y.ival.nonpos:
                ok, sub_chain = _prove_le(x, u, depth - 1)
                if ok:
                    return True, [f"{here} = x + y with y {y.ival} <= 0"] + \
                        sub_chain
        return False, [f"{here}: no nonpositive addend"]
    if f.prim in ("min", "minimum") and f.operands:
        fails = []
        for op in f.operands:
            ok, sub_chain = _prove_le(op, u, depth - 1)
            if ok:
                return True, [f"{here} = min(...), one operand proves"] + \
                    sub_chain
            fails = sub_chain
        return False, [f"{here}: no min operand proves"] + fails
    if f.prim in ("max", "maximum") and f.operands:
        chains = [f"{here} = max(...), all operands must prove"]
        for op in f.operands:
            ok, sub_chain = _prove_le(op, u, depth - 1)
            if not ok:
                return False, [f"{here}: max operand fails"] + sub_chain
            chains += sub_chain
        return True, chains
    if f.prim == "select_n" and len(f.operands) >= 2:
        chains = [f"{here} = select_n, every case must prove"]
        for op in f.operands[1:]:
            ok, sub_chain = _prove_le(op, u, depth - 1)
            if not ok:
                return False, [f"{here}: select case fails"] + sub_chain
            chains += sub_chain
        return True, chains
    if f.prim == "clamp" and len(f.operands) == 3:
        ok, sub_chain = _prove_le(f.operands[2], u, depth - 1)
        if ok:
            return True, [f"{here} = clamp(..., hi), hi proves"] + sub_chain
    return False, [f"{here}: no structural rule applies "
                   f"(u is {u.describe()})"]


# ---------------------------------------------------------------------------
# Certificates for the serving stack
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SignCertificate:
    """Per-(target, arch, sigma) result of the safety proof."""

    target: str           # "collab_forward" | "catchup"
    arch: str
    sigma: str
    ok: bool
    corr_interval: Optional[Interval]
    detail: str           # proof summary or counterexample chain

    def __str__(self) -> str:
        verdict = "PROVED" if self.ok else "REFUTED"
        corr = f" corr={self.corr_interval}" if self.corr_interval else ""
        return (f"[{verdict}] {self.target} arch={self.arch} "
                f"sigma={self.sigma}{corr}")


def _with_sigma(cfg, sigma: Optional[str], s: Optional[float] = None):
    mon = cfg.monitor
    kw = dict(mon.__dict__)
    if sigma is not None:
        kw["sigma"] = sigma
    if s is not None:
        kw["s"] = s
    return cfg.replace(monitor=mon.__class__(**kw))


def abstract_params(cfg, seed: int = 0):
    """Parameter ShapeDtypeStructs without allocating: the init runs
    under eval_shape (cfg closed over — it is config, not data)."""
    key = jax.random.PRNGKey(seed)
    from repro.core import decomposition as deco
    return jax.eval_shape(lambda k: deco.init_collab_lm(k, cfg), key)


def verify_forward(cfg, arch: str = "?", sigma: Optional[str] = None,
                   s: Optional[float] = None, batch: int = 2,
                   length: int = 4) -> SignCertificate:
    """Prove ``corr >= 0`` and ``fhat <= u`` on the traced jaxpr of the
    training-time ``collab_forward`` (params fully abstract)."""
    from repro.core import decomposition as deco
    from repro.data import tokens as tok
    cfg = _with_sigma(cfg, sigma)
    sigma_kind = cfg.monitor.sigma
    params = abstract_params(cfg)
    b = next(tok.lm_batches(0, cfg, batch, length, with_monitor=False))
    b = {k: jnp.asarray(v) for k, v in b.items()}

    def fn(p, bb):
        out = deco.collab_forward(p, cfg, bb, s=s)
        return out["corr"], out["fhat"], out["u"]

    closed = jax.make_jaxpr(fn)(params, b)
    return _certify(closed, "collab_forward", arch, sigma_kind)


def verify_catchup(cfg, arch: str = "?", sigma: Optional[str] = None,
                   s: Optional[float] = None, batch: int = 2,
                   max_len: int = 8) -> SignCertificate:
    """Prove the same inequality on the SERVING engine's fused masked
    catch-up (``CollaborativeEngine._catchup_impl`` — the jit the online
    paths call on every trigger).  The engine is built over abstract
    params; tracing allocates nothing."""
    from repro.serving.collaborative import CollaborativeEngine
    cfg = _with_sigma(cfg, sigma, s)
    sigma_kind = cfg.monitor.sigma
    params = abstract_params(cfg)
    eng = CollaborativeEngine(params, cfg, batch=batch, max_len=max_len)
    B = batch
    hist = jax.ShapeDtypeStruct(eng._history.shape, eng._history.dtype)
    cache = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         eng.server.cache)
    args = (params, cache, hist,
            jax.ShapeDtypeStruct((B,), jnp.int32),         # server_pos
            jax.ShapeDtypeStruct((), jnp.int32),           # t (scalar form)
            jax.ShapeDtypeStruct((B,), jnp.bool_),         # triggered
            jax.ShapeDtypeStruct((B,), jnp.float32))       # u

    def fn(p, c, h, sp, t, trig, u):
        _, _, fhat = eng._catchup_impl(p, c, h, sp, t, trig, u)
        return fhat, u

    closed = jax.make_jaxpr(fn)(*args)
    analysis = analyze_jaxpr(closed)
    fhat_node, u_node = analysis.out_nodes
    # the corrector inside the fusion: fhat = sub(u', corr) possibly
    # under select_n — surfaced via the structural proof itself
    ok, chain = prove_le(fhat_node, u_node)
    corr_iv = _find_corr_interval(fhat_node)
    detail = "\n".join(chain)
    return SignCertificate("catchup", arch, sigma_kind, ok, corr_iv, detail)


def _find_corr_interval(fhat_node: Node, depth: int = 24) -> Optional[Interval]:
    """Walk fhat's producers for the first ``sub`` and report the
    subtrahend's interval — the corrector term the proof hinged on."""
    stack, seen = [(fhat_node, depth)], set()
    while stack:
        node, d = stack.pop()
        if d <= 0 or id(node) in seen:
            continue
        seen.add(id(node))
        if node.prim == "sub" and len(node.operands) == 2:
            return node.operands[1].ival
        stack.extend((op, d - 1) for op in node.operands)
    return None


def _certify(closed, target: str, arch: str, sigma_kind: str) -> SignCertificate:
    analysis = analyze_jaxpr(closed)
    corr_node, fhat_node, u_node = analysis.out_nodes
    ok_corr, corr_chain = prove_nonneg(corr_node)
    ok_le, le_chain = prove_le(fhat_node, u_node)
    ok = ok_corr and ok_le
    lines: List[str] = []
    if not ok_corr:
        lines.append("corr >= 0 REFUTED; producer chain:")
        lines += ["  " + c for c in corr_chain]
    else:
        lines.append(f"corr >= 0: interval {corr_node.ival}")
    if not ok_le:
        lines.append("fhat <= u REFUTED; producer chain:")
        lines += ["  " + c for c in le_chain]
    else:
        lines.append("fhat <= u: " + le_chain[0])
    return SignCertificate(target, arch, sigma_kind, ok,
                           corr_node.ival, "\n".join(lines))


SIGMA_KINDS = ("sigmoid", "tanh01")


def verify_arch(cfg, arch: str = "?",
                sigma_kinds: Sequence[str] = SIGMA_KINDS,
                include_catchup: bool = True) -> List[SignCertificate]:
    """The full sign-safety sweep for one arch: training forward and
    serving catch-up, under every sigma kind."""
    certs = []
    for kind in sigma_kinds:
        certs.append(verify_forward(cfg, arch=arch, sigma=kind))
        if include_catchup:
            certs.append(verify_catchup(cfg, arch=arch, sigma=kind))
    return certs
