"""Abstract interpretation of jaxprs: interval + taint domain.

This is the machinery behind intlint. Each jaxpr variable is mapped to an
:class:`AbsVal` — a scalar interval ``[lo, hi]`` that bounds *every element*
of the array, plus a ``tainted`` bit marking data derived from quantized
integer codes. The interpreter walks the jaxpr equation by equation,
recursing into ``jit`` / ``cond`` / ``pallas_call`` sub-jaxprs, and calls
back into a :class:`Checker` at each equation so passes can flag violations
(float ops on tainted data, accumulator overflow, narrow accumulation).

Soundness model (documented in docs/ANALYSIS.md):

* Bounds are *contract-level*: integer array inputs/consts get their dtype
  range (codes ⊆ [-128, 127] ⊇ the paper's [-127, 127] contract), so a
  proved "no overflow" holds for any value the type system admits, not
  just the checked-in weights.
* Unknown primitives fall back to the output dtype's range and the join of
  input taints — over-approximate, never silently precise.
* ``pallas_call`` grids are executed abstractly: "arbitrary" axes are
  iterated step by step with a *concrete* ``program_id`` (so ``cond``-
  guarded accumulator init/flush resolve exactly and the accumulated bound
  is the true ``K_total * per-step`` product, not a fixpoint blowup);
  "parallel" axes get the full index interval.
* Unsigned wrap-around is modular by construction (hash mixing) — not a
  finding. Signed finite-bound overflow IS a finding.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import core as jex_core

INF = float("inf")

# ---------------------------------------------------------------------------
# the abstract domain
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AbsVal:
    """Interval bound over all elements of an array + code-taint bit."""

    lo: float
    hi: float
    tainted: bool = False

    def __post_init__(self):
        if self.lo > self.hi:  # pragma: no cover - defensive
            object.__setattr__(self, "lo", -INF)
            object.__setattr__(self, "hi", INF)

    @property
    def finite(self) -> bool:
        return math.isfinite(self.lo) and math.isfinite(self.hi)

    @property
    def concrete(self) -> bool:
        return self.lo == self.hi

    def taint(self, t: bool) -> "AbsVal":
        return self if self.tainted == t else AbsVal(self.lo, self.hi, t)

    def __repr__(self):
        t = "!" if self.tainted else ""
        return f"[{self.lo:g},{self.hi:g}]{t}"


def join(*vals: AbsVal) -> AbsVal:
    return AbsVal(min(v.lo for v in vals), max(v.hi for v in vals),
                  any(v.tainted for v in vals))


class RefCell:
    """Mutable cell backing a jax state ref (pallas VMEM block / scratch).

    ``val is None`` means "never written" (reading yields dtype-top).
    """

    __slots__ = ("val", "dtype")

    def __init__(self, val: Optional[AbsVal], dtype):
        self.val = val
        self.dtype = dtype

    def read(self) -> AbsVal:
        return self.val if self.val is not None else dtype_interval(self.dtype)


def dtype_interval(dtype, tainted: bool = False) -> AbsVal:
    """Range every element of an array of this dtype must lie in."""
    dtype = np.dtype(dtype) if not _is_extended(dtype) else dtype
    if _is_extended(dtype):
        return AbsVal(-INF, INF, tainted)   # e.g. PRNG key dtypes
    if dtype == np.bool_:
        return AbsVal(0, 1, tainted)
    if np.issubdtype(dtype, np.integer):
        ii = np.iinfo(dtype)
        return AbsVal(float(ii.min), float(ii.max), tainted)
    return AbsVal(-INF, INF, tainted)


def _is_extended(dtype) -> bool:
    """True for jax extended dtypes (PRNG keys) that numpy can't describe."""
    try:
        np.dtype(dtype)
        return False
    except TypeError:
        return True


def abs_of_concrete(x, tainted: bool = False) -> AbsVal:
    """Abstract a concrete (numpy) array by its actual min/max."""
    if _is_extended(getattr(x, "dtype", np.float32)):
        return AbsVal(-INF, INF, tainted)   # PRNG keys etc.
    try:
        arr = np.asarray(x)
    except (TypeError, ValueError):
        return AbsVal(-INF, INF, tainted)
    if arr.size == 0:
        return AbsVal(0.0, 0.0, tainted)
    if arr.dtype == np.bool_:
        return AbsVal(float(arr.min()), float(arr.max()), tainted)
    if not (np.issubdtype(arr.dtype, np.integer)
            or np.issubdtype(arr.dtype, np.floating)):
        return AbsVal(-INF, INF, tainted)
    lo, hi = float(arr.min()), float(arr.max())
    if math.isnan(lo) or math.isnan(hi):
        return AbsVal(-INF, INF, tainted)
    return AbsVal(lo, hi, tainted)


# ---------------------------------------------------------------------------
# checker callback
# ---------------------------------------------------------------------------


class Checker:
    """Per-equation hook; intlint subclasses this to emit findings."""

    def on_eqn(self, interp: "Interp", eqn, in_vals: Sequence[AbsVal],
               out_vals: Sequence[AbsVal]):
        pass

    def on_unknown(self, interp: "Interp", eqn, in_vals, out_vals):
        pass

    def on_signed_wrap(self, interp: "Interp", eqn, raw: AbsVal, dtype):
        """A signed-integer op's exact bound spilled past its dtype range
        (= potential silent overflow). Unsigned wrap is modular by design
        (hash mixing) and does not reach this hook."""
        pass


# ---------------------------------------------------------------------------
# interval arithmetic helpers
# ---------------------------------------------------------------------------


def _mul_bound(a: float, b: float) -> float:
    # inf * 0 in IEEE is nan; in interval arithmetic the exact product over
    # a set containing 0 contributes 0, so resolve nan -> 0.
    r = a * b
    return 0.0 if math.isnan(r) else r


def _interval_mul(a: AbsVal, b: AbsVal) -> Tuple[float, float]:
    cands = [_mul_bound(a.lo, b.lo), _mul_bound(a.lo, b.hi),
             _mul_bound(a.hi, b.lo), _mul_bound(a.hi, b.hi)]
    return min(cands), max(cands)


def _monotone(fn: Callable[[float], float], a: AbsVal) -> Tuple[float, float]:
    try:
        lo, hi = fn(a.lo), fn(a.hi)
    except (OverflowError, ValueError):
        return -INF, INF
    if math.isnan(lo) or math.isnan(hi):
        return -INF, INF
    return min(lo, hi), max(lo, hi)


def _safe_exp(x: float) -> float:
    if x == -INF:
        return 0.0
    try:
        return math.exp(x)
    except OverflowError:
        return INF


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------

# Pallas grid iteration cap: beyond this many sequential steps per kernel we
# refuse (a finding is emitted by intlint's driver via AnalysisIncomplete).
MAX_GRID_STEPS = 16384


class AnalysisIncomplete(Exception):
    """Raised when the abstract run cannot bound something it must bound."""


class Interp:
    def __init__(self, checker: Optional[Checker] = None):
        self.checker = checker or Checker()
        # context stack of (kind, name) for finding subjects, e.g.
        # [("jit", "int_core"), ("pallas", "fq_conv2d_kernel")]
        self.context: List[Tuple[str, str]] = []
        # grid axis -> AbsVal for program_id inside a pallas kernel body
        self.grid_env: Dict[int, AbsVal] = {}
        self.eqn_count = 0

    # -- context -----------------------------------------------------------

    def where(self) -> str:
        return "/".join(n for _, n in self.context) or "<top>"

    # -- environment -------------------------------------------------------

    @staticmethod
    def _read(env, v):
        if isinstance(v, jex_core.Literal):
            return abs_of_concrete(v.val)
        return env[v]

    # -- entry points ------------------------------------------------------

    def run_closed(self, closed_jaxpr, in_vals: Sequence[AbsVal],
                   const_taint: Optional[Callable] = None) -> List[AbsVal]:
        """Interpret a ClosedJaxpr. ``const_taint(const) -> bool`` decides
        whether a constvar is code-tainted (default: integer arrays of
        ndim >= 1, i.e. weight-code tensors)."""
        consts = []
        for c in closed_jaxpr.consts:
            t = (const_taint(c) if const_taint is not None
                 else _default_const_taint(c))
            consts.append(abs_of_concrete(c, tainted=t))
        return self.run_jaxpr(closed_jaxpr.jaxpr, consts, in_vals)

    def run_jaxpr(self, jaxpr, const_vals, in_vals) -> List[AbsVal]:
        env: Dict = {}
        for v, a in zip(jaxpr.constvars, const_vals):
            env[v] = a
        for v, a in zip(jaxpr.invars, in_vals):
            env[v] = a
        for eqn in jaxpr.eqns:
            self.eqn_count += 1
            ins = [self._read(env, v) for v in eqn.invars]
            outs = self._eval_eqn(eqn, ins)
            for v, a in zip(eqn.outvars, outs):
                if type(v).__name__ != "DropVar":
                    env[v] = a
        return [self._read(env, v) for v in jaxpr.outvars]

    # -- equation dispatch -------------------------------------------------

    def _eval_eqn(self, eqn, ins: Sequence) -> List:
        name = eqn.primitive.name
        fn = _TRANSFER.get(name)
        if fn is None:
            outs = self._unknown(eqn, ins)
            self.checker.on_unknown(self, eqn, ins, outs)
        else:
            outs = fn(self, eqn, ins)
        self.checker.on_eqn(self, eqn, ins, outs)
        return outs

    def _unknown(self, eqn, ins) -> List:
        """Dtype-top fallback: sound for any elementwise/structural op."""
        t = any(getattr(a, "tainted", False) for a in ins
                if isinstance(a, AbsVal))
        return [dtype_interval(v.aval.dtype, t) if hasattr(v.aval, "dtype")
                else AbsVal(-INF, INF, t) for v in eqn.outvars]

    # -- higher-order primitives ------------------------------------------

    def _call_closed(self, closed, ins) -> List:
        const_vals = [abs_of_concrete(c, tainted=_default_const_taint(c))
                      for c in closed.consts]
        return self.run_jaxpr(closed.jaxpr, const_vals, ins)

    def _jit(self, eqn, ins) -> List:
        closed = eqn.params["jaxpr"]
        nm = str(eqn.params.get("name", "jit"))
        self.context.append(("jit", nm))
        try:
            return self._call_closed(closed, ins)
        finally:
            self.context.pop()

    def _cond(self, eqn, ins) -> List:
        branches = eqn.params["branches"]
        pred, ops = ins[0], ins[1:]
        if pred.concrete and not pred.tainted:
            idx = int(pred.lo)
            idx = max(0, min(idx, len(branches) - 1))
            return self._call_closed(branches[idx], ops)
        results = [self._call_closed(b, ops) for b in branches]
        return [join(*outs) for outs in zip(*results)]

    def _while(self, eqn, ins) -> List:
        # Conservative: one purity-scan of the body with dtype-top carries,
        # outputs are dtype-top joined with the scanned result.
        params = eqn.params
        body = params["body_jaxpr"]
        nb = params["body_nconsts"]
        nc = params["cond_nconsts"]
        carry_in = ins[nc + nb:]
        tops = [dtype_interval(v.aval.dtype,
                               getattr(a, "tainted", False))
                if hasattr(v.aval, "dtype") else AbsVal(-INF, INF)
                for v, a in zip(body.jaxpr.invars[nb:], carry_in)]
        body_consts = ins[nc:nc + nb]
        outs = self._call_closed_with(body, list(body_consts) + tops)
        return [join(o, t, c) for o, t, c in zip(outs, tops, carry_in)]

    def _scan(self, eqn, ins) -> List:
        params = eqn.params
        body = params["jaxpr"]
        n_consts = params["num_consts"]
        n_carry = params["num_carry"]
        consts = list(ins[:n_consts])
        carry = list(ins[n_consts:n_consts + n_carry])
        xs = ins[n_consts + n_carry:]
        # widen carries to dtype-top, scan body once for purity + ys bounds
        carry_top = []
        for v, a in zip(body.jaxpr.invars[n_consts:n_consts + n_carry],
                        carry):
            if hasattr(v.aval, "dtype"):
                carry_top.append(dtype_interval(v.aval.dtype, a.tainted))
            else:
                carry_top.append(AbsVal(-INF, INF, a.tainted))
        body_ins = consts + carry_top + list(xs)
        outs = self._call_closed_with(body, body_ins)
        new_carry = [join(o, t) for o, t in zip(outs[:n_carry], carry_top)]
        ys = outs[n_carry:]
        return new_carry + list(ys)

    def _call_closed_with(self, closed, ins) -> List:
        return self._call_closed(closed, ins)

    # -- pallas ------------------------------------------------------------

    def _pallas_call(self, eqn, ins) -> List:
        params = eqn.params
        jaxpr = params["jaxpr"]           # open Jaxpr (kernel body)
        gm = params["grid_mapping"]
        grid = tuple(gm.grid)
        sem = _dimension_semantics(params, len(grid))
        nm = str(params.get("name_and_src_info", params.get("name", "kernel")))
        nm = nm.split(" ")[0]
        n_index = getattr(gm, "num_index_operands", 0)
        n_in = gm.num_inputs
        n_out = gm.num_outputs
        n_scratch = getattr(gm, "num_scratch_operands", 0)

        kvars = jaxpr.invars
        expect = n_index + n_in + n_out + n_scratch
        if len(kvars) != expect:  # pragma: no cover - layout drift guard
            raise AnalysisIncomplete(
                f"pallas kernel invars {len(kvars)} != expected {expect} "
                f"(index/in/out/scratch = {n_index}/{n_in}/{n_out}/"
                f"{n_scratch})")

        cells: List = []
        # index (scalar-prefetch) operands arrive as plain values
        cells.extend(ins[:n_index])
        for i in range(n_in):
            aval = kvars[n_index + i].aval
            cells.append(RefCell(ins[n_index + i], _ref_dtype(aval)))
        out_cells = []
        for i in range(n_out):
            aval = kvars[n_index + n_in + i].aval
            c = RefCell(None, _ref_dtype(aval))
            cells.append(c)
            out_cells.append(c)
        for i in range(n_scratch):
            aval = kvars[n_index + n_in + n_out + i].aval
            cells.append(RefCell(None, _ref_dtype(aval)))

        # iterate sequential ("arbitrary") axes; parallel axes get intervals
        seq_axes = [i for i, s in enumerate(sem) if s != "parallel"]
        seq_sizes = [int(grid[i]) for i in seq_axes]
        total = 1
        for s in seq_sizes:
            total *= max(s, 1)
        if total > MAX_GRID_STEPS:
            raise AnalysisIncomplete(
                f"pallas grid has {total} sequential steps "
                f"(> {MAX_GRID_STEPS}); cannot bound accumulator "
                f"step-by-step")

        base_grid_env = {i: AbsVal(0, max(int(grid[i]) - 1, 0))
                         for i, s in enumerate(sem) if s == "parallel"}

        self.context.append(("pallas", nm))
        prev_env = self.grid_env
        try:
            for step in range(max(total, 1)):
                genv = dict(base_grid_env)
                rem = step
                for ax, size in zip(reversed(seq_axes), reversed(seq_sizes)):
                    idx = rem % max(size, 1)
                    rem //= max(size, 1)
                    genv[ax] = AbsVal(idx, idx)
                self.grid_env = genv
                self.run_jaxpr(jaxpr, [], cells)
        finally:
            self.grid_env = prev_env
            self.context.pop()

        return [c.read() for c in out_cells]


def _ref_dtype(aval):
    inner = getattr(aval, "inner_aval", aval)
    return getattr(inner, "dtype", np.float32)


def _dimension_semantics(params, n_axes: int) -> Tuple[str, ...]:
    cp = params.get("compiler_params") or {}
    mosaic = cp.get("mosaic") if isinstance(cp, dict) else None
    if mosaic is None and not isinstance(cp, dict):
        mosaic = getattr(cp, "mosaic", None)
    sem = None
    if isinstance(mosaic, dict):
        sem = mosaic.get("dimension_semantics")
    elif mosaic is not None:
        sem = getattr(mosaic, "dimension_semantics", None)
    if sem is None:
        return ("arbitrary",) * n_axes
    return tuple(str(s) for s in sem)


def _default_const_taint(c) -> bool:
    if _is_extended(getattr(c, "dtype", np.float32)):
        return False
    try:
        arr = np.asarray(c)
    except (TypeError, ValueError):
        return False
    return bool(np.issubdtype(arr.dtype, np.integer)
                and arr.dtype != np.bool_ and arr.ndim >= 1)


# ---------------------------------------------------------------------------
# transfer functions
# ---------------------------------------------------------------------------


def _t(*ins: AbsVal) -> bool:
    return any(a.tainted for a in ins if isinstance(a, AbsVal))


def _pass(interp, eqn, ins):
    a = ins[0]
    return [AbsVal(a.lo, a.hi, a.tainted)] * len(eqn.outvars)


def _add(interp, eqn, ins):
    a, b = ins
    out = AbsVal(a.lo + b.lo, a.hi + b.hi, _t(a, b))
    return [_clip_wrap(interp, eqn, out)]


def _sub(interp, eqn, ins):
    a, b = ins
    out = AbsVal(a.lo - b.hi, a.hi - b.lo, _t(a, b))
    return [_clip_wrap(interp, eqn, out)]


def _mul(interp, eqn, ins):
    a, b = ins
    lo, hi = _interval_mul(a, b)
    return [_clip_wrap(interp, eqn, AbsVal(lo, hi, _t(a, b)))]


def _div(interp, eqn, ins):
    a, b = ins
    aval = eqn.outvars[0].aval
    if b.lo <= 0 <= b.hi:
        return [dtype_interval(aval.dtype, _t(a, b))]
    if np.issubdtype(np.dtype(aval.dtype), np.integer):
        # floor division with positive or negative divisor
        cands = []
        for x in (a.lo, a.hi):
            for y in (b.lo, b.hi):
                if math.isfinite(x) and math.isfinite(y) and y != 0:
                    cands.append(math.floor(x / y))
                else:
                    cands.extend([-INF, INF])
        return [AbsVal(min(cands), max(cands), _t(a, b))]
    cands = [x / y for x in (a.lo, a.hi) for y in (b.lo, b.hi) if y != 0]
    return [AbsVal(min(cands), max(cands), _t(a, b))]


def _rem(interp, eqn, ins):
    a, b = ins
    t = _t(a, b)
    if b.concrete and b.lo > 0 and a.lo >= 0:
        return [AbsVal(0, b.lo - 1, t)]
    if b.finite:
        m = max(abs(b.lo), abs(b.hi))
        return [AbsVal(-m + 1 if a.lo < 0 else 0, m - 1, t)]
    return [dtype_interval(eqn.outvars[0].aval.dtype, t)]


def _neg(interp, eqn, ins):
    a = ins[0]
    return [AbsVal(-a.hi, -a.lo, a.tainted)]


def _abs(interp, eqn, ins):
    a = ins[0]
    if a.lo >= 0:
        return [a]
    hi = max(abs(a.lo), abs(a.hi))
    lo = 0.0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
    return [AbsVal(lo, hi, a.tainted)]


def _sign(interp, eqn, ins):
    return [AbsVal(-1, 1, ins[0].tainted)]


def _max(interp, eqn, ins):
    a, b = ins
    return [AbsVal(max(a.lo, b.lo), max(a.hi, b.hi), _t(a, b))]


def _min(interp, eqn, ins):
    a, b = ins
    return [AbsVal(min(a.lo, b.lo), min(a.hi, b.hi), _t(a, b))]


def _clamp(interp, eqn, ins):
    amin, x, amax = ins
    lo = min(max(x.lo, amin.lo), amax.hi)
    hi = max(min(x.hi, amax.hi), amin.lo)
    return [AbsVal(lo, hi, _t(amin, x, amax))]


def _round_like(interp, eqn, ins):
    a = ins[0]
    lo = a.lo if not math.isfinite(a.lo) else float(np.round(a.lo))
    hi = a.hi if not math.isfinite(a.hi) else float(np.round(a.hi))
    return [AbsVal(lo, hi, a.tainted)]


def _exp(interp, eqn, ins):
    lo, hi = _monotone(_safe_exp, ins[0])
    return [AbsVal(lo, hi, ins[0].tainted)]


def _log(interp, eqn, ins):
    a = ins[0]
    if a.lo <= 0:
        return [AbsVal(-INF, INF if a.hi <= 0 else
                       (math.log(a.hi) if math.isfinite(a.hi) else INF),
                       a.tainted)]
    lo, hi = _monotone(math.log, a)
    return [AbsVal(lo, hi, a.tainted)]


def _convert(interp, eqn, ins):
    a = ins[0]
    aval = eqn.outvars[0].aval
    dt = aval.dtype
    if _is_extended(dt):
        return [AbsVal(-INF, INF, a.tainted)]
    dt = np.dtype(dt)
    if dt == np.bool_:
        return [AbsVal(0, 1, a.tainted)]
    if np.issubdtype(dt, np.integer):
        rng = dtype_interval(dt)
        lo = a.lo if not math.isfinite(a.lo) else float(int(a.lo))
        hi = a.hi if not math.isfinite(a.hi) else float(int(a.hi))
        if lo < rng.lo or hi > rng.hi:
            if np.issubdtype(dt, np.signedinteger) and a.finite:
                interp.checker.on_signed_wrap(
                    interp, eqn, AbsVal(lo, hi, a.tainted), dt)
            return [AbsVal(rng.lo, rng.hi, a.tainted)]
        return [AbsVal(lo, hi, a.tainted)]
    return [AbsVal(a.lo, a.hi, a.tainted)]


def _iota(interp, eqn, ins):
    aval = eqn.outvars[0].aval
    dim = eqn.params.get("dimension", 0)
    n = aval.shape[dim] if aval.shape else 1
    return [AbsVal(0, max(n - 1, 0))]


def _select_n(interp, eqn, ins):
    pred, cases = ins[0], ins[1:]
    out = join(*cases)
    return [out.taint(out.tainted or pred.tainted)]


def _concat(interp, eqn, ins):
    return [join(*ins)]


def _pad(interp, eqn, ins):
    operand, padval = ins[0], ins[1]
    cfg = eqn.params.get("padding_config", ())
    pads_anything = any(l > 0 or h > 0 or i > 0 for (l, h, i) in cfg)
    if not pads_anything:
        return [operand]
    return [join(operand, padval)]


def _gather(interp, eqn, ins):
    operand = ins[0]
    return [AbsVal(operand.lo, operand.hi, operand.tainted)]


def _dynamic_slice(interp, eqn, ins):
    return [ins[0]]


def _dynamic_update_slice(interp, eqn, ins):
    return [join(ins[0], ins[1])]


def _reduce_sum(interp, eqn, ins):
    a = ins[0]
    in_aval = eqn.invars[0].aval
    axes = eqn.params.get("axes", ())
    n = 1
    for ax in axes:
        n *= int(in_aval.shape[ax])
    lo, hi = _interval_mul(a, AbsVal(n, n))
    return [_clip_wrap(interp, eqn, AbsVal(lo, hi, a.tainted))]


def _reduce_minmax(interp, eqn, ins):
    return [ins[0]]


def _reduce_window_max(interp, eqn, ins):
    return [join(*ins)] if len(ins) > 1 else [ins[0]]


def _dot_general(interp, eqn, ins):
    a, b = ins
    dnums = eqn.params["dimension_numbers"]
    (lhs_c, _), _ = dnums
    in_aval = eqn.invars[0].aval
    csize = 1
    for ax in lhs_c:
        csize *= int(in_aval.shape[ax])
    plo, phi = _interval_mul(a, b)
    lo, hi = _interval_mul(AbsVal(plo, phi, False), AbsVal(csize, csize))
    return [_clip_wrap(interp, eqn, AbsVal(lo, hi, _t(a, b)))]


def _conv_general(interp, eqn, ins):
    a, w = ins
    w_aval = eqn.invars[1].aval
    # contraction size = cin/groups * prod(kernel spatial dims)
    dn = eqn.params["dimension_numbers"]
    groups = int(eqn.params.get("feature_group_count", 1))
    rhs_spec = dn.rhs_spec  # (out_c, in_c, *spatial)
    csize = int(w_aval.shape[rhs_spec[1]])
    for d in rhs_spec[2:]:
        csize *= int(w_aval.shape[d])
    del groups  # in_c dim is already per-group
    plo, phi = _interval_mul(a, w)
    lo, hi = _interval_mul(AbsVal(plo, phi), AbsVal(csize, csize))
    return [_clip_wrap(interp, eqn, AbsVal(lo, hi, _t(a, w)))]


def _program_id(interp, eqn, ins):
    axis = int(eqn.params["axis"])
    v = interp.grid_env.get(axis)
    return [v if v is not None else AbsVal(0, INF)]


def _num_programs(interp, eqn, ins):
    return [AbsVal(0, INF)]


def _get(interp, eqn, ins):
    cell = ins[0]
    if isinstance(cell, RefCell):
        return [cell.read()]
    return [cell]


def _swap(interp, eqn, ins):
    cell, new = ins[0], ins[1]
    if isinstance(cell, RefCell):
        old = cell.read() if cell.val is not None else \
            dtype_interval(cell.dtype)
        # strong update: pallas blocks are fully overwritten by our kernels;
        # set-semantics (not join) keeps the accumulator bound exact.
        cell.val = new if isinstance(new, AbsVal) else AbsVal(-INF, INF)
        return [old]
    return [cell]


def _addupdate(interp, eqn, ins):
    cell, delta = ins[0], ins[1]
    if isinstance(cell, RefCell) and isinstance(delta, AbsVal):
        old = cell.read()
        cell.val = AbsVal(old.lo + delta.lo, old.hi + delta.hi,
                          old.tainted or delta.tainted)
    return []


def _cmp(interp, eqn, ins):
    a, b = ins
    t = _t(a, b)
    name = eqn.primitive.name
    if a.concrete and b.concrete and a.finite and b.finite:
        x, y = a.lo, b.lo
        val = {"eq": x == y, "ne": x != y, "lt": x < y, "le": x <= y,
               "gt": x > y, "ge": x >= y}[name]
        return [AbsVal(float(val), float(val), t)]
    return [AbsVal(0, 1, t)]


def _bool_out(interp, eqn, ins):
    return [AbsVal(0, 1, _t(*[a for a in ins if isinstance(a, AbsVal)]))]


def _bitwise(interp, eqn, ins):
    aval = eqn.outvars[0].aval
    if np.dtype(aval.dtype) == np.bool_:
        return [AbsVal(0, 1, _t(*ins))]
    return [dtype_interval(aval.dtype, _t(*ins))]


def _pow2_mask_above(hi: float) -> float:
    """Smallest 2^k - 1 >= hi (an all-ones mask covering hi's bits)."""
    m = 1
    while m - 1 < int(hi):
        m <<= 1
    return float(m - 1)


def _bitwise_and(interp, eqn, ins):
    """x & y stays in [0, x] whenever x >= 0, for ANY y (the sign bit of
    the nonnegative operand is clear, and every result bit is a subset of
    its bits). Needed to trace packed-weight unpack chains tightly."""
    aval = eqn.outvars[0].aval
    if np.dtype(aval.dtype) == np.bool_:
        return [AbsVal(0, 1, _t(*ins))]
    t = _t(*ins)
    his = [v.hi for v in ins if v.lo >= 0 and v.finite]
    if his:
        return [AbsVal(0.0, float(min(his)), t)]
    return [dtype_interval(aval.dtype, t)]


def _bitwise_or_xor(interp, eqn, ins):
    """For nonnegative x, y: x|y and x^y never set a bit above the highest
    bit of max(x, y), so both lie in [0, 2^k - 1]; x|y >= max(x, y)."""
    aval = eqn.outvars[0].aval
    if np.dtype(aval.dtype) == np.bool_:
        return [AbsVal(0, 1, _t(*ins))]
    a, b = ins
    t = _t(a, b)
    if a.lo >= 0 and b.lo >= 0 and a.finite and b.finite:
        hi = _pow2_mask_above(max(a.hi, b.hi))
        lo = max(a.lo, b.lo) if eqn.primitive.name == "or" else 0.0
        return [AbsVal(lo, hi, t)]
    return [dtype_interval(aval.dtype, t)]


def _shift_left(interp, eqn, ins):
    a, s = ins
    t = _t(a, s)
    if a.finite and s.concrete and s.finite and s.lo >= 0:
        k = int(s.lo)
        if k < 63:  # beyond that, python-int math is sound but pointless
            return [_clip_wrap(interp, eqn, AbsVal(
                float(int(a.lo) << k), float(int(a.hi) << k), t))]
    return [dtype_interval(eqn.outvars[0].aval.dtype, t)]


def _shift_right_arithmetic(interp, eqn, ins):
    a, s = ins
    t = _t(a, s)
    if a.finite and s.concrete and s.finite and s.lo >= 0:
        k = int(s.lo)
        # python's >> on ints IS arithmetic shift, negatives included
        return [AbsVal(float(int(a.lo) >> k), float(int(a.hi) >> k), t)]
    return [dtype_interval(eqn.outvars[0].aval.dtype, t)]


def _shift_right_logical(interp, eqn, ins):
    a, s = ins
    t = _t(a, s)
    if a.lo >= 0 and s.concrete and s.finite and a.finite:
        k = int(s.lo)
        return [AbsVal(float(int(a.lo) >> k), float(int(a.hi) >> k), t)]
    return [dtype_interval(eqn.outvars[0].aval.dtype, t)]


def _erf_inv(interp, eqn, ins):
    return [AbsVal(-INF, INF, ins[0].tainted)]


def _integer_pow(interp, eqn, ins):
    a = ins[0]
    p = int(eqn.params.get("y", 2))
    if p % 2 == 0:
        hi = max(abs(a.lo), abs(a.hi)) ** p if a.finite else INF
        lo = 0.0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi)) ** p
        return [_clip_wrap(interp, eqn, AbsVal(lo, hi, a.tainted))]
    lo = a.lo ** p if math.isfinite(a.lo) else a.lo
    hi = a.hi ** p if math.isfinite(a.hi) else a.hi
    return [_clip_wrap(interp, eqn, AbsVal(lo, hi, a.tainted))]


def _sqrt(interp, eqn, ins):
    a = ins[0]
    lo = math.sqrt(max(a.lo, 0.0)) if math.isfinite(a.lo) else 0.0
    hi = math.sqrt(a.hi) if (math.isfinite(a.hi) and a.hi >= 0) else INF
    return [AbsVal(lo, hi, a.tainted)]


def _rsqrt(interp, eqn, ins):
    return [AbsVal(-INF, INF, ins[0].tainted)]


def _clip_wrap(interp: "Interp", eqn, v: AbsVal) -> AbsVal:
    """Integer results that exceed their dtype wrap around; the *bound* we
    return must stay sound, so widen to the dtype range when the exact
    bound spills. Signed spills additionally notify the checker (potential
    silent overflow); unsigned wrap is modular by design (hash mixing) and
    is not reported. Floats pass through unchanged."""
    aval = eqn.outvars[0].aval
    dt = getattr(aval, "dtype", None)
    if dt is None or _is_extended(dt):
        return v
    dt = np.dtype(dt)
    if not np.issubdtype(dt, np.integer):
        return v
    rng = dtype_interval(dt)
    if v.lo < rng.lo or v.hi > rng.hi:
        if np.issubdtype(dt, np.signedinteger):
            interp.checker.on_signed_wrap(interp, eqn, v, dt)
        return AbsVal(rng.lo, rng.hi, v.tainted)
    return v


_TRANSFER: Dict[str, Callable] = {
    # structure
    "broadcast_in_dim": _pass, "reshape": _pass, "squeeze": _pass,
    "slice": _pass, "transpose": _pass, "rev": _pass, "copy": _pass,
    "expand_dims": _pass, "convert_element_type": _convert,
    "concatenate": _concat, "pad": _pad, "gather": _gather,
    "dynamic_slice": _dynamic_slice,
    "dynamic_update_slice": _dynamic_update_slice,
    "stop_gradient": _pass,
    # arithmetic
    "add": _add, "sub": _sub, "mul": _mul, "div": _div, "rem": _rem,
    "neg": _neg, "abs": _abs, "sign": _sign, "max": _max, "min": _min,
    "clamp": _clamp, "round": _round_like, "floor": _round_like,
    "ceil": _round_like, "nextafter": _pass,
    "exp": _exp, "log": _log, "integer_pow": _integer_pow,
    "pow": lambda i, e, ins: [AbsVal(-INF, INF, _t(*ins))],
    "sqrt": _sqrt, "rsqrt": _rsqrt, "erf_inv": _erf_inv,
    "tanh": lambda i, e, ins: [AbsVal(-1, 1, ins[0].tainted)],
    "logistic": lambda i, e, ins: [AbsVal(0, 1, ins[0].tainted)],
    "is_finite": _bool_out,
    # comparisons / logic
    "eq": _cmp, "ne": _cmp, "lt": _cmp, "le": _cmp, "gt": _cmp, "ge": _cmp,
    "and": _bitwise_and, "or": _bitwise_or_xor, "xor": _bitwise_or_xor,
    "not": _bitwise,
    "shift_left": _shift_left, "shift_right_logical": _shift_right_logical,
    "shift_right_arithmetic": _shift_right_arithmetic,
    "select_n": _select_n,
    # iota / reductions / contractions
    "iota": _iota, "reduce_sum": _reduce_sum, "reduce_max": _reduce_minmax,
    "reduce_min": _reduce_minmax, "reduce_and": _bool_out,
    "reduce_or": _bool_out,
    "argmax": lambda i, e, ins: [dtype_interval(e.outvars[0].aval.dtype)],
    "argmin": lambda i, e, ins: [dtype_interval(e.outvars[0].aval.dtype)],
    "reduce_window_max": _reduce_window_max,
    "reduce_window_min": _reduce_window_max,
    "dot_general": _dot_general,
    "conv_general_dilated": _conv_general,
    # randomness (bounds unknown; keys untainted)
    "random_bits": lambda i, e, ins: [
        dtype_interval(e.outvars[0].aval.dtype, _t(*ins))],
    "random_split": lambda i, e, ins: [AbsVal(-INF, INF, _t(*ins))],
    "random_wrap": lambda i, e, ins: [AbsVal(-INF, INF, _t(*ins))],
    "random_unwrap": lambda i, e, ins: [
        dtype_interval(e.outvars[0].aval.dtype, _t(*ins))],
    "random_fold_in": lambda i, e, ins: [AbsVal(-INF, INF, _t(*ins))],
    "bitcast_convert_type": lambda i, e, ins: [
        dtype_interval(e.outvars[0].aval.dtype, _t(*ins))],
    "threefry2x32": lambda i, e, ins: [
        dtype_interval(e.outvars[0].aval.dtype, _t(*ins))
        for _ in e.outvars],
    # refs / pallas
    "get": _get, "swap": _swap, "addupdate": _addupdate,
    "program_id": _program_id, "num_programs": _num_programs,
    # higher-order
    "jit": Interp._jit, "cond": Interp._cond, "while": Interp._while,
    "scan": Interp._scan, "pallas_call": Interp._pallas_call,
    "custom_jvp_call": lambda i, e, ins: i._call_closed(
        e.params["call_jaxpr"], ins),
    "custom_vjp_call": lambda i, e, ins: i._call_closed(
        e.params["call_jaxpr"], ins),
    "custom_vjp_call_jaxpr": lambda i, e, ins: i._call_closed(
        e.params["fun_jaxpr"], ins),
    "remat": lambda i, e, ins: i._call_closed(e.params["jaxpr"], ins)
    if hasattr(e.params.get("jaxpr"), "consts")
    else i.run_jaxpr(e.params["jaxpr"], [], ins),
    "closed_call": lambda i, e, ins: i._call_closed(e.params["call_jaxpr"],
                                                    ins),
    # no-ops for analysis
    "debug_callback": lambda i, e, ins: [],
    "optimization_barrier": lambda i, e, ins: list(ins),
    "sharding_constraint": lambda i, e, ins: [ins[0]],
    "device_put": lambda i, e, ins: list(ins),
}
