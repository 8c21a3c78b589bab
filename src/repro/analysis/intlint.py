"""intlint: dtype-purity + interval analysis over traced integer cores.

Traces a stack's integer segment (``int_core``: int8 codes in -> int8
codes out) with ``jax.make_jaxpr`` and abstractly interprets the jaxpr
(:mod:`repro.analysis.absint`) to establish, per stack x impl x
mac_chunks:

1. **integer purity** — no op promotes code-derived data to float outside
   the sanctioned requant/dequant edges. The sanction list is the closed
   set of float ops the paper's deployment recipe needs: the per-layer
   requant epilogue (``acc * rescale`` -> round -> clip -> int cast), the
   noise model's LSB-fraction fields, and elementwise/monotone structure
   ops. Float contractions (``dot_general`` / ``conv_general_dilated``),
   float pooling (``reduce_window_max``) and float ``reduce_sum`` on
   tainted data are violations: they mean real math left the integer
   domain.
2. **no accumulator overflow** — worst-case contract bounds (codes at
   their dtype range, every reduction at its declared ``cin*kh*kw``
   depth, any ``mac_chunks``) stay inside int32. Any signed-integer
   bound spill is an ERROR.
3. **no narrow accumulation** — an integer contraction whose output
   itemsize is below 4 bytes is flagged even if its bound happens to
   fit (int8/int16 accumulators violate the paper's int32 contract).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import core as jex_core

from . import absint
from .absint import AbsVal, AnalysisIncomplete, Checker, Interp
from .report import Report

# Float ops that are sanctioned on tainted (code-derived) data: the requant
# epilogue, dequant edges, the noise field, and structure/monotone ops.
# Everything float and tainted outside this set is a purity finding.
SANCTIONED_TAINTED_FLOAT = frozenset({
    # requant / dequant arithmetic
    "convert_element_type", "add", "sub", "mul", "div", "neg", "abs",
    "max", "min", "clamp", "round", "floor", "ceil", "sign", "exp",
    # selection & structure
    "select_n", "broadcast_in_dim", "reshape", "squeeze", "slice",
    "transpose", "rev", "copy", "expand_dims", "concatenate", "pad",
    "gather", "dynamic_slice", "dynamic_update_slice", "stop_gradient",
    "optimization_barrier", "sharding_constraint", "device_put",
    # comparisons produce bools; harmless
    "eq", "ne", "lt", "le", "gt", "ge", "is_finite",
    # ref plumbing inside kernels (float accumulator scratch after the
    # epilogue's dequant is itself the sanctioned edge)
    "get", "swap", "addupdate",
})

# Heavy float math that is *never* sanctioned on tainted data: if one of
# these shows up tainted+float the integer contract is broken.
_HEAVY_FLOAT = frozenset({
    "dot_general", "conv_general_dilated", "reduce_sum", "reduce_max",
    "reduce_min", "reduce_window_max", "reduce_window_min", "tanh",
    "logistic", "log", "sqrt", "rsqrt", "pow", "integer_pow", "erf_inv",
})

INT32_MIN, INT32_MAX = -2**31, 2**31 - 1


def _is_float_dtype(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return False
    try:
        return np.issubdtype(np.dtype(dt), np.floating)
    except TypeError:
        return False


class IntLintChecker(Checker):
    def __init__(self, report: Report, subject: str,
                 weight_range=None):
        self.report = report
        self.subject = subject
        self.max_acc_bound = 0.0   # widest finite int32 accumulation seen
        self.contraction_depths = []
        # (lo, hi) bound every contraction's WEIGHT operand must provably
        # lie in — set for packed cores to the sign-extended decode range
        # of the declared weight_format, so a broken unpack (e.g. missing
        # nibble sign extension: fields land in [0, 2^bits-1] instead of
        # the symmetric code range) is a finding, not silent garbage.
        self.weight_range = weight_range

    # -- purity ------------------------------------------------------------

    _HIGHER_ORDER = frozenset({
        "jit", "cond", "while", "scan", "pallas_call", "custom_jvp_call",
        "custom_vjp_call", "custom_vjp_call_jaxpr", "closed_call", "remat",
    })

    def on_eqn(self, interp: Interp, eqn, ins, outs):
        name = eqn.primitive.name
        if name in self._HIGHER_ORDER:
            return  # their bodies are interpreted (and checked) recursively
        tainted_in = any(getattr(a, "tainted", False) for a in ins
                         if isinstance(a, AbsVal))
        if not tainted_in:
            return
        out_float = any(_is_float_dtype(v.aval) for v in eqn.outvars
                        if hasattr(v, "aval"))
        in_float = any(_is_float_dtype(v.aval) for v in eqn.invars
                       if hasattr(v, "aval") and not isinstance(
                           v, jex_core.Literal))
        if not (out_float or in_float):
            # pure integer op on codes: always fine (purity-wise)
            if name == "dot_general":
                self._check_int_contraction(interp, eqn, ins)
            return
        if name in _HEAVY_FLOAT:
            self.report.error(
                "intlint/float-leak", self.subject,
                f"float `{name}` consumes code-derived data at "
                f"{interp.where()} — integer math left the int domain",
                primitive=name, location=interp.where(),
                out_shapes=[tuple(getattr(v.aval, 'shape', ()))
                            for v in eqn.outvars])
        elif name not in SANCTIONED_TAINTED_FLOAT \
                and name not in absint._TRANSFER:
            # unknown primitive touching floats + taint: flag, don't guess
            self.report.error(
                "intlint/float-leak", self.subject,
                f"unrecognized primitive `{name}` mixes tainted data with "
                f"floats at {interp.where()} — cannot prove purity",
                primitive=name, location=interp.where())
        elif name not in SANCTIONED_TAINTED_FLOAT:
            self.report.warning(
                "intlint/unsanctioned-float", self.subject,
                f"float `{name}` on code-derived data at {interp.where()} "
                f"is outside the sanctioned requant/dequant edge set",
                primitive=name, location=interp.where())

    # -- contraction width / overflow --------------------------------------

    def _check_int_contraction(self, interp, eqn, ins):
        out_aval = eqn.outvars[0].aval
        dt = np.dtype(out_aval.dtype)
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        csize = 1
        for ax in lhs_c:
            csize *= int(eqn.invars[0].aval.shape[ax])
        self.contraction_depths.append(csize)
        if dt.itemsize < 4:
            self.report.error(
                "intlint/narrow-accumulator", self.subject,
                f"integer dot_general accumulates into {dt.name} "
                f"(itemsize {dt.itemsize} < 4) at {interp.where()}",
                primitive="dot_general", dtype=dt.name, depth=csize,
                location=interp.where())
        if self.weight_range is not None and len(ins) > 1 \
                and isinstance(ins[1], AbsVal):
            # weights are the rhs operand of every contraction in this
            # codebase (activations @ weights); a packed core's unpacked
            # weight tile must provably decode into the declared format's
            # sign-extended range.
            lo, hi = self.weight_range
            rhs = ins[1]
            if not rhs.finite or rhs.lo < lo or rhs.hi > hi:
                self.report.error(
                    "intlint/weight-range", self.subject,
                    f"dot_general weight operand bound "
                    f"[{rhs.lo:.3g}, {rhs.hi:.3g}] is not provably inside "
                    f"the declared packed-weight decode range [{lo}, {hi}] "
                    f"at {interp.where()} — a broken unpack (sign "
                    "extension, field masks) would look exactly like this",
                    primitive="dot_general", lo=rhs.lo, hi=rhs.hi,
                    expected=(lo, hi), location=interp.where())

    def on_signed_wrap(self, interp, eqn, raw: AbsVal, dtype):
        self.report.error(
            "intlint/acc-overflow", self.subject,
            f"`{eqn.primitive.name}` bound [{raw.lo:.3g}, {raw.hi:.3g}] "
            f"exceeds {np.dtype(dtype).name} range at {interp.where()} — "
            f"worst-case codes can silently wrap",
            primitive=eqn.primitive.name, lo=raw.lo, hi=raw.hi,
            dtype=np.dtype(dtype).name, location=interp.where())

    def note_acc(self, v: AbsVal):
        if v.finite:
            self.max_acc_bound = max(self.max_acc_bound, abs(v.lo),
                                     abs(v.hi))


@dataclasses.dataclass
class TraceSpec:
    """One integer core to verify."""

    subject: str                   # e.g. "kws/im2col/mac_chunks=1"
    fn: Callable                   # codes -> codes (or codes -> float out)
    example_args: Sequence        # concrete arrays for make_jaxpr
    expect_float_out: bool = False
    # which positional args carry quantized codes (tainted at entry)
    tainted_args: Optional[Sequence[int]] = None
    # (lo, hi) decode range every contraction's weight operand must
    # provably lie in — set for packed-weight cores
    # (core.quant.format_interval), None disables the check
    weight_range: Optional[Tuple[int, int]] = None


def lint_trace(spec: TraceSpec, report: Report) -> None:
    """Trace ``spec.fn`` and abstractly interpret it; findings + proofs go
    into ``report``."""
    subject = spec.subject
    try:
        closed = jax.make_jaxpr(spec.fn)(*spec.example_args)
    except Exception as e:  # noqa: BLE001 - tracing failure is a finding
        report.error("intlint/trace-failed", subject,
                     f"make_jaxpr failed: {type(e).__name__}: {e}")
        return

    flat_specs = []
    leaves_per_arg = []
    for i, a in enumerate(spec.example_args):
        leaves = jax.tree_util.tree_leaves(a)
        leaves_per_arg.append(len(leaves))
        taint_this = (spec.tainted_args is None
                      or i in tuple(spec.tainted_args))
        for leaf in leaves:
            arr = np.asarray(leaf) if not absint._is_extended(
                getattr(leaf, "dtype", np.float32)) else None
            if arr is not None and np.issubdtype(arr.dtype, np.integer) \
                    and arr.dtype != np.bool_ and taint_this:
                v = absint.dtype_interval(arr.dtype, tainted=True)
            elif arr is not None:
                v = absint.abs_of_concrete(arr)
            else:
                v = AbsVal(-absint.INF, absint.INF)
            flat_specs.append(v)
    if len(flat_specs) != len(closed.jaxpr.invars):
        # pytree flattening order == invar order for positional args
        report.error("intlint/trace-failed", subject,
                     f"arg leaves ({len(flat_specs)}) != jaxpr invars "
                     f"({len(closed.jaxpr.invars)})")
        return

    checker = IntLintChecker(report, subject,
                             weight_range=spec.weight_range)
    interp = Interp(checker)
    n_before = len(report.findings) + len(report.suppressed)
    try:
        outs = interp.run_closed(closed, flat_specs)
    except AnalysisIncomplete as e:
        report.error("intlint/analysis-incomplete", subject, str(e))
        return
    except RecursionError:
        report.error("intlint/analysis-incomplete", subject,
                     "jaxpr nesting exceeded the interpreter's recursion "
                     "budget")
        return

    # output dtype contract: integer out unless the core declares a final
    # dequant (expect_float_out)
    out_avals = closed.out_avals
    for i, (aval, bound) in enumerate(zip(out_avals, outs)):
        is_f = _is_float_dtype(aval)
        if is_f and not spec.expect_float_out:
            report.error(
                "intlint/float-output", subject,
                f"core output {i} is {aval.dtype} — the integer segment "
                "must hand off int codes", index=i, dtype=str(aval.dtype))
        if not is_f and bound.finite:
            checker.note_acc(bound)

    depths = checker.contraction_depths
    report.count("intlint/eqns", interp.eqn_count)
    report.count("intlint/traces")
    if len(report.findings) + len(report.suppressed) > n_before:
        return  # violations (or exemptions) found — nothing proved
    report.prove(
        "intlint", subject,
        "integer purity + int32 accumulator safety hold at contract "
        "bounds (codes at dtype range, declared shapes)",
        eqns=interp.eqn_count,
        contractions=len(depths),
        max_contraction_depth=max(depths) if depths else 0,
        max_int_bound=checker.max_acc_bound,
        int32_headroom=(
            (INT32_MAX - checker.max_acc_bound) / INT32_MAX
            if checker.max_acc_bound else 1.0),
    )
