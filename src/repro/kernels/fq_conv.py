"""Pallas TPU kernel: fused fully-quantized convolution (implicit GEMM).

The im2col path (kernels/ops.py) materializes every input patch in HBM — a
``ksize**2 x`` blow-up of activation bytes that dominates the int8 memory
roofline. This kernel never builds patches. Each grid step holds one row
tile of the input in VMEM and runs every kernel tap over it as a static
slice, multiplying it against that tap's weight slice on the MXU and
accumulating int8 x int8 into an int32 VMEM scratch. The requantization
"ADC" is the same fused epilogue as ``fq_matmul`` (shared code — bit-exact
by construction), so codes never leave VMEM at higher precision.

Layout contract (matches the im2col path and ``integer_inference``):
  * activations  (B, H, W, Cin) int8 codes, NHWC,
  * weights      (kh*kw*Cin, Cout) int8 codes, tap-major im2col layout
                 (row  t*Cin + c  is tap (t // kw, t % kw), channel c),
  * output       (B, Ho, Wo, Cout) int8 codes (requant) or f32 (dequant);
                 with ``pool`` set, (B, Ho//ph, Wo//pw, Cout).

Tiling (what Mosaic accepts: block shapes whose last two dims are the
array's own, and sublane slices whose offsets are static):
  * **Phases.** The output pixel the kernel writes steps through the input
    by ``period = pool * stride`` per row and column. The wrapper splits
    the edge-padded input into its ``period_h * period_w`` phase images
    (input pixel (P*q + r) lands in phase r at q), so every tap of every
    pool position reads a *contiguous* window of one phase image: stride
    and the 2x2 pool become phase selection, never strided slicing.
  * **Flat rows.** Each phase image is flattened with a row pitch ``wq``
    (the output width plus the widest tap's column offset), so a tap's
    window over ``bhf`` output rows is the flat slice
    ``[qh*wq + qw, +bhf*wq)`` — one 2-D int8 matmul operand. Columns
    ``wo_out..wq-1`` of each output row are computed and sliced away.
  * **Row tiles.** Output rows are cut into tiles of ``bhf``; each tile's
    input rows, halo included, are gathered once in HBM, so the x block is
    the tile's whole (phases, rows*wq, bc) slab and all tap offsets inside
    it are compile-time constants.

Grid is (B * n_row_tiles, Cout/bco, Cin/bc): the batch dimension is
*folded* into the row-tile axis (small serving batches B=1..4 otherwise
burn a whole grid dimension on 1-4 steps), and the channel reduction is
innermost ("arbitrary" semantics) so each output tile's accumulators stay
resident in VMEM. The phase split and halo gather cost one rearranged
copy of the activations in HBM — O(input bytes), not the
O(ksize^2 * input) of im2col patches.

Fused maxpool epilogue: FQ-Conv's learned quantizer is monotone, so
requantization commutes with max (Q(max x) == max Q(x) — the same fact
``integer_inference.int_maxpool2d`` exploits on codes). With ``pool=(2,2)``
the kernel keeps one int32 accumulator per pool position and takes their
elementwise max before requant: a pooled layer writes Ho*Wo/4 output
bytes to HBM instead of Ho*Wo plus a second full read+write pooling pass.

Fused residual add epilogue: with ``residual`` (a residual block's
shortcut codes, on the same scale as the output) the epilogue adds the
shortcut tile to the requantized signed branch codes and clips to
[0, n_out], the add and the ReLU after it, before the int8 store. The
branch's codes never reach HBM; the call is named ``fq_conv2d_add``.

Block sizes: explicit knobs win, then ``AUTOTUNE_TABLE`` — measured-sweep
winners persisted by ``benchmarks/autotune_conv.py`` to the checked-in
``autotune_table.json`` next to this file, loaded once on first use
(entries measured on a different backend family are ignored;
interpret-mode timings say nothing about Mosaic) — then a VMEM-budget
heuristic.
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import quant
from .fq_matmul import apply_epilogue, noise_tile

# ---------------------------------------------------------------------------
# Block-size selection
# ---------------------------------------------------------------------------

# Hand defaults, keyed by (kh, kw, stride_h, weight_format); measured sweep
# entries from autotune_table.json override these when their backend
# matches. Packed lookups that miss fall back to the same-shape int8 entry
# (minus bc, which packed kernels derive from cin).
_BUILTIN_TABLE: dict = {
    (3, 3, 1, "int8"): {"bco": 128},
    (3, 3, 2, "int8"): {"bco": 128},
    (1, 1, 1, "int8"): {"bho": 128, "bco": 128},
}

AUTOTUNE_TABLE_PATH = os.path.join(os.path.dirname(__file__),
                                   "autotune_table.json")


class AutotuneMissWarning(UserWarning):
    """A served conv shape has no *measured* autotune entry for the active
    backend family — block sizes fall back to builtin defaults / the VMEM
    heuristic. Structured: ``.key`` is the (kh, kw, stride, weight_format)
    lookup key and ``.backend`` the backend it was missing for, so the
    analysis report can count misses instead of scraping warning text."""

    def __init__(self, key: Tuple[int, int, int, str], backend: str):
        self.key = key
        self.backend = backend
        super().__init__(
            f"no measured autotune entry for conv shape key {key} on "
            f"backend {backend!r}; falling back to builtin defaults "
            "(run benchmarks/autotune_conv.py --record to measure it)")


def load_autotune_table(path: str = AUTOTUNE_TABLE_PATH) -> dict:
    """Builtin defaults overlaid with measured winners for *this* backend.

    The JSON is written by ``benchmarks/autotune_conv.py`` and records the
    backend it was measured on; winners from another backend family are
    skipped (a block shape that wins in CPU interpret mode is meaningless
    for Mosaic, and vice versa), leaving the builtin defaults in force.
    """
    table = {k: dict(v) for k, v in _BUILTIN_TABLE.items()}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return table
    if not isinstance(doc, dict) or doc.get("format") != 1 \
            or doc.get("backend") != jax.default_backend():
        return table
    for e in doc.get("entries", []):
        try:
            fmt = str(e.get("format", "int8"))
            key = (int(e["kh"]), int(e["kw"]), int(e["stride"]), fmt)
            knobs = {k: int(e[k]) for k in ("bho", "bco", "bc") if e.get(k)}
        except (KeyError, TypeError, ValueError):
            continue  # a malformed entry never takes the defaults down
        if fmt not in quant.WEIGHT_FORMATS:
            continue  # kernellint reports this; the loader stays lenient
        table[key] = knobs
    return table


# Memoized on first use rather than at module import: load_autotune_table
# asks jax for the backend, and forcing backend initialization as an import
# side effect would break callers that configure platforms after import.
AUTOTUNE_TABLE: Optional[dict] = None
# Keys whose knobs came from a measured (backend-matching) JSON entry, as
# opposed to the builtin defaults — the miss warning keys off this set.
MEASURED_KEYS: Optional[set] = None
# (kh, kw, stride, weight_format) -> number of pick_blocks lookups that
# missed a measured entry; repro.analysis folds these counts into its
# report.
AUTOTUNE_MISSES: dict = {}
# (replica_tag, key) -> misses recorded while a serving replica lane's
# replica_scope was active. Misses fire at jit-trace time, so with a step
# SHARED across lanes only the first-compiling lane records — per-replica
# apply closures each trace and each record. kernellint folds these and
# warns when same-backend replicas report divergent miss keys.
AUTOTUNE_MISSES_BY_REPLICA: dict = {}
_REPLICA_TAG: list = [None]
_WARNED_KEYS: set = set()


@contextlib.contextmanager
def replica_scope(tag):
    """Attribute autotune-table misses inside the block to replica ``tag``
    (serve.cnn_batching wraps each lane's dispatch in one)."""
    prev, _REPLICA_TAG[0] = _REPLICA_TAG[0], tag
    try:
        yield
    finally:
        _REPLICA_TAG[0] = prev


def measured_keys(path: str = AUTOTUNE_TABLE_PATH) -> set:
    """Lookup keys with a measured entry for the active backend."""
    keys = set()
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return keys
    if not isinstance(doc, dict) or doc.get("format") != 1 \
            or doc.get("backend") != jax.default_backend():
        return keys
    for e in doc.get("entries", []):
        try:
            keys.add((int(e["kh"]), int(e["kw"]), int(e["stride"]),
                      str(e.get("format", "int8"))))
        except (KeyError, TypeError, ValueError):
            continue
    return keys


def _autotune_table() -> dict:
    global AUTOTUNE_TABLE, MEASURED_KEYS
    if AUTOTUNE_TABLE is None:
        AUTOTUNE_TABLE = load_autotune_table()
        MEASURED_KEYS = measured_keys()
    return AUTOTUNE_TABLE


def reset_autotune_cache():
    """Drop the memoized table + warn/miss state (tests, table swaps)."""
    global AUTOTUNE_TABLE, MEASURED_KEYS
    AUTOTUNE_TABLE = None
    MEASURED_KEYS = None
    AUTOTUNE_MISSES.clear()
    AUTOTUNE_MISSES_BY_REPLICA.clear()
    _WARNED_KEYS.clear()


def _note_autotune_miss(key: Tuple[int, int, int, str]):
    AUTOTUNE_MISSES[key] = AUTOTUNE_MISSES.get(key, 0) + 1
    if _REPLICA_TAG[0] is not None:
        rk = (_REPLICA_TAG[0], key)
        AUTOTUNE_MISSES_BY_REPLICA[rk] = \
            AUTOTUNE_MISSES_BY_REPLICA.get(rk, 0) + 1
    if key not in _WARNED_KEYS:
        _WARNED_KEYS.add(key)
        warnings.warn(AutotuneMissWarning(key, jax.default_backend()),
                      stacklevel=3)


_VMEM_BUDGET = 4 * 1024 * 1024  # conservative half-ish of usable VMEM
_LANES = 128                       # TPU vector lane width


def _divisor_at_most(n: int, cap: int) -> int:
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


def _channel_block(n: int, cap: int) -> int:
    """Default channel block: the whole extent when it fits ``cap``, else
    the largest lane-multiple divisor of ``n`` under ``cap`` — the two
    block widths Mosaic accepts for a minor dimension."""
    if n <= cap:
        return n
    for d in range(cap - cap % _LANES, 0, -_LANES):
        if n % d == 0:
            return d
    return n


class Tiling(NamedTuple):
    """Static geometry of one fused-conv call (see the module docstring)."""

    period: Tuple[int, int]   # input rows/cols per output row/col (pool*stride)
    pool: Tuple[int, int]     # (1, 1) when no pool is fused
    bhf: int                  # output rows (post-pool) per row tile
    wq: int                   # row pitch of the flattened phase images
    ht: int                   # phase-image rows one tile reads, halo included

    @property
    def n_phase(self) -> int:
        return self.period[0] * self.period[1]

    @property
    def n_pos(self) -> int:
        return self.pool[0] * self.pool[1]

    @property
    def m(self) -> int:
        return self.bhf * self.wq


def tiling(*, bho: int, wo: int, kh: int, kw: int, stride: Tuple[int, int],
           dilation: Tuple[int, int] = (1, 1),
           pool: Optional[Tuple[int, int]] = None) -> Tiling:
    """Row-tile geometry for ``bho`` pre-pool output rows per tile."""
    pool = tuple(pool) if pool is not None else (1, 1)
    period = (pool[0] * stride[0], pool[1] * stride[1])
    # deepest tap offset, in whole phase rows/cols
    qh = ((pool[0] - 1) * stride[0] + (kh - 1) * dilation[0]) // period[0]
    qw = ((pool[1] - 1) * stride[1] + (kw - 1) * dilation[1]) // period[1]
    bhf = bho // pool[0]
    # a tap window starting qw columns into the flat tile runs qw elements
    # into the row below the last one: one extra row covers it
    return Tiling(period=period, pool=pool, bhf=bhf, wq=wo // pool[1] + qw,
                  ht=bhf + qh + (1 if qw else 0))


def _tap_reads(t: Tiling, kh: int, kw: int, stride, dilation):
    """Per pool position, the static (tap, phase, flat offset) of each tap's
    window inside a tile."""
    reads = []
    for a in range(t.pool[0]):
        for b in range(t.pool[1]):
            pos = []
            for di in range(kh):
                for dj in range(kw):
                    oh = a * stride[0] + di * dilation[0]
                    ow = b * stride[1] + dj * dilation[1]
                    phase = (oh % t.period[0]) * t.period[1] + ow % t.period[1]
                    off = (oh // t.period[0]) * t.wq + ow // t.period[1]
                    pos.append((di * kw + dj, phase, off))
            reads.append(tuple(pos))
    return tuple(reads)


def vmem_footprint(*, bho: int, wo: int, bco: int, bc: int, kh: int,
                   kw: int, stride: Tuple[int, int],
                   dilation: Tuple[int, int] = (1, 1),
                   pool: Optional[Tuple[int, int]] = None,
                   weight_format: str = "int8") -> int:
    """Static VMEM bytes of one grid step, minor dims padded to 128 lanes:
    double-buffered int8 input tile, weight block and out tile (worst case
    f32), plus the int32 accumulator scratch (one per pool position).
    Shared with repro.analysis.kernellint, which checks it against the
    per-backend budget so a bad autotune row is a lint error rather than
    a Mosaic OOM. Packed formats stream bc*bco/factor weight bytes but
    also materialize the unpacked int8 taps before the MAC, so both
    terms count."""
    t = tiling(bho=bho, wo=wo, kh=kh, kw=kw, stride=stride,
               dilation=dilation, pool=pool)
    lanes = lambda n: -(-n // _LANES) * _LANES  # noqa: E731
    factor = quant.format_factor(weight_format)
    x_b = t.n_phase * t.ht * t.wq * lanes(bc)
    w_b = kh * kw * (bc // factor) * lanes(bco)
    unpacked = kh * kw * bc * lanes(bco) if factor > 1 else 0
    out = 4 * t.m * lanes(bco)
    acc = 4 * t.n_pos * t.m * lanes(bco)
    return 2 * (x_b + w_b + out) + unpacked + acc


def pick_blocks(*, ho: int, wo: int, cin: int, cout: int, kh: int, kw: int,
                stride: Tuple[int, int], pool: Optional[Tuple[int, int]] = None,
                dilation: Tuple[int, int] = (1, 1),
                bho: Optional[int] = None, bco: Optional[int] = None,
                bc: Optional[int] = None,
                weight_format: str = "int8") -> Tuple[int, int, int]:
    """(bho, bco, bc): output-row / output-channel / input-channel blocks.

    ``bho`` counts conv output rows before the fused pool. Explicit
    arguments win, then the autotune table, then a VMEM-budget heuristic
    that halves bho until the footprint fits. An explicit ``bc`` must
    divide ``cin`` exactly (a non-divisor block would read weight rows
    across a tap boundary); table values are rounded down to a divisor.
    The default ``bc``/``bco`` are the whole extent or a multiple of 128
    lanes, the block widths Mosaic accepts. With a fused ``pool``, bho is
    rounded down to a multiple of the pool height so pool windows never
    straddle a row-tile boundary (explicit values included — tiling is a
    performance knob, never a semantics knob).

    Packed weight formats fix ``bc`` to cin rounded up to the pack
    factor: a partial-channel block would split weight rows mid-byte.
    Autotune entries for the packed key override bho/bco only; a missing
    packed entry borrows the same-shape int8 entry's bho/bco.
    """
    packed = weight_format != "int8"
    factor = quant.format_factor(weight_format)
    if packed:
        cin_p = -(-cin // factor) * factor
        if bc is not None and bc != cin_p:
            raise ValueError(
                f"weight_format={weight_format!r} requires bc == cin "
                f"padded to the pack factor ({cin_p}), got bc={bc}")
        bc = cin_p
    elif bc is not None and cin % bc != 0:
        raise ValueError(f"bc={bc} must divide cin={cin}")
    key = (kh, kw, stride[0], weight_format)
    over = _autotune_table().get(key)
    if over is None and packed:
        over = {k: v for k, v in _autotune_table().get(
            (kh, kw, stride[0], "int8"), {}).items() if k != "bc"}
    over = over or {}
    explicit = bho is not None and bco is not None \
        and (packed or bc is not None)
    if not explicit and key not in (MEASURED_KEYS or ()):
        # only a real table consultation counts as a miss; fully-explicit
        # knobs never look at the table
        _note_autotune_miss(key)
    bco = bco or over.get("bco")
    bho = bho or over.get("bho")
    if not packed and bc is None:
        bc = over.get("bc")
        bc = _divisor_at_most(cin, bc) if bc else _channel_block(cin, 512)

    bco = min(bco or _LANES, cout)

    ph = pool[0] if pool is not None else 1
    if bho is None:
        bho = min(ho, 128)
        while bho > ph and vmem_footprint(
                bho=bho, wo=wo, bco=bco, bc=bc, kh=kh, kw=kw, stride=stride,
                dilation=dilation, pool=pool,
                weight_format=weight_format) > _VMEM_BUDGET:
            bho = (bho + 1) // 2
    bho = min(bho, ho)
    return max(ph, bho - bho % ph), bco, bc


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _kernel(scale_ref, x_ref, w_ref, *refs, n_cb: int, reads, t: Tiling,
            epilogue: str, n_out: int, lo: int, noise: bool, add: bool,
            mac_chunks: int, n_i: int, ho: int, wo: int, cout: int,
            weight_format: str):
    if noise:
        sigma_ref, seed_ref, *refs = refs
        # program_id reads hoisted out of the pl.when body (interpret
        # mode can't lower the primitive inside the cond).
        p, j = pl.program_id(0), pl.program_id(1)
    if add:
        res_ref, *refs = refs
    o_ref, acc_ref = refs
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    taps = []
    for tap in range(w_ref.shape[0]):
        w_tap = w_ref[tap]
        if weight_format != "int8":
            # (bc/factor, bco) packed bytes -> (bc, bco) int8 codes in
            # VMEM ahead of the MAC; accumulator math is the int8 kernel's.
            w_tap = quant.unpack_codes(w_tap, weight_format)
        taps.append(w_tap)
    for k, pos in enumerate(reads):
        part = None
        for tap, phase, off in pos:
            d = jnp.dot(x_ref[0, phase, off:off + t.m, :], taps[tap],
                        preferred_element_type=jnp.int32)
            part = d if part is None else part + d
        acc_ref[k] += part

    @pl.when(c == n_cb - 1)
    def _epilogue():
        if noise:
            # ADC noise on each PRE-POOL int32 accumulator (paper §4.4),
            # indexed by the global conv-output coordinate flattened the
            # same way the im2col path flattens matmul rows: tile element
            # (il, jj) of pool position (a, b) is conv output
            # (y, x) = (pool_h*(ti*bhf + il) + a, pool_w*jj + b) of image
            # bb, row (bb*ho + y)*wo + x, column j*bco + c over the TRUE
            # (ho, wo, cout) — independent of tiling — so fq_matmul's
            # epilogue draws the identical field and the reference path
            # is bit-for-bit reproducible. Elements in the pitch/grid
            # padding draw values that are sliced away.
            shape3 = (t.bhf, t.wq, acc_ref.shape[-1])
            il = jax.lax.broadcasted_iota(jnp.int32, shape3, 0).reshape(
                t.m, -1)
            jj = jax.lax.broadcasted_iota(jnp.int32, shape3, 1).reshape(
                t.m, -1)
            y0 = t.pool[0] * ((p % n_i) * t.bhf + il)
            img0 = (p // n_i) * ho
        acc = None
        for k in range(t.n_pos):
            a_k = acc_ref[k]
            if noise:
                a, b = divmod(k, t.pool[1])
                rows = (img0 + y0 + a) * wo + t.pool[1] * jj + b
                a_k = a_k.astype(jnp.float32) + noise_tile(
                    rows, j * a_k.shape[1], cout, seed_ref[0, 0],
                    sigma_ref[0, 0], mac_chunks)
            # Code-domain maxpool hoisted onto the accumulators (the noisy
            # f32 ones when the noise epilogue ran — both f32 conversion
            # and the requant epilogue are monotone non-decreasing,
            # scale > 0, so max commutes either way): bit-exact with
            # int_maxpool2d over requantized codes, but the unpooled tile
            # never reaches HBM.
            acc = a_k if acc is None else jnp.maximum(acc, a_k)
        y = apply_epilogue(acc, scale_ref[0, 0], epilogue=epilogue,
                           n_out=n_out, lo=lo)
        if add:
            # the residual add and the ReLU after it, on the stream's
            # common scale: the branch's codes plus the shortcut's,
            # clipped to [0, n_out] (integer_inference.int_residual_add)
            y = jnp.clip(y.astype(jnp.int32) + res_ref[0].astype(jnp.int32),
                         0, n_out).astype(jnp.int8)
        o_ref[0] = y


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "stride", "padding", "dilation", "pool",
                     "epilogue", "n_out", "lo", "bho", "bco", "bc",
                     "mac_chunks", "interpret", "weight_format"),
)
def fq_conv2d(
    a_codes: jax.Array,   # (B, H, W, Cin) int8
    w_codes: jax.Array,   # (kh*kw*Cin, Cout) int8, tap-major; packed
                          # formats: (kh*kw*cin_p/factor, Cout) uint8
    scale: jax.Array,     # scalar f32: rescale (requant) or alpha (dequant)
    *,
    kh: int,
    kw: int,
    stride: Tuple[int, int] = (1, 1),
    padding: Tuple[int, int] = (0, 0),
    dilation: Tuple[int, int] = (1, 1),
    pool: Optional[Tuple[int, int]] = None,
    epilogue: str = "requant",
    n_out: int = 7,
    lo: int = 0,
    bho: Optional[int] = None,
    bco: Optional[int] = None,
    bc: Optional[int] = None,
    noise_sigma_acc: Optional[jax.Array] = None,
    noise_seed: Optional[jax.Array] = None,
    mac_chunks: int = 1,
    interpret: bool = False,
    weight_format: str = "int8",
    residual: Optional[jax.Array] = None,  # (B, Ho, Wo, Cout) int8 codes
) -> jax.Array:
    """Fused int8 NHWC conv2d with the requant/dequant epilogue in VMEM.

    ``weight_format`` in {"int8", "int4", "ternary"} selects weight
    storage. Packed weights keep the tap-major im2col layout but with the
    per-tap channel count padded up to the pack factor at conversion time
    (``cin_p = ceil(cin/factor)*factor``, pad codes 0) and every factor
    consecutive rows packed into one uint8 row — so each tap owns a whole
    number of byte rows. Activations are zero-padded to cin_p channels
    here, making the pad lanes 0*0 contributions; tiles are unpacked in
    VMEM before the MAC, so accumulator/pool/noise/epilogue behavior is
    bit-identical to the int8 path.

    ``pool=(ph, pw)`` additionally fuses a non-overlapping VALID maxpool
    (window == stride, e.g. (2, 2)) into the epilogue: the pool runs on the
    int32 accumulators before requant, so only the pooled tile reaches HBM.

    ``noise_sigma_acc`` (std in ACCUMULATOR units, the caller folds the
    paper's sigma_mac through the requant scale) + ``noise_seed`` (uint32)
    switch on the deterministic ADC-noise epilogue: the pre-pool int32
    accumulator is perturbed in VMEM before pool/requant, bit-for-bit
    reproducible by the im2col + fq_matmul path. ``mac_chunks=K`` models
    the chunked-accumulation mitigation (K per-chunk conversions at 1/K
    dynamic range -> effective noise std / sqrt(K)). When
    ``noise_sigma_acc`` is None the compiled program is the unchanged
    clean kernel.

    ``residual`` (shortcut codes shaped like the output) fuses a residual
    add and the ReLU after it into the requant epilogue: each output tile
    becomes ``clip(clip(round(acc * scale), lo, n_out) + residual, 0,
    n_out)`` before the int8 store, the shortcut's tile read into VMEM
    beside the accumulator's. The call is then named ``fq_conv2d_add``,
    so a device trace tells it from the plain conv. Without it the
    compiled program is the plain kernel.
    """
    assert epilogue in ("requant", "dequant")
    assert mac_chunks >= 1
    noise = noise_sigma_acc is not None
    assert not noise or noise_seed is not None, \
        "noise_seed is required when noise_sigma_acc is set"
    b, h, w, cin = a_codes.shape
    kcin, cout = w_codes.shape
    factor = quant.format_factor(weight_format)
    if weight_format != "int8":
        cin_p = -(-cin // factor) * factor
        assert kcin * factor == kh * kw * cin_p, \
            (w_codes.shape, (kh, kw, cin, weight_format))
        if cin_p != cin:
            # zero activation lanes to pair with the zero-code pad rows
            # packed at conversion time — 0 * 0 contributions, inert
            a_codes = jnp.pad(
                a_codes, ((0, 0), (0, 0), (0, 0), (0, cin_p - cin)))
            cin = cin_p
    else:
        assert kcin == kh * kw * cin, (w_codes.shape, (kh, kw, cin))
    sh, sw = stride
    dh, dw = dilation
    pad_h, pad_w = padding

    span_h, span_w = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    ho = (h + 2 * pad_h - span_h) // sh + 1
    wo = (w + 2 * pad_w - span_w) // sw + 1
    assert ho > 0 and wo > 0, (a_codes.shape, (kh, kw), stride, dilation)
    if pool is not None:
        assert pool[0] >= 1 and pool[1] >= 1
        assert ho >= pool[0] and wo >= pool[1], \
            f"pool {pool} larger than conv output ({ho}, {wo})"
    add = residual is not None
    if add:
        assert pool is None and epilogue == "requant", \
            "the residual add fuses into an unpooled requant epilogue"
        assert residual.shape == (b, ho, wo, cout), (residual.shape,
                                                     (b, ho, wo, cout))

    bho, bco, bc = pick_blocks(ho=ho, wo=wo, cin=cin, cout=cout, kh=kh,
                               kw=kw, stride=stride, pool=pool,
                               dilation=dilation, bho=bho, bco=bco, bc=bc,
                               weight_format=weight_format)
    t = tiling(bho=bho, wo=wo, kh=kh, kw=kw, stride=stride,
               dilation=dilation, pool=pool)
    h_out, w_out = ho // t.pool[0], wo // t.pool[1]
    n_i = pl.cdiv(h_out, t.bhf)
    n_j = pl.cdiv(cout, bco)
    cout_pad = n_j * bco
    n_cb = cin // bc

    # Edge-pad (or crop unread trailing pixels) to exactly the phase
    # images' extent, split into phases, then gather each row tile's
    # rows plus its halo: (B * n_i, phases, ht * wq, cin). Without a halo
    # (1x1 taps, no pool) the tiles partition the rows: a reshape.
    per_h, per_w = t.period
    hq = (n_i - 1) * t.bhf + t.ht
    x = jax.lax.pad(a_codes, jnp.zeros((), a_codes.dtype), (
        (0, 0, 0), (pad_h, per_h * hq - h - pad_h, 0),
        (pad_w, per_w * t.wq - w - pad_w, 0), (0, 0, 0)))
    x = x.reshape(b, hq, per_h, t.wq, per_w, cin)
    x = x.transpose(0, 2, 4, 1, 3, 5).reshape(b, t.n_phase, hq, t.wq, cin)
    if n_i > 1 and t.ht == t.bhf:
        x = jnp.moveaxis(x.reshape(b, t.n_phase, n_i, t.bhf, t.wq, cin), 2, 1)
    elif n_i > 1:
        rows = np.arange(n_i)[:, None] * t.bhf + np.arange(t.ht)[None, :]
        x = jnp.moveaxis(x[:, :, rows], 2, 1)
    x = x.reshape(b * n_i, t.n_phase, t.ht * t.wq, cin)

    w_taps = w_codes.reshape(kh * kw, kcin // (kh * kw), cout)
    if cout_pad != cout:
        w_taps = jnp.pad(w_taps, ((0, 0), (0, 0), (0, cout_pad - cout)))

    scalar_spec = pl.BlockSpec((1, 1), lambda p, j, c: (0, 0))
    in_specs = [
        scalar_spec,                                                 # scale
        pl.BlockSpec((1, t.n_phase, t.ht * t.wq, bc),
                     lambda p, j, c: (p, 0, 0, c)),                  # tile
        pl.BlockSpec((kh * kw, bc // factor, bco),
                     lambda p, j, c: (0, c, j)),                     # taps
    ]
    inputs = [scale.reshape(1, 1).astype(jnp.float32), x, w_taps]
    if noise:
        in_specs += [scalar_spec, scalar_spec]                   # sigma, seed
        inputs += [jnp.asarray(noise_sigma_acc, jnp.float32).reshape(1, 1),
                   jnp.asarray(noise_seed).astype(jnp.uint32).reshape(1, 1)]
    out_spec = pl.BlockSpec((1, t.m, bco), lambda p, j, c: (p, 0, j))
    if add:
        # the shortcut in the output's own flat tiling: rows padded to the
        # row tiles, columns to the pitch wq, channels to the out blocks
        res = jnp.pad(residual, ((0, 0), (0, n_i * t.bhf - ho),
                                 (0, t.wq - wo), (0, cout_pad - cout)))
        in_specs.append(out_spec)
        inputs.append(res.reshape(b * n_i, t.m, cout_pad))
    out_dtype = jnp.int8 if epilogue == "requant" else jnp.float32
    out = pl.pallas_call(
        functools.partial(
            _kernel, n_cb=n_cb, reads=_tap_reads(t, kh, kw, stride, dilation),
            t=t, epilogue=epilogue, n_out=n_out, lo=lo, noise=noise, add=add,
            mac_chunks=mac_chunks, n_i=n_i, ho=ho, wo=wo, cout=cout,
            weight_format=weight_format,
        ),
        grid=(b * n_i, n_j, n_cb),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b * n_i, t.m, cout_pad), out_dtype),
        scratch_shapes=[pltpu.VMEM((t.n_pos, t.m, bco), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="fq_conv2d_add" if add else "fq_conv2d",
    )(*inputs)
    out = out.reshape(b, n_i * t.bhf, t.wq, cout_pad)
    return out[:, :h_out, :w_out, :cout]


def fq_conv1d(
    a_codes: jax.Array,   # (B, T, Cin) int8
    w_codes: jax.Array,   # (ksize*Cin, Cout) int8
    scale: jax.Array,
    *,
    ksize: int,
    dilation: int = 1,
    epilogue: str = "requant",
    n_out: int = 7,
    lo: int = 0,
    noise_sigma_acc: Optional[jax.Array] = None,
    noise_seed: Optional[jax.Array] = None,
    mac_chunks: int = 1,
    interpret: bool = False,
    weight_format: str = "int8",
    **block_kw,
) -> jax.Array:
    """Fused int8 1-D conv (VALID, dilated — the paper's KWS layers).

    A (ksize, 1) conv2d over a width-1 spatial axis: the tap-major weight
    layout of conv1d is exactly the kw=1 conv2d layout, so this is free
    (the noise field's flattened (b*T_out + t)*cout + co indices also
    coincide with the 1-D im2col path's). ``weight_format`` follows the
    conv2d packed-weight contract.
    """
    y = fq_conv2d(
        a_codes[:, :, None, :], w_codes, scale, kh=ksize, kw=1,
        dilation=(dilation, 1), epilogue=epilogue, n_out=n_out, lo=lo,
        noise_sigma_acc=noise_sigma_acc, noise_seed=noise_seed,
        mac_chunks=mac_chunks, interpret=interpret,
        weight_format=weight_format, **block_kw,
    )
    return y[:, :, 0, :]
