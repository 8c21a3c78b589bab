"""kernellint: autotune-table schema + BlockSpec/grid/VMEM checks.

A bad ``autotune_table.json`` row should be a lint error, not a Mosaic
crash (or a silent fallback). Three layers of checking:

* **schema** — the raw JSON is validated directly (format tag, backend
  string, integer knobs, positive values), *independently* of the active
  backend: the loader silently skips malformed entries, the linter does
  not;
* **per-shape** — every conv geometry a stack actually serves is pushed
  through ``pick_blocks`` and the resulting (bho, bco, bc) is checked
  for grid divisibility (bc | cin, pool-aligned bho, positive grid), for
  block widths Mosaic accepts (a channel block is the whole extent or a
  multiple of 128 lanes) and for static VMEM footprint against the
  per-backend budget;
* **coverage** — served shape keys without a *measured* entry for the
  active backend are counted as structured misses (mirroring
  ``fq_conv.AutotuneMissWarning`` at serve time).
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Optional, Sequence, Tuple

import jax

from ..core import quant
from ..kernels import fq_conv
from .report import Report

# Hard lint ceiling for one grid step's static VMEM: the picker *targets*
# fq_conv._VMEM_BUDGET, but explicit/table knobs may exceed it; past 2x the
# target a TPU core's ~16 MiB VMEM (double-buffered pipelines, both
# operands resident) is at real risk, so the linter draws the line there.
VMEM_LINT_BUDGET = {
    "tpu": 2 * fq_conv._VMEM_BUDGET,
    # interpret-mode backends have no VMEM, but keeping the same ceiling
    # means a table tuned on CPU cannot smuggle an over-budget row onto TPU
    "cpu": 2 * fq_conv._VMEM_BUDGET,
    "gpu": 2 * fq_conv._VMEM_BUDGET,
}

_KNOBS = ("bho", "bco", "bc")


@dataclasses.dataclass(frozen=True)
class ConvShape:
    """One conv geometry a stack serves (post-padding output extents)."""

    name: str                      # "kws/conv3"
    ho: int
    wo: int
    cin: int
    cout: int
    kh: int
    kw: int
    stride: Tuple[int, int] = (1, 1)
    pool: Optional[Tuple[int, int]] = None
    weight_format: str = "int8"
    dilation: Tuple[int, int] = (1, 1)

    @property
    def key(self) -> Tuple[int, int, int, str]:
        return (self.kh, self.kw, self.stride[0], self.weight_format)


def lint_table_schema(report: Report,
                      path: str = fq_conv.AUTOTUNE_TABLE_PATH):
    """Validate the raw JSON: every row must be loadable on its backend."""
    subject = f"autotune:{path.rsplit('/', 1)[-1]}"
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError:
        report.info("kernellint/table-schema", subject,
                    "no autotune table on disk — builtin defaults only")
        return
    except ValueError as e:
        report.error("kernellint/table-schema", subject,
                     f"unparseable JSON: {e}")
        return
    if not isinstance(doc, dict):
        report.error("kernellint/table-schema", subject,
                     f"top level is {type(doc).__name__}, expected object")
        return
    if doc.get("format") != 1:
        report.error("kernellint/table-schema", subject,
                     f"format={doc.get('format')!r} (expected 1) — the "
                     "loader ignores the whole file")
    if not isinstance(doc.get("backend"), str) or not doc.get("backend"):
        report.error("kernellint/table-schema", subject,
                     f"backend={doc.get('backend')!r} is not a non-empty "
                     "string — entries can never match any backend")
    entries = doc.get("entries", [])
    if not isinstance(entries, list):
        report.error("kernellint/table-schema", subject,
                     f"entries is {type(entries).__name__}, expected list")
        return
    seen = {}
    bad = 0
    for i, e in enumerate(entries):
        esub = f"{subject}[{i}]"
        if not isinstance(e, dict):
            bad += 1
            report.error("kernellint/table-schema", esub,
                         f"entry is {type(e).__name__}, expected object")
            continue
        try:
            key = (int(e["kh"]), int(e["kw"]), int(e["stride"]))
        except (KeyError, TypeError, ValueError):
            bad += 1
            report.error(
                "kernellint/table-schema", esub,
                f"missing/non-integer shape key fields in {e!r} — the "
                "loader silently skips this row", entry=repr(e))
            continue
        if any(k <= 0 for k in key):
            bad += 1
            report.error("kernellint/table-schema", esub,
                         f"non-positive shape key {key}", key=key)
        fmt = e.get("format", "int8")
        if not isinstance(fmt, str) or fmt not in quant.WEIGHT_FORMATS:
            bad += 1
            report.error(
                "kernellint/table-schema", esub,
                f"unknown weight format {fmt!r} for key {key} (known: "
                f"{quant.WEIGHT_FORMATS}) — the loader silently skips "
                "this row", key=key, format=repr(fmt))
            continue
        key = key + (fmt,)
        if fmt != "int8" and e.get("bc") is not None:
            report.warning(
                "kernellint/table-schema", esub,
                f"packed entry {key} carries bc={e['bc']!r} — pick_blocks "
                "fixes packed bc to the padded cin, so this knob is dead",
                key=key, bc=e["bc"])
        knobs = {}
        for k in _KNOBS:
            if k not in e or e[k] is None:
                continue
            if not isinstance(e[k], int) or isinstance(e[k], bool) \
                    or e[k] < 1:
                bad += 1
                report.error(
                    "kernellint/table-schema", esub,
                    f"knob {k}={e[k]!r} is not a positive int — the "
                    "loader silently drops this row", knob=k,
                    value=repr(e[k]))
            else:
                knobs[k] = e[k]
        if not knobs:
            report.warning("kernellint/table-schema", esub,
                           f"entry {key} carries no block knobs — it "
                           "overrides builtins with nothing", key=key)
        if key in seen:
            report.error("kernellint/table-schema", esub,
                         f"duplicate entry for key {key} (first at index "
                         f"{seen[key]}) — last-writer-wins is ambiguous",
                         key=key, first=seen[key])
        else:
            seen[key] = i
    report.count("kernellint/table-entries", len(entries))
    if not bad and entries:
        report.prove("kernellint/table-schema", subject,
                     f"all {len(entries)} rows well-formed "
                     f"(backend={doc.get('backend')!r})")


def lint_shapes(shapes: Sequence[ConvShape], report: Report, *,
                backend: Optional[str] = None,
                table: Optional[dict] = None,
                measured: Optional[set] = None):
    """Push every served geometry through the block picker and check the
    result. ``table``/``measured`` default to the live fq_conv caches
    (pass explicit values to lint a candidate table file)."""
    backend = backend or jax.default_backend()
    budget = VMEM_LINT_BUDGET.get(backend, 2 * fq_conv._VMEM_BUDGET)
    if table is None:
        table = fq_conv._autotune_table()
        measured = fq_conv.MEASURED_KEYS or set()
    measured = measured or set()

    clean = True
    missed = {}
    for s in shapes:
        sub = s.name
        packed = s.weight_format != "int8"
        # packed kernels read whole bytes: the effective channel extent is
        # cin padded to the pack factor (activations are zero-padded to
        # match; pad lanes are inert in the integer MAC)
        factor = quant.format_factor(s.weight_format)
        cin_eff = -(-s.cin // factor) * factor
        over = table.get(s.key, {})
        # mirror serve-time semantics for the table's bc knob: pick_blocks
        # rounds a table bc down to a cin divisor (only an *explicit* bc
        # must divide exactly), so a non-divisor row serves fine — but the
        # measured winner silently doesn't apply, which is worth a warning.
        # Packed shapes never take a table bc (bc is fixed to cin_eff).
        over_bc = over.get("bc") if not packed else None
        if over_bc is not None and s.cin % over_bc != 0:
            eff = fq_conv._divisor_at_most(s.cin, over_bc)
            report.warning(
                "kernellint/table-drift", sub,
                f"table bc={over_bc} for key {s.key} does not divide "
                f"cin={s.cin} — serving rounds down to bc={eff}, so the "
                "measured winner is not what actually runs",
                key=s.key, table_bc=over_bc, effective_bc=eff)
            over_bc = eff
        try:
            bho, bco, bc = fq_conv.pick_blocks(
                ho=s.ho, wo=s.wo, cin=s.cin, cout=s.cout, kh=s.kh,
                kw=s.kw, stride=s.stride, pool=s.pool, dilation=s.dilation,
                bho=over.get("bho"), bco=over.get("bco"), bc=over_bc,
                weight_format=s.weight_format)
        except ValueError as e:
            clean = False
            report.error("kernellint/blockspec", sub,
                         f"pick_blocks rejected table knobs {over} for "
                         f"{s}: {e}", key=s.key, knobs=over)
            continue

        # grid divisibility invariants the kernel's index maps assume
        if cin_eff % bc != 0:
            clean = False
            report.error(
                "kernellint/blockspec", sub,
                f"bc={bc} does not divide cin={cin_eff} — weight-row "
                "reads cross a tap boundary", bc=bc, cin=cin_eff)
        if s.pool is not None and bho % s.pool[0] != 0:
            clean = False
            report.error(
                "kernellint/blockspec", sub,
                f"bho={bho} not a multiple of fused pool height "
                f"{s.pool[0]} — pool windows straddle the row tile",
                bho=bho, pool=s.pool)
        if bco < 1 or bho < 1 or bc < 1:
            clean = False
            report.error("kernellint/blockspec", sub,
                         f"non-positive block ({bho}, {bco}, {bc})")
        # Mosaic's block rule: a minor block dim is the array's whole
        # extent or a multiple of the 128-lane tile (interpret-mode
        # backends take any width, and never load a TPU table)
        for knob, blk, full in (("bc", bc, cin_eff), ("bco", bco, s.cout)):
            if backend == "tpu" and blk != full and blk % fq_conv._LANES:
                clean = False
                report.error(
                    "kernellint/blockspec", sub,
                    f"{knob}={blk} is neither the whole extent {full} nor "
                    f"a multiple of {fq_conv._LANES} lanes — Mosaic "
                    "refuses the block", knob=knob, block=blk, extent=full)
        grid = (math.ceil(s.ho / bho), math.ceil(s.cout / bco),
                cin_eff // max(bc, 1))
        if any(g < 1 for g in grid):
            clean = False
            report.error("kernellint/blockspec", sub,
                         f"degenerate grid {grid}", grid=grid)

        vmem = fq_conv.vmem_footprint(bho=bho, wo=s.wo, bco=bco, bc=bc,
                                      kh=s.kh, kw=s.kw, stride=s.stride,
                                      dilation=s.dilation, pool=s.pool,
                                      weight_format=s.weight_format)
        report.count("kernellint/shapes-checked")
        if vmem > budget:
            clean = False
            report.error(
                "kernellint/vmem", sub,
                f"static VMEM footprint {vmem / 2**20:.2f} MiB for blocks "
                f"({bho}, {bco}, {bc}) exceeds the {backend} lint budget "
                f"{budget / 2**20:.2f} MiB — this row OOMs before it "
                "computes", vmem_bytes=vmem, budget=budget,
                blocks=(bho, bco, bc))

        if s.key not in measured:
            missed.setdefault(s.key, []).append(s.name)

    for key, names in sorted(missed.items()):
        report.warning(
            "kernellint/autotune-miss", names[0],
            f"served shape key {key} has no measured autotune entry for "
            f"backend {backend!r} ({len(names)} layer(s): "
            f"{', '.join(names)}) — serving falls back to builtin "
            "defaults", key=key, backend=backend, layers=names)
        report.count("kernellint/autotune-misses")

    if clean and shapes:
        report.prove(
            "kernellint/blockspec", f"{len(shapes)} served shapes",
            f"block picks divide their grids, are lane-legal and fit "
            f"the {backend} VMEM "
            f"lint budget ({budget / 2**20:.1f} MiB)",
            shapes=len(shapes))


def runtime_miss_counters(report: Report):
    """Fold fq_conv's serve-time miss counters into the report.

    Besides the global per-key counts, the serving mesh records misses
    per replica lane (``AUTOTUNE_MISSES_BY_REPLICA``, tagged via
    ``fq_conv.replica_scope``). Replicas in one process share a backend
    family, so they should trace the same shapes against the same table
    — a lane whose miss-key set diverges from the union means the lanes
    are NOT serving identical compiled work (e.g. a per-replica swap
    half-landed, or a lane compiled a shape the others never saw), which
    is worth a warning before it becomes a latency mystery."""
    for key, n in sorted(fq_conv.AUTOTUNE_MISSES.items()):
        report.count(f"kernellint/runtime-miss:{key}", n)
    per: dict = {}
    for (tag, key), n in sorted(fq_conv.AUTOTUNE_MISSES_BY_REPLICA.items(),
                                key=lambda kv: (str(kv[0][0]), kv[0][1])):
        report.count(f"kernellint/runtime-miss:replica[{tag}]:{key}", n)
        per.setdefault(tag, set()).add(key)
    if len(per) > 1:
        union = set().union(*per.values())
        for tag in sorted(per, key=str):
            missing = union - per[tag]
            if missing:
                report.warning(
                    "kernellint/replica-miss-divergence", f"replica[{tag}]",
                    f"replica {tag!r} reported autotune misses for "
                    f"{sorted(per[tag])} but same-backend peers also missed "
                    f"{sorted(missing)} — replica lanes are not tracing "
                    "identical work", replica=tag,
                    missing=sorted(map(str, missing)))
