"""Measured-sweep autotuner for the fused Pallas conv kernel.

    PYTHONPATH=src python -m benchmarks.autotune_conv [--full] [--no-persist]

Replaces the placeholder AUTOTUNE_TABLE entries with *measured* winners:
for each benchmark shape the harness sweeps the kernel's (bho, bco, bc)
block knobs, times each candidate (compiled on TPU; interpret mode on CPU,
which validates the pipeline but says nothing about Mosaic — the loader in
kernels/fq_conv.py therefore only applies entries whose recorded backend
matches the running one), verifies the winner's codes against the default
blocking, and persists:

  * ``src/repro/kernels/autotune_table.json`` — the winners, keyed
    (kh, kw, stride), loaded by ``kernels.fq_conv`` at import,
  * ``BENCH_autotune.json`` — the full sweep record (every candidate's
    wall time), so a regression in the table is diagnosable.

Run this once per backend family; re-run after kernel changes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.core import quant
from repro.kernels import fq_conv
from benchmarks import common

# One canonical shape per (kh, kw, stride) table key. B=2 matches the
# batch-folded serving grid; pooled variants ride the same key (the pool
# only changes the epilogue, not the blocking trade-off). ``ks`` is an int
# (square) or a (kh, kw) pair — the KWS stack serves 1-D convs as
# (ksize, 1) kernels on (B, T, 1, C) planes, under their own table key.
SHAPES = [
    # name,            B, H,  W,  cin, cout, ks, stride, pad, pool
    ("darknet_3x3_s1", 2, 28, 28, 32,  64,   3,  1,      1,   None),
    ("darknet_3x3_pool", 2, 28, 28, 32, 64,  3,  1,      1,   2),
    ("downsample_3x3_s2", 2, 28, 28, 64, 128, 3,  2,      1,   None),
    ("pointwise_1x1",  2, 14, 14, 128, 128,  1,  1,      0,   None),
    # KWS dilated conv1d: dilation only moves the static tap offsets and
    # adds halo rows, so one undilated (3, 1) sweep covers the ladder.
    ("kws_3x1_s1",     2, 138, 1, 45,  45, (3, 1), 1,    0,   None),
]


def _khkw(ks):
    return ks if isinstance(ks, tuple) else (ks, ks)

# Weight formats swept per shape: each gets its own table key (kh, kw,
# stride, format). Packed formats fix bc to the factor-padded cin (whole
# byte rows), so their candidate grid is bho x bco only.
FORMATS = ("int8", "ternary", "int4")

# --dry-run: one tiny shape, minimal candidates — exercises the full
# sweep -> verify -> persist pipeline in seconds (schema/round-trip tests).
DRY_SHAPES = [
    ("dry_3x3_s1", 1, 8, 8, 8, 8, 3, 1, 1, None),
]
DRY_FORMATS = ("int8", "ternary")


def _candidates(*, ho, wo, cin, cout, kh, kw, pool, full: bool,
                weight_format: str = "int8"):
    bhos = [8, 16, 32, 64, 128] if full else [8, 32, 128]
    bcos = [32, 64, 128, 256] if full else [64, 128]
    if weight_format != "int8":
        bcs = [None]  # pick_blocks fixes packed bc to the padded cin
    else:
        bcs = [d for d in (8, 16, 32, 64, 128, 256) if cin % d == 0] or [cin]
        if not full:
            bcs = bcs[-2:]
    seen, out = set(), []
    for bho in bhos:
        for bco in bcos:
            for bc in bcs:
                # normalize to what pick_blocks will actually use, so the
                # sweep doesn't time the same effective blocking twice
                eff = fq_conv.pick_blocks(
                    ho=ho, wo=wo, cin=cin, cout=cout, kh=kh, kw=kw,
                    stride=(1, 1), pool=(pool, pool) if pool else None,
                    bho=bho, bco=bco, bc=bc, weight_format=weight_format)
                if eff in seen:
                    continue
                seen.add(eff)
                out.append(eff)
    return out


def _time_one(a, w, scale, *, ks, stride, pad, pool, bho, bco, bc, interpret,
              weight_format="int8", reps=2):
    kh, kw = _khkw(ks)

    def call():
        return fq_conv.fq_conv2d(
            a, w, scale, kh=kh, kw=kw, stride=(stride, stride),
            padding=(pad, pad), pool=(pool, pool) if pool else None,
            n_out=15, lo=0, bho=bho, bco=bco, bc=bc, interpret=interpret,
            weight_format=weight_format)
    return call, common.timer(call, reps=reps)


def sweep(full: bool = False, shapes=SHAPES, reps: int = 2,
          formats=FORMATS):
    backend = jax.default_backend()
    interpret = backend != "tpu"
    rows, winners = [], {}
    k1, k2 = jax.random.split(jax.random.key(0))
    for name, B, H, W, cin, cout, ks, stride, pad, pool in shapes:
        kh, kw = _khkw(ks)
        a = jax.random.randint(k1, (B, H, W, cin), 0, 16).astype(jnp.int8)
        scale = jnp.float32(0.01)
        ho = (H + 2 * pad - kh) // stride + 1
        wo = (W + 2 * pad - kw) // stride + 1
        for fmt in formats:
            # codes drawn in the format's own range, packed to its layout
            n_w = quant.format_range(fmt)
            w_int8 = jax.random.randint(
                k2, (kh * kw * cin, cout), -n_w, n_w + 1).astype(jnp.int8)
            w = w_int8 if fmt == "int8" else \
                quant.pack_im2col_codes(w_int8, kh * kw, fmt)
            fname = name if fmt == "int8" else f"{name}_{fmt}"
            ref_call, _ = _time_one(
                a, w, scale, ks=ks, stride=stride, pad=pad, pool=pool,
                bho=None, bco=None, bc=None, interpret=interpret,
                weight_format=fmt, reps=reps)
            ref = np.asarray(ref_call())
            best = None
            for bho, bco, bc in _candidates(
                    ho=ho, wo=wo, cin=cin, cout=cout, kh=kh, kw=kw,
                    pool=pool, full=full, weight_format=fmt):
                call, us = _time_one(
                    a, w, scale, ks=ks, stride=stride, pad=pad, pool=pool,
                    bho=bho, bco=bco, bc=bc, interpret=interpret,
                    weight_format=fmt, reps=reps)
                rows.append(dict(shape=fname, kh=kh, kw=kw, stride=stride,
                                 format=fmt, pool=pool, bho=bho, bco=bco,
                                 bc=bc, wall_us=round(us, 1)))
                if best is None or us < best[0]:
                    best = (us, (bho, bco, bc), call)
                print(f"autotune,{fname},bho={bho} bco={bco} bc={bc},"
                      f"{us:.0f}us")
            us, (bho, bco, bc), call = best
            # blocking must never change the codes — verify the winner
            # against the default blocking of the SAME format
            np.testing.assert_array_equal(np.asarray(call()), ref)
            key = (kh, kw, stride, fmt)
            # the unpooled canonical shape owns the key; pooled variant
            # only claims it if nothing else has
            if key not in winners or pool is None:
                winners[key] = dict(kh=kh, kw=kw, stride=stride, format=fmt,
                                    bho=bho, bco=bco, bc=bc,
                                    wall_us=round(us, 1), shape=fname, ho=ho)
                # a bho that equals the sweep shape's (pool-rounded) output
                # plane was clipped, not chosen — persisting it would cap
                # row blocking on larger planes that were never measured
                plane = ho - (ho % pool) if pool else ho
                if bho >= plane:
                    winners[key].pop("bho")
                # likewise bc == cin is "no channel blocking", not a
                # measured sub-blocking choice; persisting it would force a
                # non-divisor (rounded-down) bc onto served shapes with a
                # different cin under the same key (e.g. kws conv0's embed
                # width). Packed entries never carry bc: serving fixes it
                # to the factor-padded cin of whatever shape is served.
                if fmt != "int8" or bc >= cin:
                    winners[key].pop("bc")
            print(f"autotune,{fname}_winner,bho={bho} bco={bco} bc={bc},"
                  f"{us:.0f}us")
    return backend, rows, winners


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="wider candidate grid (slower)")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny shape + minimal candidates: exercise the "
                         "sweep->verify->persist pipeline in seconds "
                         "(use with --table/--record tmp paths)")
    ap.add_argument("--no-persist", action="store_true",
                    help="sweep and report only; don't rewrite the table")
    ap.add_argument("--table", default=fq_conv.AUTOTUNE_TABLE_PATH)
    ap.add_argument("--record", default="BENCH_autotune.json")
    args = ap.parse_args(argv)
    if args.dry_run:  # never let throwaway data clobber checked-in artifacts
        ap_ = os.path.abspath
        if ap_(args.record) == ap_("BENCH_autotune.json"):
            ap.error("--dry-run would overwrite the checked-in "
                     "BENCH_autotune.json; pass --record <tmp path>")
        if not args.no_persist and \
                ap_(args.table) == ap_(fq_conv.AUTOTUNE_TABLE_PATH):
            ap.error("--dry-run would overwrite the checked-in table; pass "
                     "--table <tmp path> (or --no-persist)")

    t0 = time.time()
    backend, rows, winners = sweep(
        full=args.full,
        shapes=DRY_SHAPES if args.dry_run else SHAPES,
        reps=1 if args.dry_run else 2,
        formats=DRY_FORMATS if args.dry_run else FORMATS)
    doc = {
        "format": 1,
        "backend": backend,
        "generated_by": "benchmarks/autotune_conv.py",
        "note": ("interpret-mode timings; kernels/fq_conv.py ignores these "
                 "entries on other backends" if backend != "tpu"
                 else "compiled Mosaic timings"),
        "entries": sorted(winners.values(),
                          key=lambda e: (e["kh"], e["kw"], e["stride"],
                                         e["format"])),
    }
    with open(args.record, "w") as f:
        json.dump({"benchmark": "fq_conv_autotune_sweep", "backend": backend,
                   "rows": rows, "winners": doc["entries"]}, f, indent=2)
    print(f"autotune,record,{args.record},{len(rows)} candidates")
    if not args.no_persist:
        with open(args.table, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"autotune,table,{args.table},{len(winners)} keys")
    print(f"autotune,done,{time.time()-t0:.1f}s,")
    return 0


if __name__ == "__main__":
    sys.exit(main())
