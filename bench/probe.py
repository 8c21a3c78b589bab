"""Readings behind the benchmark's settings, many in one process on the chip.

    python3 bench/probe.py readings --workload W --seeds 1,2,3 --seconds 3
    python3 bench/probe.py sweep --workload W --rates 2000,4000 --seconds 5
    python3 bench/probe.py trace --workload W --seconds 0.05 --out DIR

* ``readings`` builds the cell's system once and, for each seed, serves a
  window of the cell's own traffic and prints the number ``correct``
  compares (``logit_gap``) for the program, and for the control: the
  plain reference computed with bf16x3 float edges, in the program's place,
  over the same payloads. The limits in the configuration files are set
  from these readings (PERF.md).
* ``sweep`` serves the cell's open-loop mix at each offered rate in turn
  and prints what was answered and the latency percentiles: the knee
  behind an open-loop mix's ``rate``.
* ``trace`` records a short traced window and keeps its ``.xplane.pb``
  (the reducer's test fixture) and prints the trace's planes and lines.

Each prints one JSON line per reading on standard output. Like
``run.py`` it measures only on a TPU.
"""
import argparse
import copy
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, session  # noqa: E402
from bench.session import log, percentile  # noqa: E402


def _session(cell):
    import jax
    from repro import compile_cache
    devices = session.require_chips(cell.chips)
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = {"t": time.perf_counter()}

    def phase(name):
        now = time.perf_counter()
        log(f"{cell.name}: {name} {now - clock['t']:.3f}s")
        clock["t"] = now

    return session.Session(cell, devices, phase), phase


def _summary(win):
    return {"attempted": win.attempted, "failed": win.failed,
            "throughput": win.completed / win.seconds,
            "latency_p50": percentile(win.latency_ms, 50),
            "latency_p95": percentile(win.latency_ms, 95),
            "lateness_p95": percentile(win.lateness_ms, 95),
            "counters": win.counters, "compiles": win.compiles}


def readings(args):
    cell = cells.find_cell(args.workload)
    sess, phase = _session(cell)
    model, spec = cell.model, cell.spec
    for seed in (int(s) for s in args.seeds.split(",")):
        win = sess.measure(seed, args.seconds, phase)
        ref = model.Reference(spec, sess.params, sess.state,
                              spec["edge_precision"]).logits(win.pool)
        ctl = model.Reference(spec, sess.params, sess.state,
                              "bf16x3").logits(win.pool)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "logit_gap": session.gap_of(win.answers, ref),
            "control_gap": session.gap_of(list(enumerate(ctl)), ref),
            "logit_span_min": float((ref.max(-1) - ref.min(-1)).min()),
            "top1_moved_by_control": int(
                (ctl.argmax(-1) != ref.argmax(-1)).sum()),
            **_summary(win)}), flush=True)


def sweep(args):
    cell = cells.find_cell(args.workload)
    sess, phase = _session(cell)
    base = cell.traffic
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(copy.deepcopy(base), rate=rate)
        win = sess.measure(args.seed, args.seconds, phase)
        row = _summary(win)
        print(json.dumps({"workload": cell.name, "offered": rate, **row}),
              flush=True)
        if row["throughput"] < 0.9 * rate:
            break  # past the knee: a backlog grows


def trace(args):
    from jax.profiler import ProfileData
    cell = cells.find_cell(args.workload)
    sess, phase = _session(cell)
    tmp = os.path.join(cells.BENCH_DIR, ".cache", "trace", "probe")
    try:
        win = sess.measure(args.seed, args.seconds, phase, trace_dir=tmp)
    except ValueError as e:  # a trace the reducer cannot read: show it
        log(f"reduce failed: {e}")
        win = None
    path = session._xplane(tmp)
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(path, os.path.join(args.out, os.path.basename(path)))
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            print(json.dumps({"plane": plane.name, "line": line.name,
                              "events": len(evs),
                              "first": [(e.name, e.start_ns, e.duration_ns)
                                        for e in evs[:3]]}), flush=True)
    if win is not None:
        print(json.dumps({"reduced": win.trace, **_summary(win)}),
              flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("readings", "sweep", "trace"):
        p = sub.add_parser(name)
        p.add_argument("--workload", required=True)
        p.add_argument("--seconds", type=float, required=True)
        p.add_argument("--seed", type=int, default=7)
    sub.choices["readings"].add_argument("--seeds", required=True)
    sub.choices["sweep"].add_argument("--rates", required=True)
    sub.choices["trace"].add_argument("--out", required=True)
    args = ap.parse_args(argv)
    {"readings": readings, "sweep": sweep, "trace": trace}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
