"""Run one benchmark cell once, on the TPU chips of this machine.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell is looked up in ``BENCHMARK.json``; set-up builds the system under
test from seeded weights and warms every batch bucket the cell uses, then
the window serves the cell's traffic for ``--seconds``. ``--trace 0``
reports the end-to-end metrics, ``--trace 1`` traces the window with the
JAX profiler and reports the per-layer metrics. Every answer due in the
window is checked against the configuration's plain reference.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared, with its limit).
Progress and the checks go to standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits with code 3 and prints no
result; where a per-layer metric that ``BENCHMARK.json`` lists for the cell
finds nothing in the trace, with code 4.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells, session, trace_reduce  # noqa: E402
from bench.session import log, percentile  # noqa: E402

END_TO_END = {
    "throughput": lambda w, setup: w.completed / w.seconds,
    "latency_p50": lambda w, setup: percentile(w.latency_ms, 50),
    "latency_p95": lambda w, setup: percentile(w.latency_ms, 95),
    "setup_s": lambda w, setup: setup,
}


def _num(v):
    return v if v is None or math.isfinite(v) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell = cells.find_cell(args.workload)
    except (cells.UnknownName, KeyError, OSError) as e:
        log(f"error: {e}")
        return 2
    try:
        devices = session.require_chips(cell.chips)
    except session.NoChip as e:
        log(f"error: {e}")
        return 3

    import jax
    from repro import compile_cache
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"cell {cell.name}: {devices[0].device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, compile cache {cache_dir}")
    peaks = cells.peaks(devices[0].device_kind)

    clock = {"t": time.perf_counter()}
    log(f"setup: process start -> jax ready "
        f"{clock['t'] - T_START:.3f}s")

    def phase(name):
        now = time.perf_counter()
        log(f"setup: {name} {now - clock['t']:.3f}s")
        clock["t"] = now

    sess = session.Session(cell, devices, phase)
    trace_dir = (os.path.join(cells.BENCH_DIR, ".cache", "trace", cell.name)
                 if args.trace else None)
    win = sess.measure(args.seed, args.seconds, phase, trace_dir)
    setup_s = win.t0 - T_START
    log(f"window: {win.seconds}s, {win.attempted} due, {win.completed} "
        f"answered in the window, {win.failed} failed; counters "
        f"{win.counters}; compiles in the window {win.compiles}")
    if len(win.lateness_ms):
        log(f"generator lateness (submit - due): p50 "
            f"{percentile(win.lateness_ms, 50):.4f} ms, p95 "
            f"{percentile(win.lateness_ms, 95):.4f} ms, max "
            f"{win.lateness_ms.max():.4f} ms")
    memory = sess.memory_peak_bytes()
    params, state = sess.params, sess.state
    sess.release()
    t = time.perf_counter()
    correct, checks = session.check(cell, params, state, win)
    log(f"reference check of {len(win.answers)} answers over "
        f"{len(win.pool)} payloads: {time.perf_counter() - t:.3f}s")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed}
    if args.trace:
        run = types.SimpleNamespace(cell=cell, window=win, trace=win.trace,
                                    peaks=peaks, chips=len(devices),
                                    max_batch=sess.max_batch)
        metrics = {}
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            elif "workloads" in m:  # listed for this cell: a broken reading
                log(f"error: metric {m['name']} found nothing to read in "
                    f"a cell that lists it")
                return 4
        device.update(busy_s=win.trace["busy_s"],
                      window_s=win.trace["window_s"])
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": trace_reduce.top(win.trace["op_s"]),
            "idle_gaps": trace_reduce.top(win.trace["idle_s"])})
    else:
        result.update(device=device, metrics={
            m["name"]: {"value": _num(END_TO_END[m["name"]](win, setup_s)),
                        "unit": m["unit"]}
            for m in cell.end_to_end})
    for name, c in checks.items():
        c["value"] = _num(c["value"])
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
