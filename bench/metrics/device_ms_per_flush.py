"""Model step: device busy time in the traced window (summed over the
cell's chips) per flush dispatched in it."""


def read(run):
    if run.trace is None or not run.window.counters["flushes"]:
        return None
    busy = sum(run.trace["busy_s_by_device"].values())
    return 1e3 * busy / run.window.counters["flushes"]
