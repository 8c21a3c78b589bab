"""Batcher: answered requests over the rows the window's flushes offered,
``served / (flushes * max_batch)``, from the batcher's own counters."""


def read(run):
    c = run.window.counters
    if not c["flushes"]:
        return None
    return 100.0 * c["served"] / (c["flushes"] * run.max_batch)
