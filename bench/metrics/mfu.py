"""Model step: the whole step's share of the chips' peak. The operations a
request needs (``request_ops``; integer at the int8 peak, float edges at
the bf16 peak), times requests answered per second in the window, over the
cell's chips."""


def read(run):
    ops = run.cell.model.request_ops(run.cell.spec)
    p = run.peaks
    least_s = ops["int"] / p["int8_ops_per_s"] + \
        ops["float"] / p["bf16_flops_per_s"]
    rate = run.window.completed / run.window.seconds
    return 100.0 * least_s * rate / run.chips
