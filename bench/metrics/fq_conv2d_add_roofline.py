"""Kernels: the fused conv + residual add's share of its roofline. Least
time of every ``fq_conv2d_add`` call of every flush in the window -- the
larger of its operations over the int8 peak and its bytes over the HBM
peak, from the layer's shapes at the flushed batch (``fq_conv_add_calls``)
-- over the device time of the ``fq_conv2d_add`` events in the traced
window. A program without the fused add has no such events: nothing to
read."""


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace["op_s"].get("fq_conv2d_add", 0.0)
    calls = getattr(run.cell.model, "fq_conv_add_calls", None)
    if device_s <= 0 or calls is None:
        return None
    p, spec = run.peaks, run.cell.spec
    per_slots = {}
    least = 0.0
    for slots in run.window.flush_slots:
        if slots not in per_slots:
            per_slots[slots] = sum(
                max(ops / p["int8_ops_per_s"], nbytes / p["hbm_bytes_per_s"])
                for ops, nbytes in calls(spec, slots))
        least += per_slots[slots]
    return 100.0 * least / device_s
