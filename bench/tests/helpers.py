"""Small configurations and mixes for running the harness on the CPU."""
import copy
import json
import os

from bench import cells

TINY_DARKNET = {
    "layers": [[3, 8], "M", [3, 16], "M", [3, 16], [1, 8], [3, 16]],
    "num_classes": 16, "input_hw": [32, 32],
    "batcher": {"rungs": [32], "max_batch": 4, "dispatch_ahead": True,
                "max_inflight": 2, "max_wait_ticks": 0},
}
TINY_KWS = {
    "n_mfcc": 8, "embed": 16, "filters": 8, "dilations": [1, 1, 2],
    "num_classes": 4, "seq_len": 24,
    "batcher": {"rungs": [24], "max_batch": 8, "dispatch_ahead": True,
                "max_inflight": 2, "max_wait_ticks": 0},
}
TINY_CLOSED = {
    "loop": "closed", "clients": 6, "ramp_requests": 8, "pool": 8,
    "shape_seed": 0,
    "payload": [{"share": 0.75, "spatial": [[32, 32], [32, 32]]},
                {"share": 0.25, "spatial": [[24, 40], [24, 40]]}],
}
TINY_OPEN = {
    "loop": "open", "rate": 60, "burst": {"p": 0.2, "size": 4}, "pool": 16,
    "shape_seed": 0,
    "payload": [{"share": 0.75, "spatial": [[14, 14]]},
                {"share": 0.25, "spatial": [[15, 30]]}],
}


def spec(config: str, **small):
    with open(os.path.join(cells.BENCH_DIR, "configs", f"{config}.json")) as f:
        out = json.load(f)
    out.update(copy.deepcopy(small))
    return out


def tiny_cell(model: str, traffic, chips: int = 1):
    small = TINY_DARKNET if model == "darknet" else TINY_KWS
    s = spec("darknet19" if model == "darknet" else "kws", **small)
    return cells.Cell(name=f"tiny-{model}", chips=chips, spec=s,
                      traffic=copy.deepcopy(traffic),
                      model=cells.model(model), end_to_end=[], per_layer=[])
