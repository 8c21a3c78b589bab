"""BENCHMARK.json against the harness: every name resolves to its files,
and a new configuration, traffic mix or per-layer metric is found by name
with no edit to a file that is there."""
import json
import os
import re
import shutil
import types

import pytest

from bench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return cells.benchmark()


def test_unknown_device_kind_raises():
    with pytest.raises(cells.UnknownName, match="no peaks"):
        cells.peaks("TPU v9 imaginary")
    assert cells.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12


def test_benchmark_entries_resolve(bm):
    assert bm["command"][:2] == ["python3", "bench/run.py"]
    assert bm["paths"] == ["bench"]
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    names += [c["name"] for c in bm["configs"] + bm["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert {m["name"] for m in bm["end_to_end"]} >= {"setup_s"}
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    for c in bm["configs"]:
        spec = json.load(open(os.path.join(cells.ROOT, c["file"])))
        assert c["reduced"] == [] and c["source"] == spec["source"]
    for w in bm["workloads"]:
        cell = cells.find_cell(w["name"])
        assert cell.chips == w["chips"] in (1, 4)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
            assert callable(cells.metric_reader(m["name"]))
            assert UNIT.match(m["unit"])
    for m in bm["per_layer"]:
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in bm["workloads"]}


def test_unknown_names_raise():
    with pytest.raises(cells.UnknownName):
        cells.find_cell("no-such-cell")
    with pytest.raises(cells.UnknownName):
        cells.traffic("no_such_mix")
    with pytest.raises(cells.UnknownName):
        cells.metric_reader("no_such_metric.open")


def test_new_files_are_found_by_name(tmp_path):
    """A new config, mix and metric: new files and new entries only."""
    bench = tmp_path / "bench"
    shutil.copytree(cells.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        ".cache", "tests", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    bm = cells.benchmark()
    spec = json.load(open(bench / "configs" / "kws.json"))
    spec["seq_len"] = 200
    (bench / "configs" / "kws_long.json").write_text(json.dumps(spec))
    mix = json.load(open(bench / "traffic" / "bursty_clip.json"))
    mix["rate"] = 123
    (bench / "traffic" / "trickle.json").write_text(json.dumps(mix))
    (bench / "metrics" / "answered.py").write_text(
        "def read(run):\n    return float(run.window.completed)\n")
    bm["configs"].append({"name": "kws_long", "source": spec["source"],
                          "file": "bench/configs/kws_long.json",
                          "reduced": [], "why": "longer clips"})
    bm["workloads"].append({"name": "kws_long-trickle", "config": "kws_long",
                            "traffic": "trickle", "chips": 1, "why": "t"})
    bm["per_layer"].append({"name": "answered.open", "unit": "req",
                            "better": "higher", "source": "host_clock",
                            "layer": "batcher (serve.cnn_batching)",
                            "moves": "latency_p95",
                            "workloads": ["kws_long-trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = cells.find_cell("kws_long-trickle", root=str(tmp_path),
                           bench_dir=str(bench))
    assert cell.spec["seq_len"] == 200 and cell.traffic["rate"] == 123
    assert cell.model.rung_shape(cell.spec) == (200, 39)
    assert [m["name"] for m in cell.per_layer] == ["answered.open"]
    read = cells.metric_reader("answered.open", bench_dir=str(bench))
    run = types.SimpleNamespace(window=types.SimpleNamespace(completed=5))
    assert read(run) == 5.0
    # the stem rule: batch_fill.<anything> reads metrics/batch_fill.py
    assert cells.metric_reader("batch_fill.anything", bench_dir=str(bench))
    # no file that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())
