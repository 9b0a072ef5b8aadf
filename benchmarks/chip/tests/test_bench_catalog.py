"""The harness finds configurations, traffic mixes, limits and per-layer
metrics by name, so a later change adds files and edits none; and
BENCHMARK.json keeps to the shape the benchmark's contract sets."""
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(__file__))
import bench_tiny  # noqa: E402
from benchmarks.chip import catalog  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_discovers_a_throwaway_config_traffic_and_metric(tmp_path):
    bench, cell = bench_tiny.make(tmp_path, "qwen2-0.5b")
    (tmp_path / "metrics" / "throwaway.share.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['n_steps']\n")
    bench["per_layer"].append(dict(
        name="throwaway.share", unit="%", better="higher", source="host_clock",
        layer="trainer loop (train/trainer.py)", moves="tokens_per_s_per_chip",
        workloads=[cell]))
    c = catalog.cell(cell, bench, tmp_path, tmp_path)
    assert c["config"]["hidden_size"] == 128
    assert c["traffic"]["seq_len"] == 128
    assert c["limits"]["loss_gap"]["limit"] > 0
    assert "throwaway.share" in [m["name"] for m in c["per_layer"]]
    assert catalog.metric_reader("throwaway.share", tmp_path)({"n_steps": 3}) == 6.0


def test_every_cell_of_the_benchmark_resolves():
    bench = catalog.load_benchmark()
    for w in bench["workloads"]:
        c = catalog.cell(w["name"], bench)
        table = catalog.reference(c["config"]["reference"]).param_table(c["config"])
        assert table
        for m in c["per_layer"]:
            assert callable(catalog.metric_reader(m["name"]))


def test_benchmark_json_shape():
    bench = catalog.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (catalog.ROOT / c["file"]).is_file()
        cfg = json.loads((catalog.ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["layer"] in layers
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200
