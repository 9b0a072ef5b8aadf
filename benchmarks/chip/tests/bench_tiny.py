"""Tiny cells for the CPU tests: the real configurations and jobs with
every size cut down, in a scratch directory laid out like the benchmark's
(configs/, traffic/, limits/, metrics/)."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Limits for the tiny cells, from their CPU readings (seeds 1-3: the program
# reads at most 1.7e-4 / 6.2e-3 / 1.2e-2; the INT4-weight control at least
# 1.2e-3 / 4.5e-2 / 1.1e-2; half the batch 1.3e-3 / 5.3e-2 / 2.7e-2)
TINY_LIMITS = {"loss_gap": {"limit": 1e-3}, "grad_norm_gap": {"limit": 2.5e-2},
               "update_norm_gap": {"limit": 1e-1}}

TINY = {
    "qwen2-0.5b": (dict(hidden_size=128, intermediate_size=256,
                        num_attention_heads=2, num_key_value_heads=1,
                        num_hidden_layers=2, vocab_size=512),
                   "zero_topo.s4096.b3", dict(seq_len=128, global_batch=2,
                                              quant_block=64), 1),
    "gpt-neox-20b": (dict(hidden_size=128, intermediate_size=256,
                          num_attention_heads=2, num_hidden_layers=2,
                          vocab_size=512),
                     "zero3.2x2.s2048.b8", dict(seq_len=64, global_batch=4,
                                                quant_block=64), 4),
}


def make(tmp: Path, config: str) -> tuple[dict, str]:
    """Write a tiny cell of ``config`` under ``tmp``; return the benchmark
    dict that names it and the cell's name."""
    sizes, traffic, job, chips = TINY[config]
    for d in ("configs", "traffic", "limits"):
        (tmp / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(CHIP / "metrics", tmp / "metrics", dirs_exist_ok=True)
    cfg = json.loads((CHIP / "configs" / f"{config}.json").read_text())
    cfg.update(sizes)
    (tmp / "configs" / "tiny.json").write_text(json.dumps(cfg))
    tr = json.loads((CHIP / "traffic" / f"{traffic}.json").read_text())
    tr.update(job)
    (tmp / "traffic" / "tiny.json").write_text(json.dumps(tr))
    (tmp / "limits" / "tiny-cell.json").write_text(json.dumps(TINY_LIMITS))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(name="tiny", file="configs/tiny.json",
                             source="test", reduced=[], why="test")]
    bench["workloads"] = [dict(name="tiny-cell", config="tiny",
                               traffic="tiny", chips=chips, why="test")]
    for m in bench["per_layer"] + bench["end_to_end"]:
        m.pop("workloads", None)
    return bench, "tiny-cell"


def run(tmp: Path, config: str, seed: int = 7) -> dict:
    """One harness run of the tiny cell on the CPU (no chip check)."""
    import time

    from benchmarks.chip import harness
    bench, name = make(tmp, config)
    return harness.run_cell(name, seed, 0.01, False, t_start=time.perf_counter(),
                            bench=bench, require_tpu=False, base=tmp, root=tmp)
