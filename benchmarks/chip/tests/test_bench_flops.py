"""Model FLOPs a token at both configurations' published widths, against
hand arithmetic; and the kernels' work from their shapes."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import bench_tiny  # noqa: E402,F401
from benchmarks.chip import catalog, flops  # noqa: E402


def _config(name):
    cfg = catalog.load_json(f"configs/{name}.json")
    return cfg, catalog.reference(cfg["reference"]).param_table(cfg)


def test_qwen2_05b():
    cfg, table = _config("qwen2-0.5b")
    # per layer: q, o 896x896; k, v 896x128; gate, up 896x4864; down 4864x896
    layer = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    head = 151936 * 896              # tied: the embedding is the head
    assert flops.matmul_params(table) == 24 * layer + head == 493_961_216
    attn = 3 * 2 * 4096 * 896 * 24   # fwd + bwd, scores and sum, causal half
    assert flops.model_flops_per_token(cfg, table, 4096) \
        == 6 * 493_961_216 + attn == pytest.approx(3.4922e9, rel=1e-4)


def test_gpt_neox_20b_two_layers():
    cfg, table = _config("gpt-neox-20b")
    layer = 4 * 6144 * 6144 + 2 * 6144 * 24576
    head = 50432 * 6144               # untied: the embedding lookup is free
    assert flops.matmul_params(table) == 2 * layer + head == 1_215_823_872
    attn = 3 * 2 * 2048 * 6144 * 2
    assert flops.model_flops_per_token(cfg, table, 2048) \
        == 6 * 1_215_823_872 + attn == pytest.approx(7.4459e9, rel=1e-4)


def test_kernel_work_from_shapes():
    f, b = flops.matmul([("bf16", (2048, 896)), ("s8", (896, 4864)),
                         ("f32", (896, 38))], ("bf16", (2048, 4864)))
    assert f == 2 * 2048 * 896 * 4864
    assert b == 2048 * 896 * 2 + 896 * 4864 + 896 * 38 * 4 + 2048 * 4864 * 2
    q = ("bf16", (28, 4096, 64))
    f, b = flops.causal_attention([q, q, q], q)
    assert f == 4 * 28 * 64 * 4096 * 4097 / 2
    assert b == 4 * 28 * 4096 * 64 * 2
