"""Model FLOPs a token at both configurations' published widths, against
hand arithmetic, and from a family's own count where its reference module
has one; the helpers such a count is built from; and the kernels' work
from their shapes."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import bench_tiny  # noqa: E402,F401
from benchmarks.chip import catalog, flops, harness  # noqa: E402


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
    ref = catalog.reference(cfg["reference"])
    assert harness.flops_per_token(ref, cfg, table, 4096) == 6 * 493_961_216 + attn


def test_gpt_neox_20b_two_layers():
    cfg, table = _config("gpt-neox-20b")
    layer = 4 * 6144 * 6144 + 2 * 6144 * 24576
    head = 50432 * 6144               # untied: the embedding lookup is free
    assert flops.matmul_params(table) == 2 * layer + head == 1_215_823_872
    attn = 3 * 2 * 2048 * 6144 * 2
    assert flops.model_flops_per_token(cfg, table, 2048) \
        == 6 * 1_215_823_872 + attn == pytest.approx(7.4459e9, rel=1e-4)
    ref = catalog.reference(cfg["reference"])
    assert harness.flops_per_token(ref, cfg, table, 2048) == 6 * 1_215_823_872 + attn


FAMILY = """
from benchmarks.chip import flops
from benchmarks.chip.reference.qwen2 import param_table  # noqa: F401


def flops_per_token(cfg, table, seq_len):
    return 6 * flops.active_matmul_params(table, {"attn.w_up": 0.5}) + 1.0
"""


def test_family_count_is_the_one_the_harness_uses(tmp_path):
    cfg, table = _config("qwen2-0.5b")
    path = tmp_path / "reference" / "throwaway.py"
    path.parent.mkdir()
    path.write_text(FAMILY)
    ref = catalog._module(path, "bench_reference_throwaway")
    up = 24 * 896 * 4864
    assert harness.flops_per_token(ref, cfg, table, 4096) \
        == 6 * (493_961_216 - up / 2) + 1.0


def test_attention_with_a_window_by_hand():
    # 4096 queries, window 512: query i sees min(i + 1, 512) keys; over the
    # causal square as an area, 4096 x 512 - 512^2 / 2 pairs, 480 a query
    layers = [(16, 192, 128, 512), (16, 192, 128, 0)]
    assert flops.attention_flops_per_token(layers, 4096) \
        == 3 * 2 * 16 * (192 + 128) * (480 + 2048)
    # a window as long as the sequence is full attention
    assert flops.attention_flops_per_token([(4, 64, 64, 4096)], 4096) \
        == flops.attention_flops_per_token([(4, 64, 64, 0)], 4096) \
        == 3 * 2 * 4096 * 256


def test_routed_experts_at_their_active_share():
    # 8 of 64 experts held, each of width 1408 at d 2048, 6 routed a token:
    # 8 x 6 / 64 = 0.75 of an expert a token, beside a dense 2048 x 2048
    table = {"moe.w_up": dict(shape=(8, 2048, 1408), stack=5),
             "attn.wo": dict(shape=(2048, 2048), stack=5),
             "attn.ln1": dict(shape=(2048,), stack=5)}
    expert = 2048 * 1408
    assert flops.active_matmul_params(table, {"moe.w_up": 6 / 64}) \
        == 5 * (0.75 * expert + 2048 * 2048)
    assert flops.matmul_params(table) == 5 * (8 * expert + 2048 * 2048)


def test_kernel_work_from_shapes():
    f, b = flops.matmul([("bf16", (2048, 896)), ("s8", (896, 4864)),
                         ("f32", (896, 38))], ("bf16", (2048, 4864)))
    assert f == 2 * 2048 * 896 * 4864
    assert b == 2048 * 896 * 2 + 896 * 4864 + 896 * 38 * 4 + 2048 * 4864 * 2
    q = ("bf16", (28, 4096, 64))
    f, b = flops.causal_attention([q, q, q], q)
    assert f == 4 * 28 * 64 * 4096 * 4097 / 2
    assert b == 4 * 28 * 4096 * 64 * 2
    # latent attention: scores at 128 + 64, the weighted sum at v's 128
    q, v = ("bf16", (16, 2048, 192)), ("bf16", (16, 2048, 128))
    f, b = flops.causal_attention([q, q, v], v)
    assert f == 2 * 16 * 2048 * 2049 / 2 * (192 + 128)
    assert b == 2 * 16 * 2048 * (192 * 2 + 128 * 2)
