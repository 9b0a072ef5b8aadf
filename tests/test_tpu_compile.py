"""Compile the main-path Pallas kernels for a TPU v5e at real widths.

Nothing runs: the v5e compiler, which ships with jax's TPU support, lowers
each kernel for a *described* chip (``jax.experimental.topologies``), so a
block shape Mosaic refuses, a layout it cannot infer or a kernel that does
not fit VMEM fails here instead of on the chip. Widths are qwen2-0.5b's
(d 896, d_ff 4864, vocab 151,936, 14 heads of 64, seq 4096) with the
launcher's quant block 128, GPT-NeoX-20B's and gemma3-1b's heads for flash
attention, and falcon-mamba-7b's d_inner for the scan.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and every xdist worker imports this file.
The same described 2x2 host checks the mesh the launcher builds on it.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.launch.mesh import make_device_mesh, zero_tiers
from repro.models.registry import get_arch

QWEN = get_arch("qwen2-0.5b")
NEOX = get_arch("gpt-neox-20b")
GEMMA = get_arch("gemma3-1b")
MAMBA = get_arch("falcon-mamba-7b")
BLOCK = 128
ROWS = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_device_mesh_pairs_ici_neighbours(topo):
    """A 2x2 host becomes (data, node, gcd) = (1, 2, 2); each gcd pair is two
    chips one hop apart, and zero_tiers puts that axis on l0."""
    mesh = make_device_mesh(topo.devices)
    assert dict(mesh.shape) == {"data": 1, "node": 2, "gcd": 2}
    for pair in mesh.devices.reshape(-1, 2):
        a, b = (np.asarray(d.coords) for d in pair)
        assert np.abs(a - b).sum() == 1
    assert zero_tiers(mesh)["l0"] == ("gcd",)
    assert dict(make_device_mesh(topo.devices[:1]).shape) == \
        {"data": 1, "node": 1, "gcd": 1}


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


D, FF, V = QWEN.d_model, QWEN.d_ff, QWEN.vocab


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("k,n", [(D, FF), (FF, D), (V, D)],
                         ids=["ffn_in", "ffn_out", "lm_head"])
def test_dequant_matmul_compiles(one_chip, k, n, transpose):
    _compile(lambda x, q, s: ops.dequant_matmul(
        x, q, s, (k, n), BLOCK, transpose=transpose, impl="pallas"),
        one_chip, ((ROWS, n if transpose else k), jnp.bfloat16),
        ((k * n,), jnp.int8), ((k * n // BLOCK,), jnp.float32))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("k,n", [(D, FF), (FF, D)], ids=["ffn_in", "ffn_out"])
def test_matmul_quant_compiles(one_chip, k, n, bits):
    _compile(lambda x, g: ops.matmul_quant(x, g, BLOCK, bits=bits,
                                           impl="pallas"),
             one_chip, ((ROWS, k), jnp.float32), ((ROWS, n), jnp.float32))


def test_quantize_int4_compiles(one_chip):
    _compile(lambda x: ops.quantize_int4(x, BLOCK, impl="pallas"),
             one_chip, ((D * FF,), jnp.float32))


def test_flash_attention_fwd_compiles(one_chip):
    bh = 2 * QWEN.n_heads
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, impl="pallas"),
             one_chip, *[((bh, 4096, QWEN.hdim), jnp.bfloat16)] * 3)


def test_flash_attention_fwd_compiles_neox_widths(one_chip):
    """GPT-NeoX-20B's 64 heads of 96 at 2048 tokens, 2 rows: the other
    benchmark cell's tiles lower and fit VMEM."""
    bh = 2 * NEOX.n_heads
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, impl="pallas"),
             one_chip, *[((bh, 2048, NEOX.hdim), jnp.bfloat16)] * 3)


@pytest.mark.parametrize("sq,sk,d,kw", [
    (4096, 4096, GEMMA.hdim, dict(window=GEMMA.sliding_window)),
    (1536, 2048, QWEN.hdim, dict(q_offset=512)),
    (640, 1536, NEOX.hdim, dict(causal=False)),
], ids=["gemma3_window", "prefill_chunk", "cross_attention"])
def test_flash_attention_fwd_compiles_other_masks(one_chip, sq, sk, d, kw):
    """The tiles of a sliding window at gemma3-1b's head size 256 (the
    largest VMEM blocks), a later prefill chunk and non-causal attention of
    lengths 1024 does not divide lower and fit VMEM."""
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, impl="pallas",
                                                 **kw),
             one_chip, ((8, sq, d), jnp.bfloat16),
             *[((8, sk, d), jnp.bfloat16)] * 2)


def test_selective_scan_compiles(one_chip):
    s, d, n = 4096, MAMBA.d_inner, MAMBA.ssm.d_state
    f32 = jnp.float32
    _compile(lambda *a: ops.selective_scan(*a, impl="pallas"), one_chip,
             ((1, s, d), f32), ((1, s, d), f32), ((1, s, n), f32),
             ((1, s, n), f32), ((d, n), f32), ((1, d, n), f32))
