"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
shape/dtype sweeps + hypothesis property tests (deliverable (c)).

hypothesis is an optional [test] extra: only the property tests at the
bottom require it (they skip when it is missing); the deterministic kernel
tests always run."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref
from repro.kernels.quant_blockwise import (dequantize_int8_pallas,
                                           quantize_int8_pallas)
from repro.kernels.quant_int4 import (dequantize_int4_pallas,
                                      quantize_int4_pallas)
from repro.kernels.dequant_matmul import dequant_matmul_pallas

SHAPES = [(8, 128), (8, 256), (16, 128), (32, 512), (64, 1024)]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16]


def _rand(shape, dtype, seed=0):
    x = jax.random.normal(jax.random.key(seed), shape, jnp.float32) * 3.0
    return x.astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_int8_pallas_matches_ref(shape, dtype):
    x = _rand(shape, dtype)
    q_p, s_p = quantize_int8_pallas(x, interpret=True)
    q_r, s_r = ref.quantize_int8_ref(x)
    # interpret-mode fma ordering can flip round-to-nearest ties by 1 LSB
    # for half dtypes; f32 must match exactly
    diff = np.abs(np.asarray(q_p, np.int32) - np.asarray(q_r, np.int32))
    assert diff.max() <= (0 if dtype == jnp.float32 else 1)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r), rtol=1e-6)
    d_p = dequantize_int8_pallas(q_p, s_p, jnp.float32, interpret=True)
    d_r = ref.dequantize_int8_ref(q_r, s_r, jnp.float32)
    tol = 0.0 if dtype == jnp.float32 else float(np.asarray(s_r).max())
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_r), rtol=1e-6,
                               atol=tol + 1e-7)


@pytest.mark.parametrize("shape", [(8, 256), (16, 512), (32, 1024)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_int4_pallas_matches_ref(shape, dtype):
    x = _rand(shape, dtype, seed=1)
    q_p, s_p = quantize_int4_pallas(x, interpret=True)
    q_r, s_r = ref.quantize_int4_ref(x)
    lo_p, hi_p = np.asarray(q_p, np.int32) & 0xF, np.asarray(q_p, np.int32) >> 4
    lo_r, hi_r = np.asarray(q_r, np.int32) & 0xF, np.asarray(q_r, np.int32) >> 4
    tol = 0 if dtype == jnp.float32 else 1
    assert np.abs(lo_p - lo_r).max() <= tol
    assert np.abs(hi_p - hi_r).max() <= tol
    d_p = dequantize_int4_pallas(q_p, s_p, jnp.float32, interpret=True)
    d_r = ref.dequantize_int4_ref(q_r, s_r, jnp.float32)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_r),
                               atol=float(np.asarray(s_r).max()) * (tol + 1e-6))


@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 128, 256),
                                 (128, 256, 384)])
def test_dequant_matmul_pallas(mkn):
    m, k, n = mkn
    x = _rand((m, k), jnp.float32, 2)
    w = _rand((k, n), jnp.float32, 3)
    # block-quantize w along K in bk=128 blocks, per column
    wb = np.asarray(w).reshape(k // 128, 128, n)
    absmax = np.abs(wb).max(axis=1)
    scales = np.where(absmax == 0, 1.0, absmax / 127.0).astype(np.float32)
    q = np.clip(np.round(wb / scales[:, None, :]), -127, 127).astype(np.int8)
    q = q.reshape(k, n)
    out = dequant_matmul_pallas(x, jnp.asarray(q), jnp.asarray(scales),
                                interpret=True)
    expect = ref.dequant_matmul_ref(x, jnp.asarray(q), jnp.asarray(scales))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=5e-4)


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("knb", [(128, 384, 64), (256, 256, 128),
                                 (128, 512, 512)])
def test_dequant_matmul_flat_matches_ref_and_unfused(knb, transpose):
    """Flat-shard scale layout: the fused kernel (interpret) == the blocked
    ref == the unfused dequant->matmul, both orientations."""
    k, n, block = knb
    m = 66   # deliberately not a sublane multiple: exercises the M padding
    w = _rand((k * n,), jnp.float32, 4)
    q, s = ops.quantize_int8(w, block)
    x = _rand((m, n if transpose else k), jnp.float32, 5)
    y_j = ops.dequant_matmul(x, q, s, (k, n), block, transpose=transpose,
                             dtype=jnp.float32, impl="jnp")
    y_p = ops.dequant_matmul(x, q, s, (k, n), block, transpose=transpose,
                             dtype=jnp.float32, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(y_j), np.asarray(y_p))
    wd = ops.dequantize_int8(q, s, block, jnp.float32).reshape(k, n)
    y_u = x @ (wd.T if transpose else wd)
    # approximate: the unfused dot's reduction order depends on XLA's CPU
    # partitioning (it shifts under --xla_force_host_platform_device_count)
    np.testing.assert_allclose(np.asarray(y_j), np.asarray(y_u),
                               rtol=1e-4, atol=5e-4)


def test_dequant_matmul_flat_bf16_out():
    k, n, block = 128, 256, 64
    q, s = ops.quantize_int8(_rand((k * n,), jnp.float32, 6), block)
    x = _rand((8, k), jnp.float32, 7)
    y = ops.dequant_matmul(x, q, s, (k, n), block, dtype=jnp.bfloat16,
                           impl="pallas_interpret")
    assert y.dtype == jnp.bfloat16 and y.shape == (8, n)
    y32 = ops.dequant_matmul(x, q, s, (k, n), block, dtype=jnp.float32,
                             impl="jnp")
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(y32.astype(jnp.bfloat16)))


def test_matmul_fusable_gate():
    assert ops.matmul_fusable((128, 384), 64)
    assert not ops.matmul_fusable((128, 100), 64)   # N not block-aligned
    assert not ops.matmul_fusable((512,), 64)       # 1-D leaf


@pytest.mark.parametrize("d", [2, 8])
def test_int4_sum_kernel_matches_ref(d):
    """Fused unpack+dequant+reduce == per-chunk dequant + sum; jnp and
    interpret impls bitwise identical under jit (the engine always runs
    jitted, where XLA applies the same fma contraction to both)."""
    block = 256
    x = _rand((d * 8 * block,), jnp.float32, 8)
    q, s = ops.quantize_int4(x, block)
    r_j = jax.jit(lambda q, s: ops.dequantize_int4_sum(
        q, s, d, block, impl="jnp"))(q, s)
    r_p = jax.jit(lambda q, s: ops.dequantize_int4_sum(
        q, s, d, block, impl="pallas_interpret"))(q, s)
    np.testing.assert_array_equal(np.asarray(r_j), np.asarray(r_p))
    unfused = ops.dequantize_int4(q, s, block).reshape(d, -1).sum(axis=0)
    np.testing.assert_allclose(np.asarray(r_j), np.asarray(unfused),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("nb", [1, 3, 12, 20])
def test_kernels_cover_unaligned_block_counts(nb):
    """Block counts that are not a multiple of the 8-row tile must still be
    fully written (the row tile degrades via gcd instead of the grid
    truncating and leaving trailing rows as uninitialized garbage)."""
    block, d = 128, 2
    x = _rand((nb * block,), jnp.float32, 10)
    for quant, dequant in ((ops.quantize_int8, ops.dequantize_int8),
                           (ops.quantize_int4, ops.dequantize_int4)):
        q, s = quant(x, block, impl="pallas_interpret")
        qr, sr = quant(x, block, impl="jnp")
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        out = dequant(q, s, block, jnp.float32, impl="pallas_interpret")
        outr = dequant(q, s, block, jnp.float32, impl="jnp")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))
    xs = _rand((d * nb * block,), jnp.float32, 11)
    q, s = ops.quantize_int4(xs, block)
    r_p = jax.jit(lambda q, s: ops.dequantize_int4_sum(
        q, s, d, block, impl="pallas_interpret"))(q, s)
    r_j = jax.jit(lambda q, s: ops.dequantize_int4_sum(
        q, s, d, block, impl="jnp"))(q, s)
    assert np.isfinite(np.asarray(r_p)).all()
    np.testing.assert_array_equal(np.asarray(r_p), np.asarray(r_j))
    q8, s8 = ops.quantize_int8(xs, block)
    r8_p = jax.jit(lambda q, s: ops.dequantize_int8_sum(
        q, s, d, block, impl="pallas_interpret"))(q8, s8)
    r8_j = jax.jit(lambda q, s: ops.dequantize_int8_sum(
        q, s, d, block, impl="jnp"))(q8, s8)
    assert np.isfinite(np.asarray(r8_p)).all()
    np.testing.assert_array_equal(np.asarray(r8_p), np.asarray(r8_j))


@pytest.mark.parametrize("d", [2, 8])
def test_int8_sum_kernel_matches_ref(d):
    block = 128
    x = _rand((d * 16 * block,), jnp.float32, 9)
    q, s = ops.quantize_int8(x, block)
    r_j = jax.jit(lambda q, s: ops.dequantize_int8_sum(
        q, s, d, block, impl="jnp"))(q, s)
    r_p = jax.jit(lambda q, s: ops.dequantize_int8_sum(
        q, s, d, block, impl="pallas_interpret"))(q, s)
    np.testing.assert_array_equal(np.asarray(r_j), np.asarray(r_p))
    unfused = ops.dequantize_int8(q, s, block).reshape(d, -1).sum(axis=0)
    np.testing.assert_allclose(np.asarray(r_j), np.asarray(unfused),
                               rtol=1e-6, atol=1e-5)


# ---------------------------------------------------------------------------
# ops-level (flat API, padding plumbing)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("n,block", [(1024, 128), (4096, 512), (512, 512)])
def test_ops_int8_roundtrip_error_bound(impl, n, block):
    x = jax.random.normal(jax.random.key(5), (n,)) * 2.0
    q, s = ops.quantize_int8(x, block, impl=impl)
    d = ops.dequantize_int8(q, s, block, jnp.float32, impl=impl)
    blocks = np.asarray(x).reshape(-1, block)
    bound = np.abs(blocks).max(axis=1, keepdims=True) / 127.0 * 0.5 + 1e-7
    err = np.abs(np.asarray(d).reshape(-1, block) - blocks)
    assert (err <= bound + 1e-6).all()


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_ops_int4_roundtrip_error_bound(impl):
    n, block = 2048, 256
    x = jax.random.normal(jax.random.key(6), (n,))
    q, s = ops.quantize_int4(x, block, impl=impl)
    assert q.shape == (n // 2,) and q.dtype == jnp.uint8
    d = ops.dequantize_int4(q, s, block, jnp.float32, impl=impl)
    blocks = np.asarray(x).reshape(-1, block)
    bound = np.abs(blocks).max(axis=1, keepdims=True) / 7.0 * 0.5 + 1e-7
    err = np.abs(np.asarray(d).reshape(-1, block) - blocks)
    assert (err <= bound + 1e-6).all()


# ---------------------------------------------------------------------------
# matmul_quant: the fused dW -> wire-format epilogue (DESIGN.md §5)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("m,k,n,block", [(33, 16, 512, 64), (64, 128, 384, 64),
                                         (10, 8, 256, 128)])
def test_matmul_quant_jnp_vs_interpret_bitwise(bits, m, k, n, block):
    """Wire bytes AND scales are bitwise identical across the pair — the
    downstream a2a ships these verbatim, so close-enough is not enough."""
    x = _rand((m, k), jnp.float32, 8)
    g = _rand((m, n), jnp.float32, 9)
    q_j, s_j = ops.matmul_quant(x, g, block, bits=bits, impl="jnp")
    q_p, s_p = ops.matmul_quant(x, g, block, bits=bits,
                                impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(q_j), np.asarray(q_p))
    np.testing.assert_array_equal(np.asarray(s_j), np.asarray(s_p))


@pytest.mark.parametrize("bits", [8, 4])
def test_matmul_quant_matches_unfused_pair(bits):
    """Dequantizing the fused output recovers x.T @ g to quantization error,
    and the wire layout equals quantize(C.reshape(-1)) up to rounding."""
    m, k, n, block = 32, 16, 512, 64
    x = _rand((m, k), jnp.float32, 10)
    g = _rand((m, n), jnp.float32, 11)
    q, s = ops.matmul_quant(x, g, block, bits=bits, impl="jnp")
    dense = np.asarray(x).T @ np.asarray(g)
    deq = ops.dequantize_int4 if bits == 4 else ops.dequantize_int8
    got = np.asarray(deq(q, s, block, jnp.float32)).reshape(k, n)
    qmax = 7.0 if bits == 4 else 127.0
    bound = np.abs(dense.reshape(-1, block)).max(axis=1, keepdims=True) \
        / qmax * 0.5 + 1e-6
    err = np.abs((got - dense).reshape(-1, block))
    assert (err <= bound + 1e-5).all()


def test_matmul_quant_pad_to_exact_zero_blocks():
    """pad_to appends exact wire zeros (q=0 / 0x88, scale=1) — the same
    bytes quantize-of-zero-padding ships on the unfused path."""
    m, k, n, block = 16, 8, 256, 64
    x = _rand((m, k), jnp.float32, 12)
    g = _rand((m, n), jnp.float32, 13)
    logical = k * n
    pad_to = logical + 4 * block
    for bits, fill in ((8, 0), (4, 0x88)):
        q, s = ops.matmul_quant(x, g, block, bits=bits, pad_to=pad_to,
                                impl="pallas_interpret")
        q0, s0 = ops.matmul_quant(x, g, block, bits=bits, impl="jnp")
        wire = logical // 2 if bits == 4 else logical
        assert q.shape == (pad_to // 2 if bits == 4 else pad_to,)
        np.testing.assert_array_equal(np.asarray(q)[:wire], np.asarray(q0))
        assert (np.asarray(q)[wire:] == fill).all()
        np.testing.assert_array_equal(np.asarray(s)[:logical // block],
                                      np.asarray(s0))
        assert (np.asarray(s)[logical // block:] == 1.0).all()


def test_dw_fusable_routes_unaligned_to_unfused(monkeypatch):
    """Regression: a leaf whose columns don't tile into quant blocks (e.g.
    falcon-mamba's w_xproj (512, 48) with block 64) must keep the dense
    matmul + quantize pair — matmul_quant would produce a broken wire
    layout for it. Any fused call for such a spec is an error."""
    from repro.core import linear
    from repro.core.partition import LeafSpec
    from repro.launch.mesh import make_test_mesh, scheme_config

    mesh = make_test_mesh(shape=(1, 1, 1), axes=("data", "node", "gcd"))
    cfg = scheme_config("zero_topo", mesh, quant_block=64,
                        compute_dtype="float32")
    aligned = LeafSpec("w_in", (256, 1024))
    unaligned = LeafSpec("w_xproj", (512, 48))
    assert not linear._dw_fusable(unaligned, cfg)
    # the gate result for the aligned spec depends only on the RS config;
    # on a 1-device weight axis there is no quantized a2a to fuse into
    if cfg.quantize_grads and cfg.size(cfg.axes.weight) > 1:
        assert linear._dw_fusable(aligned, cfg)

    def _boom(*a, **kw):
        raise AssertionError("matmul_quant called for a non-fusable leaf")
    monkeypatch.setattr(ops, "matmul_quant", _boom)
    x2 = _rand((12, 512), jnp.float32, 14)
    g2 = _rand((12, 48), jnp.float32, 15)
    out = linear._mm_dw_stage1(x2, g2, False, unaligned, cfg)
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# hypothesis property tests (skip when the optional extra is missing)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 8), st.integers(0, 2 ** 31 - 1),
           st.sampled_from([64, 128, 512]))
    def test_prop_int8_scales_positive_and_bounded(nb, seed, block):
        x = jax.random.normal(jax.random.key(seed), (nb, block)) * 10
        q, s = ref.quantize_int8_ref(x)
        assert (np.asarray(s) > 0).all()
        assert (np.abs(np.asarray(q)) <= 127).all()
        # all-zero blocks dequantize to exact zeros
        z, sz = ref.quantize_int8_ref(jnp.zeros((2, block)))
        assert (np.asarray(ref.dequantize_int8_ref(z, sz)) == 0).all()

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1))
    def test_prop_int4_pack_bijection(seed):
        """pack(unpack(q)) == q for all valid nibble pairs."""
        rng = np.random.default_rng(seed)
        vals = rng.integers(-7, 8, size=(4, 256)).astype(np.float32)
        q, s = ref.quantize_int4_ref(jnp.asarray(vals))  # scale==1 blocks
        d = ref.dequantize_int4_ref(q, s)
        # since |vals| <= 7 and absmax<=7 -> scale = absmax/7 <= 1;
        # round-trip re-quantizing gives identical packed bytes
        q2, s2 = ref.quantize_int4_ref(d)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.sampled_from([128, 256]))
    def test_prop_quant_idempotent(seed, block):
        """Dequantized tensors are fixed points of quantize∘dequantize."""
        x = jax.random.normal(jax.random.key(seed), (4, block))
        q, s = ref.quantize_int8_ref(x)
        d = ref.dequantize_int8_ref(q, s)
        q2, s2 = ref.quantize_int8_ref(d)
        d2 = ref.dequantize_int8_ref(q2, s2)
        np.testing.assert_allclose(np.asarray(d), np.asarray(d2),
                                   rtol=1e-5, atol=1e-6)
else:
    def test_prop_hypothesis_missing():
        pytest.skip("hypothesis not installed (optional [test] extra); "
                    "property tests run on CI")


def _interpret_compiled_tiles(monkeypatch):
    """Run ``impl="pallas"`` — the compiled path's tile choice and grid —
    through the Pallas interpreter, so its index maps are checked for
    numbers on CPU."""
    for name in ("dequant_matmul_flat_pallas", "matmul_quant_pallas"):
        real = getattr(ops, name)
        monkeypatch.setattr(
            ops, name, lambda *a, real=real, **k: real(*a, **{
                **k, "interpret": True}))


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("k,n,block", [(1024, 256, 128), (256, 1024, 2)])
def test_compiled_dequant_matmul_tiles_match_ref(monkeypatch, k, n, block,
                                                 transpose):
    """(1024, 256, 128): several contraction / output / row tiles with the
    scale tile spanning N; (256, 1024, 2): two 128*block-wide N tiles."""
    _interpret_compiled_tiles(monkeypatch)
    m = 520
    q = jax.random.randint(jax.random.key(1), (k * n,), -127, 128,
                           jnp.int32).astype(jnp.int8)
    s = jax.random.uniform(jax.random.key(2), (k * n // block,),
                           jnp.float32, 0.5, 1.5)
    x = _rand((m, n if transpose else k), jnp.float32, 3)
    out = ops.dequant_matmul(x, q, s, (k, n), block, transpose=transpose,
                             dtype=jnp.float32, impl="pallas")
    w = ref.dequant_w_flat_ref(q.reshape(k, n), s.reshape(k, n // block),
                               block)
    want = x @ (w.T if transpose else w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("bits", [8, 4])
def test_compiled_matmul_quant_tiles_match_ref(monkeypatch, bits):
    """M 4096 gives two contraction steps, K 256 two output row tiles; every
    element must land within half a quantization step of x.T @ g."""
    _interpret_compiled_tiles(monkeypatch)
    m, k, n, block = 4096, 256, 256, 128
    x = _rand((m, k), jnp.float32, 4)
    g = _rand((m, n), jnp.float32, 5)
    q, s = ops.matmul_quant(x, g, block, bits=bits, impl="pallas")
    if bits == 4:
        p = np.asarray(q, np.int32)
        codes = np.stack([p & 0xF, p >> 4], axis=-1).reshape(-1) - 8
    else:
        codes = np.asarray(q, np.int32)
    s = np.asarray(s)
    deq = (codes.reshape(-1, block) * s[:, None]).reshape(k, n)
    want = np.asarray(x).T.astype(np.float64) @ np.asarray(g, np.float64)
    step = np.repeat(s, block).reshape(k, n)
    assert (np.abs(deq - want) <= 0.5 * step * (1 + 1e-4) + 1e-4).all()
