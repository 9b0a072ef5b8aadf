"""Flash attention under the kernels/ops dispatch: the jnp oracle and the
interpret-mode Pallas kernel must agree BITWISE through forward and backward
(DESIGN.md §5), multi-block kernel configs match to tolerance, and rejected
shapes fall back with a structured warning + counter, never silently.

hypothesis is an optional [test] extra: the property tests degrade to a
skip when it is missing (same guard as tests/test_kernels.py).
"""
import itertools
import math
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.models import layers as L


def _qkv(bh, sq, sk, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (bh, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (bh, sk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (bh, sk, d), jnp.float32).astype(dtype)
    return q, k, v


def _grads(q, k, v, impl, **kw):
    """Fresh jit per impl: the dispatch is baked in at trace time, so a
    shared jit cache would silently reuse the first impl's executable."""
    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, impl=impl, **kw)
        return jnp.sum(o.astype(jnp.float32) ** 2)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _assert_bitwise(ra, rb, what):
    la, ga = ra
    lb, gb = rb
    assert np.asarray(la).tobytes() == np.asarray(lb).tobytes(), what
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(what))


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ops_attention_jnp_vs_interpret_bitwise(causal, window, dtype):
    q, k, v = _qkv(4, 128, 128, 32, dtype)
    kw = dict(causal=causal, window=window)
    _assert_bitwise(_grads(q, k, v, "jnp", **kw),
                    _grads(q, k, v, "pallas_interpret", **kw),
                    f"causal={causal} window={window} dtype={dtype}")


def test_ops_attention_q_offset_decode_bitwise_and_correct():
    """A later q chunk (KV cache longer than q) is bitwise across impls and
    matches the dense softmax with absolute positions."""
    bh, sq, sk, off, d = 2, 128, 256, 128, 32
    q, k, v = _qkv(bh, sq, sk, d, seed=1)
    kw = dict(causal=True, q_offset=off)
    rj = _grads(q, k, v, "jnp", **kw)
    ri = _grads(q, k, v, "pallas_interpret", **kw)
    _assert_bitwise(rj, ri, "q_offset decode chunk")
    out = ops.flash_attention(q, k, v, impl="pallas_interpret", **kw)
    s = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(d)
    mask = (off + jnp.arange(sq))[:, None] >= jnp.arange(sk)[None, :]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    ref = jnp.einsum("bqk,bkd->bqd", p, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 1), (8, 2)])
def test_layers_attention_gqa_bitwise_across_impls(h, hkv):
    """models/layers.flash_attention (GQA head folding included) is bitwise
    under the process-default impl switch."""
    b, s, d = 1, 128, 32
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    outs = {}
    try:
        for impl in ("jnp", "pallas_interpret"):
            ops.set_default_impl(impl)
            outs[impl] = jax.jit(
                lambda q, k, v, _i=impl: L.flash_attention(q, k, v))(q, k, v)
    finally:
        ops.set_default_impl("jnp")
    np.testing.assert_array_equal(np.asarray(outs["jnp"]),
                                  np.asarray(outs["pallas_interpret"]))


@pytest.mark.parametrize("causal,window,q_offset,sq,sk,d,bq,bk", [
    pytest.param(True, 0, 0, 512, 512, 32, 128, 128, id="True-0"),
    pytest.param(True, 100, 0, 512, 512, 32, 128, 128, id="True-100"),
    pytest.param(False, 0, 0, 512, 512, 32, 128, 128, id="False-0"),
    # tiles flash_tiles can choose; the causal k/v clamp skips the copies
    pytest.param(True, 0, 0, 2048, 2048, 64, 512, 512, id="causal-512x512"),
    pytest.param(True, 0, 0, 2048, 2048, 96, 256, 512, id="causal-256x512"),
    pytest.param(True, 0, 0, 2048, 2048, 64, 512, 1024,
                 id="causal-512x1024"),
    pytest.param(True, 0, 0, 2048, 2048, 64, 1024, 512,
                 id="causal-1024x512"),
    # 1536 is no multiple of 1024 and 640 none of 256: the smaller tiles
    pytest.param(True, 0, 0, 1536, 1536, 96, 512, 768, id="causal-s1536"),
    pytest.param(True, 0, 0, 640, 640, 64, 128, 128, id="causal-s640"),
    # a window moves the first kv block as well as the last
    pytest.param(True, 300, 0, 1536, 1536, 64, 256, 256, id="window-256"),
    pytest.param(True, 700, 0, 2048, 2048, 96, 512, 1024, id="window-512"),
    # a later q chunk against a longer cache (static q_offset)
    pytest.param(True, 0, 512, 1024, 1536, 64, 512, 768, id="q_offset"),
    pytest.param(True, 300, 512, 1024, 1536, 96, 256, 512,
                 id="q_offset-window"),
    pytest.param(False, 0, 0, 1024, 1536, 96, 512, 768, id="non_causal"),
])
def test_multiblock_kernel_matches_oracle(causal, window, q_offset, sq, sk, d,
                                          bq, bk):
    """Multi-block tilings (online-softmax rescales active, k/v index maps
    clamped to the blocks each q block needs) match the oracle to fp32
    tolerance — no bitwise contract here."""
    q, k, v = _qkv(2, sq, sk, d, seed=3)
    kw = dict(causal=causal, window=window, q_offset=q_offset)
    out = flash_attention_pallas(q, k, v, **kw, bb=1, bq=bq, bk=bk,
                                 interpret=True)
    ref = ops.flash_attention(q, k, v, **kw, impl="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_fallback_warns_once_and_counts():
    """Non-fusable shapes take the chunked jnp path with one structured
    warning per (kernel, reason) and a dispatch counter entry."""
    b, h, d = 1, 2, 32
    sq, sk = 128, 192               # 192 % min(128, 192) != 0
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, sk, h, d))
    v = jax.random.normal(ks[2], (b, sk, h, d))
    ops.reset_dispatch_counters()
    with pytest.warns(UserWarning, match="fell back to the chunked jnp"):
        L.flash_attention(q, k, v, causal=False)
    counts = ops.dispatch_counters()
    assert counts.get("attention/fallback/seq_unaligned") == 1, counts
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second call must NOT warn again
        L.flash_attention(q, k, v, causal=False)
    assert ops.dispatch_counters()["attention/fallback/seq_unaligned"] == 2
    # a different reason warns separately
    with pytest.warns(UserWarning, match="custom_scale"):
        sp = jax.random.normal(ks[0], (1, 128, 2, 32))
        L.flash_attention(sp, sp, sp, softmax_scale=0.5)
    assert ops.dispatch_counters()["attention/fallback/custom_scale"] == 1


def _grid_steps(bh, sq, sk, bq, bk):
    return bh * (sq // bq) * (sk // bk)


@pytest.mark.parametrize("bh,s,d", [(42, 4096, 64), (128, 2048, 96)],
                         ids=["qwen2-0.5b", "gpt-neox-20b"])
def test_flash_tiles_cut_the_grid(bh, s, d):
    """At the benchmark cells' shapes (3 x 14 heads of 64 at 4096; 2 x 64
    heads of 96 at 2048) the chosen tiles take at most 1/16 of the 128 x 128
    grid's steps."""
    for itemsize in (2, 4):
        bq, bk = ops.flash_tiles(s, s, d, itemsize)
        assert 16 * _grid_steps(bh, s, s, bq, bk) <= \
            _grid_steps(bh, s, s, 128, 128), (itemsize, bq, bk)


def test_flash_tiles_cover_every_fusable_shape():
    """Every shape attention_fusable accepts gets tiles that divide its
    lengths, are 128-aligned or whole, and fit the VMEM budget; the gate's
    answers are those of its rule (lengths >= 8, 128-multiples above 128)."""
    lengths = (4, 8, 64, 100, 128, 192, 384, 640, 1536, 2048, 4096, 8192)
    for sq in lengths:
        for sk in lengths:
            for d, itemsize in itertools.product((32, 64, 96, 128, 256),
                                                 (2, 4)):
                ok, reason = ops.attention_fusable(sq, sk, d, d)
                want = all(n >= 8 and (n < 128 or n % 128 == 0)
                           for n in (sq, sk))
                assert ok == want and (reason is None) == want, (sq, sk)
                if not ok:
                    continue
                bq, bk = ops.flash_tiles(sq, sk, d, itemsize)
                at = (sq, sk, d, itemsize, bq, bk)
                for n, t in ((sq, bq), (sk, bk)):
                    assert n % t == 0 and (t == n or t % 128 == 0), at
                assert ops.flash_vmem_bytes(bq, bk, d, itemsize) <= \
                    ops.FLASH_VMEM_BUDGET, at


def test_dispatch_counts_the_compiled_tile():
    """A compiled call counts under its tile at trace time (so a call left
    at the old 128 x 128 grid shows in dispatch_counters)."""
    bq, bk = ops.flash_tiles(1536, 2048, 96, 2)
    q = jax.ShapeDtypeStruct((4, 1536, 96), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((4, 2048, 96), jnp.bfloat16)
    ops.reset_dispatch_counters()
    jax.eval_shape(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, q_offset=512, impl="pallas"), q, kv, kv)
    assert ops.dispatch_counters() == {
        "attention/pallas": 1, f"attention/pallas/tile/{bq}x{bk}": 1}


def test_attention_fusable_reasons():
    ok, reason = ops.attention_fusable(128, 128, 32, 32)
    assert ok and reason is None
    assert ops.attention_fusable(128, 128, 32, 16)[1] == "mla_dv_mismatch"
    assert ops.attention_fusable(128, 128, 32, 32,
                                 softmax_scale=0.1)[1] == "custom_scale"
    assert ops.attention_fusable(
        128, 128, 32, 32, q_offset=jnp.int32(3))[1] == "traced_q_offset"
    assert ops.attention_fusable(128, 192, 32, 32)[1] == "seq_unaligned"
    assert ops.attention_fusable(4, 128, 32, 32)[1] == "seq_unaligned"


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(1, 4), st.integers(0, 3), st.sampled_from([0, 32, 64]))
    def test_prop_window_offset_decode_bitwise(chunk_i, seed, window):
        """Sliding-window + q_offset decode attention: any 128-aligned q
        chunk against a longer cache is bitwise across impls, fwd + bwd."""
        sq, d = 128, 32
        off = chunk_i * sq
        sk = off + sq
        q, k, v = _qkv(2, sq, sk, d, seed=seed)
        kw = dict(causal=True, window=window, q_offset=off)
        _assert_bitwise(_grads(q, k, v, "jnp", **kw),
                        _grads(q, k, v, "pallas_interpret", **kw),
                        f"off={off} sk={sk} window={window} seed={seed}")
else:
    def test_prop_hypothesis_missing():
        pytest.skip("hypothesis not installed (optional [test] extra); "
                    "property tests skipped")
