"""Pallas kernel sweeps (interpret mode) vs the pure-jnp oracles.

Per the deliverable: every kernel swept over shapes/dtypes and
``assert_allclose``d against ref.py.  Integer sub-paths are bit-exact; float
accumulation paths match to f32 matmul-order noise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import split_softmax as ss
from repro.core.lut import LUTConfig
from repro.kernels import ops

CFG = LUTConfig(scale_z=2.6 / 127)
EXP_LUT, RECIP_LUT = ss.make_luts(CFG)
SCALES = (jnp.float32(0.01), jnp.float32(0.012), jnp.float32(0.02))


def _qkv(rng, b, hq, hkv, sq, sk, d):
    q = rng.integers(-128, 128, (b, hq, sq, d)).astype(np.int8)
    k = rng.integers(-128, 128, (b, hkv, sk, d)).astype(np.int8)
    v = rng.integers(-128, 128, (b, hkv, sk, d)).astype(np.int8)
    return q, k, v


SHAPE_GRID = [
    # b, hq, hkv, sq, sk, d, bq, bk
    (1, 1, 1, 128, 128, 64, 128, 128),
    (2, 4, 2, 256, 256, 64, 128, 128),
    (1, 8, 8, 128, 256, 128, 64, 64),     # MHA, rectangular
    (2, 8, 2, 192, 320, 64, 64, 64),      # non-pow2 seqs (multiple of block)
    (1, 4, 1, 256, 128, 32, 128, 64),     # MQA, narrow head
]


@pytest.mark.parametrize("shape", SHAPE_GRID)
@pytest.mark.parametrize("mode", ["causal", "bidir", "window"])
def test_splitmax_attention_sweep(rng, shape, mode):
    b, hq, hkv, sq, sk, d, bq, bk = shape
    q, k, v = _qkv(rng, b, hq, hkv, sq, sk, d)
    kw = dict(causal=mode != "bidir",
              window=64 if mode == "window" else None)
    args = (q, k, v, *SCALES, EXP_LUT, RECIP_LUT)
    ref = ops.splitmax_attention(*args, cfg=CFG, impl="ref", block_k=bk,
                                 **kw)
    ker = ops.splitmax_attention(*args, cfg=CFG, impl="interpret",
                                 block_q=bq, block_k=bk, **kw)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPE_GRID[:3])
def test_splitmax_xla_blocked_matches_ref(rng, shape):
    b, hq, hkv, sq, sk, d, bq, bk = shape
    q, k, v = _qkv(rng, b, hq, hkv, sq, sk, d)
    args = (q, k, v, *SCALES, EXP_LUT, RECIP_LUT)
    ref = ops.splitmax_attention(*args, cfg=CFG, impl="ref", block_k=bk)
    xla = ops.splitmax_attention(*args, cfg=CFG, impl="xla", block_k=bk)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_splitmax_kv_valid_len(rng):
    b, hq, hkv, s, d = 1, 2, 2, 256, 64
    q, k, v = _qkv(rng, b, hq, hkv, s, s, d)
    args = (q, k, v, *SCALES, EXP_LUT, RECIP_LUT)
    for impl in ("ref", "interpret", "xla"):
        out_full = ops.splitmax_attention(
            *args, cfg=CFG, impl=impl, causal=False,
            kv_valid_len=jnp.int32(100))
        # reference: physically truncate K/V to 100 (padded to block)
        out_trunc = ops.splitmax_attention(
            q, k[:, :, :128, :], v[:, :, :128, :], *SCALES, EXP_LUT,
            RECIP_LUT, cfg=CFG, impl="ref", causal=False,
            kv_valid_len=jnp.int32(100))
        np.testing.assert_allclose(np.asarray(out_full),
                                   np.asarray(out_trunc),
                                   rtol=2e-5, atol=2e-5)


DECODE_GRID = [
    # b, hq, hkv, s_max, d, bk
    (2, 4, 2, 256, 64, 128),
    (1, 8, 1, 128, 128, 64),
    (3, 6, 6, 384, 64, 128),
]


@pytest.mark.parametrize("shape", DECODE_GRID)
@pytest.mark.parametrize("window", [None, 64])
def test_splitmax_decode_sweep(rng, shape, window):
    b, hq, hkv, s, d, bk = shape
    q1 = rng.integers(-128, 128, (b, hq, d)).astype(np.int8)
    _, k, v = _qkv(rng, b, hq, hkv, s, s, d)
    lens = jnp.asarray(rng.integers(1, s + 1, (b,)), jnp.int32)
    args = (q1, k, v, *SCALES, lens, EXP_LUT, RECIP_LUT)
    ref = ops.splitmax_decode(*args, cfg=CFG, impl="ref", window=window)
    ker = ops.splitmax_decode(*args, cfg=CFG, impl="interpret",
                              block_k=bk, window=window)
    xla = ops.splitmax_decode(*args, cfg=CFG, impl="xla", window=window)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (256, 512, 256, 256, 256, 256),
    (128, 128, 128, 64, 64, 64),
    (512, 256, 384, 128, 128, 128),
])
def test_int8_matmul_bitexact(rng, m, k, n, bm, bn, bk):
    x = rng.integers(-128, 128, (m, k)).astype(np.int8)
    w = rng.integers(-128, 128, (k, n)).astype(np.int8)
    ref = ops.int8_matmul(x, w, impl="ref")
    ker = ops.int8_matmul(x, w, impl="interpret",
                          block_m=bm, block_n=bn, block_k=bk)
    assert np.array_equal(np.asarray(ref), np.asarray(ker))


def test_int8_matmul_fused_requant(rng):
    x = rng.integers(-128, 128, (256, 256)).astype(np.int8)
    w = rng.integers(-128, 128, (256, 256)).astype(np.int8)
    mult = jnp.float32(3.7e-4)
    ref = ops.int8_matmul(x, w, mult, impl="ref")
    ker = ops.int8_matmul(x, w, mult, impl="interpret")
    assert ref.dtype == jnp.int8
    assert np.array_equal(np.asarray(ref), np.asarray(ker))


def test_lut_compute_mode_within_one_lsb(rng):
    """'compute' mode (arithmetic exp) vs 'onehot' (exact table read)."""
    q, k, v = _qkv(rng, 1, 2, 2, 128, 128, 64)
    args = (q, k, v, *SCALES, EXP_LUT, RECIP_LUT)
    oh = ops.splitmax_attention(*args, cfg=CFG, impl="interpret",
                                lut_mode="onehot")
    cm = ops.splitmax_attention(*args, cfg=CFG, impl="interpret",
                                lut_mode="compute")
    # <= 1 LSB of 2^-15 per element propagates to ~1e-3 relative on output
    scale = float(jnp.max(jnp.abs(oh))) + 1e-9
    assert float(jnp.max(jnp.abs(oh - cm))) / scale < 5e-3


def test_denominator_bitexact_small_n(rng):
    """For a single k-tile the int32 denominator is exact — kernel == oracle
    bitwise on the integer path (exact_recip isolates it)."""
    q, k, v = _qkv(rng, 1, 1, 1, 128, 128, 64)
    args = (q, k, v, *SCALES, EXP_LUT, RECIP_LUT)
    ref = ops.splitmax_attention(*args, cfg=CFG, impl="ref",
                                 causal=False, block_k=128)
    ker = ops.splitmax_attention(*args, cfg=CFG, impl="interpret",
                                 causal=False, block_q=128, block_k=128)
    # recip-LUT indices must agree exactly -> identical normalization
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# ragged prefill, the exact LUT read and the exact e.V split
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk,causal", [
    (200, 200, True), (33, 33, True), (200, 33, False), (33, 200, False)])
def test_splitmax_attention_ragged_bitwise(rng, sq, sk, causal):
    """Any Sq/Sk: the kernel pads to its tiles and masks the padded keys.
    With |v| <= 2 every f32 partial sum is an integer below 2^24, so the
    kernel and the oracle agree bit for bit whatever their order."""
    q, k, _ = _qkv(rng, 1, 8, 2, sq, sk, 64)
    v = rng.integers(-2, 3, k.shape).astype(np.int8)
    args = (q, k, v, *SCALES, EXP_LUT, RECIP_LUT)
    ref = ops.splitmax_attention(*args, cfg=CFG, impl="ref", causal=causal)
    ker = ops.splitmax_attention(*args, cfg=CFG, impl="interpret",
                                 causal=causal)
    assert ker.shape == (1, 8, sq, 64)
    assert np.array_equal(np.asarray(ker), np.asarray(ref))


def test_splitmax_xla_ragged_long_kv(rng):
    """The XLA twin pads a key length that its 512-wide chunks do not
    divide, instead of asserting."""
    q, k, v = _qkv(rng, 1, 4, 2, 64, 600, 64)
    args = (q, k, v, *SCALES, EXP_LUT, RECIP_LUT)
    ref = ops.splitmax_attention(*args, cfg=CFG, impl="ref", causal=False)
    xla = ops.splitmax_attention(*args, cfg=CFG, impl="xla", causal=False)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cols", [1, 32, 128, 200, 256])
def test_table_lookup_matches_take(rng, cols):
    """The in-kernel lane gather reads exactly ``jnp.take`` of the table."""
    from jax.experimental import pallas as pl
    from repro.kernels.splitmax_attn import _replicate_table, _table_lookup

    idx = jnp.asarray(rng.integers(0, 256, (16, cols)), jnp.int32)
    out = pl.pallas_call(
        lambda i_ref, t_ref, o_ref: o_ref.__setitem__(
            Ellipsis, _table_lookup(i_ref[...], t_ref)),
        out_shape=jax.ShapeDtypeStruct(idx.shape, jnp.float32),
        interpret=True)(idx, _replicate_table(jnp.asarray(EXP_LUT)))
    want = jnp.take(jnp.asarray(EXP_LUT), idx).astype(jnp.float32)
    assert np.array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("cols", [32, 128, 512])
def test_exact_pv_is_correctly_rounded(rng, cols):
    """e.V through the bf16 hi/lo split equals the exact integer product
    rounded once to f32, for LUT values up to 2^15 and int8 V."""
    from repro.kernels.splitmax_attn import _exact_pv

    e = rng.integers(0, 2 ** 15 + 1, (8, cols))
    v = rng.integers(-128, 128, (cols, 64))
    got = _exact_pv(jnp.asarray(e, jnp.float32), jnp.asarray(v, jnp.int8))
    want = (e.astype(np.int64) @ v.astype(np.int64)).astype(np.float32)
    assert np.array_equal(np.asarray(got), want)
