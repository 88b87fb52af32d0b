"""Blocked (scan-based) split-softmax attention in pure XLA.

The production attention path for non-TPU backends and for the multi-pod
dry-run.  Because CIMple's split softmax has *no running max*, the k-axis
reduction is a plain associative accumulation:

    carry = (acc_v, acc_s);   acc_v += E(z_blk) . V_blk;  acc_s += sum E(z_blk)

which maps 1:1 onto ``lax.scan`` over K/V chunks — the same streaming the
silicon performs and the Pallas kernel's grid — with O(Sq * block_k) score
memory instead of O(Sq * Sk).  FlashAttention needs an online max and
rescaling here; the quantization ceiling makes that machinery unnecessary,
which is precisely the paper's observation.

Two score kinds share the skeleton:
  * ``int8``      — z32 -> requant -> exp LUT (deployment numerics)
  * ``fakequant`` — STE-quantized float scores (training numerics); the scan
                    body is ``jax.checkpoint``-ed so the backward pass
                    recomputes block scores instead of storing them (remat).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import lut as lut_lib
from repro.core import quantization as qlib
from repro.core.lut import LUTConfig, Z_QUANT_MAX

# The int8 path's e.V dots carry exp-LUT values up to 2^15, which bf16 cannot
# hold exactly; TPU's default f32 dot rounds operands to bf16, HIGHEST does
# not.  (The QK^T dots are int8 x int8 -> int32 and exact anyway.)
_EXACT = jax.lax.Precision.HIGHEST


def _chunk_mask(sq: int, bk: int, base: jax.Array, *, causal: bool,
                window: Optional[int], kv_valid_len: Optional[jax.Array],
                q_offset: int = 0) -> jax.Array:
    """(sq, bk) bool mask for a k-chunk starting at absolute position ``base``."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = base + jnp.arange(bk)[None, :]
    m = jnp.ones((sq, bk), bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    if kv_valid_len is not None:
        m &= kpos < kv_valid_len
    return m


def blocked_splitmax_attention(
    q_q: jax.Array, k_q: jax.Array, v_q: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cfg: LUTConfig, exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid_len: Optional[jax.Array] = None,
    block_k: int = 512,
    exact_recip: bool = False,
) -> jax.Array:
    """int8 split-softmax attention as a k-chunk scan.  Shapes as ref.py.

    A ragged Sk is zero-padded to whole chunks; the padded keys sit past
    ``kv_valid_len`` (defaulted to Sk) and are masked.
    """
    b, hq, sq, d = q_q.shape
    _, hkv, sk, _ = k_q.shape
    g = hq // hkv
    block_k = min(block_k, sk)
    nk = -(-sk // block_k)
    if nk * block_k != sk:
        pad = ((0, 0), (0, 0), (0, nk * block_k - sk), (0, 0))
        k_q, v_q = jnp.pad(k_q, pad), jnp.pad(v_q, pad)
        if kv_valid_len is None:
            kv_valid_len = jnp.int32(sk)

    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)
           ).astype(jnp.float32)
    # grouped view avoids materializing GQA-repeated K/V
    qg = q_q.reshape(b, hkv, g, sq, d)
    ks = jnp.moveaxis(k_q.reshape(b, hkv, nk, block_k, d), 2, 0)
    vs = jnp.moveaxis(v_q.reshape(b, hkv, nk, block_k, d), 2, 0)

    def body(carry, xs):
        acc, s = carry
        idx, kc, vc = xs
        base = idx * block_k
        z32 = jnp.einsum("bkgqd,bkcd->bkgqc", qg, kc,
                         preferred_element_type=jnp.int32)
        z_q = qlib.requantize_int32(z32, m_z)
        e = lut_lib.exp_lookup(z_q, exp_lut).astype(jnp.float32)
        mask = _chunk_mask(sq, block_k, base, causal=causal, window=window,
                           kv_valid_len=kv_valid_len)
        e = jnp.where(mask[None, None, None], e, 0.0)
        acc = acc + jnp.einsum("bkgqc,bkcd->bkgqd", e, vc.astype(jnp.float32),
                               precision=_EXACT)
        s = s + jnp.sum(e, axis=-1)
        return (acc, s), None

    acc0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    s0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    (acc, s), _ = jax.lax.scan(body, (acc0, s0),
                               (jnp.arange(nk), ks, vs))
    s = jnp.maximum(s, 1.0)[..., None]
    if exact_recip:
        out = acc / s
    else:
        r, e2 = lut_lib.recip_lookup(s, recip_lut, cfg)
        out = lut_lib.recip_apply(acc, r, e2)
    return (out * s_v).reshape(b, hq, sq, d)


def grouped_splitmax_decode(
    q_q: jax.Array,            # (B, Hq, D) int8
    k_cache: jax.Array,        # (B, Hkv, S, D) int8
    v_cache: jax.Array,        # (B, Hkv, S, D) int8
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cache_len: jax.Array,      # (B,) int32
    cfg: LUTConfig, exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    window: Optional[int] = None,
    exact_recip: bool = False,
) -> jax.Array:
    """One-token decode in pure XLA, GQA-grouped (no KV head repetition).

    Scores are (B, Hkv, G, S) — linear in cache length, which is the whole
    point of decode; no chunking needed.  Numerics identical to the Pallas
    decode kernel and the oracle.
    """
    b, hq, d = q_q.shape
    _, hkv, s_max, _ = k_cache.shape
    g = hq // hkv
    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)
           ).astype(jnp.float32)
    qg = q_q.reshape(b, hkv, g, d)
    z32 = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache,
                     preferred_element_type=jnp.int32)
    z_q = qlib.requantize_int32(z32, m_z)
    e = lut_lib.exp_lookup(z_q, exp_lut).astype(jnp.float32)
    kpos = jnp.arange(s_max)[None, :]
    valid = kpos < cache_len[:, None]
    if window is not None:
        valid &= kpos > cache_len[:, None] - 1 - window
    e = jnp.where(valid[:, None, None, :], e, 0.0)
    acc = jnp.einsum("bkgs,bksd->bkgd", e, v_cache.astype(jnp.float32),
                     precision=_EXACT)
    s = jnp.maximum(jnp.sum(e, axis=-1), 1.0)[..., None]
    if exact_recip:
        out = acc / s
    else:
        r, e2 = lut_lib.recip_lookup(s, recip_lut, cfg)
        out = lut_lib.recip_apply(acc, r, e2)
    return (out * s_v).reshape(b, hq, d)


def blocked_fakequant_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    cfg: LUTConfig,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid_len: Optional[jax.Array] = None,
    block_k: int = 512,
    remat: bool = True,
    score_dtype: jnp.dtype = jnp.float32,
    triangular: bool = False,
) -> jax.Array:
    """Training-mode (STE) split-softmax attention, k-chunk scan + remat.

    Differentiable: gradients flow through the scan; with ``remat`` the
    backward pass recomputes each chunk's scores instead of keeping the
    (Sq x Sk) score matrix alive — the memory behaviour that makes 4k-token
    training of the assigned architectures fit HBM.

    Perf levers (§Perf hillclimb; defaults are the paper-faithful baseline):
      * ``score_dtype=bfloat16`` — halves the HBM traffic of the score chain
        (z / e are [0,1]-ranged; bf16's 8-bit mantissa costs ~0.4% per prob,
        the same order as the recip LUT already accepted by the paper).
      * ``triangular`` — causal runs process q in chunks, each scanning only
        its live k prefix: ~2x fewer score FLOPs+bytes (dead chunks in the
        rectangular schedule compute fully-masked tiles).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    block_k = min(block_k, sk)
    assert sk % block_k == 0, (sk, block_k)
    nk = sk // block_k
    s_z = jnp.float32(cfg.scale_z)

    qg = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    kf = k.reshape(b, hkv, nk, block_k, d).astype(jnp.float32)
    vf = v.reshape(b, hkv, nk, block_k, d).astype(jnp.float32)
    rsqrt_d = 1.0 / jnp.sqrt(jnp.float32(d))

    import numpy as _np
    # LUT representability floor (see split_softmax.fakequant_split_softmax)
    floor = jnp.float32(-(cfg.exp_frac_bits + 1) * _np.log(2.0))
    sd = jnp.dtype(score_dtype)

    def run_scan(q_chunk, q_offset, n_live):
        """Scan k chunks [0, n_live) against q_chunk (b,hkv,g,sq_c,d)."""
        sq_c = q_chunk.shape[3]

        def body(carry, xs):
            acc, s = carry
            idx, kc, vc = xs
            base = idx * block_k
            z = (jnp.einsum("bkgqd,bkcd->bkgqc", q_chunk, kc)
                 * rsqrt_d)
            z_fq = qlib.fake_quant(z, s_z)
            zdot = z_fq - Z_QUANT_MAX * s_z
            e = jnp.exp(zdot).astype(sd)
            e = jnp.where(zdot < floor, jnp.zeros((), sd), e)
            mask = _chunk_mask(sq_c, block_k, base, causal=causal,
                               window=window, kv_valid_len=kv_valid_len,
                               q_offset=q_offset)
            e = jnp.where(mask[None, None, None], e, jnp.zeros((), sd))
            acc = acc + jnp.einsum("bkgqc,bkcd->bkgqd", e,
                                   vc.astype(sd)).astype(jnp.float32)
            s = s + jnp.sum(e.astype(jnp.float32), axis=-1)
            return (acc, s), None

        wrapped = jax.checkpoint(body) if remat else body
        acc0 = jnp.zeros((b, hkv, g, sq_c, d), jnp.float32)
        s0 = jnp.zeros((b, hkv, g, sq_c), jnp.float32)
        ks = jnp.moveaxis(kf[:, :, :n_live], 2, 0)
        vs = jnp.moveaxis(vf[:, :, :n_live], 2, 0)
        (acc, s), _ = jax.lax.scan(wrapped, (acc0, s0),
                                   (jnp.arange(n_live), ks, vs))
        return acc / jnp.maximum(s, 1e-30)[..., None]

    if causal and triangular and sq == sk and nk > 1:
        # q chunks aligned to k chunks: chunk qi needs k chunks [0, qi]
        outs = []
        n_qc = min(nk, 8)                       # cap HLO growth
        per = sq // n_qc
        assert sq % n_qc == 0
        for qi in range(n_qc):
            q_chunk = qg[:, :, :, qi * per:(qi + 1) * per, :]
            n_live = ((qi + 1) * per + block_k - 1) // block_k
            outs.append(run_scan(q_chunk, qi * per, n_live))
        out = jnp.concatenate(outs, axis=3)
    else:
        out = run_scan(qg, 0, nk)
    return out.reshape(b, hq, sq, d)
