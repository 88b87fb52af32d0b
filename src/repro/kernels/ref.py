"""Pure-jnp oracles for every Pallas kernel in this package.

These implement *exactly* the blocked arithmetic the kernels perform —
including the two-level (per-tile int32, cross-tile float32) accumulation of
the split-softmax denominator — so the kernel sweeps in ``tests/`` can assert
tight tolerances (and bit-exact equality for the integer sub-paths).

Shapes follow the kernel conventions:
  q        : (B, Hq,  Sq, D)  int8
  k, v     : (B, Hkv, Sk, D)  int8      (GQA: Hq = G * Hkv)
  output   : (B, Hq,  Sq, D)  float32
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import lut as lut_lib
from repro.core import quantization as qlib
from repro.core.lut import LUTConfig


# ---------------------------------------------------------------------------
# int8 GEMM ("the CIM core")
# ---------------------------------------------------------------------------

def int8_matmul_ref(x_q: jax.Array, w_q: jax.Array) -> jax.Array:
    """(M, K) int8 @ (K, N) int8 -> (M, N) int32."""
    return jax.lax.dot_general(
        x_q, w_q, (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def int8_matmul_requant_ref(x_q: jax.Array, w_q: jax.Array,
                            multiplier: jax.Array) -> jax.Array:
    """GEMM fused with the 32b->8b quantization unit."""
    return qlib.requantize_int32(int8_matmul_ref(x_q, w_q), multiplier)


# ---------------------------------------------------------------------------
# split-softmax attention, blocked exactly like the kernel
# ---------------------------------------------------------------------------

def _expand_gqa(k_q: jax.Array, n_q_heads: int) -> jax.Array:
    """Repeat kv heads to match query heads: (B,Hkv,S,D) -> (B,Hq,S,D)."""
    b, hkv, s, d = k_q.shape
    group = n_q_heads // hkv
    if group == 1:
        return k_q
    return jnp.repeat(k_q, group, axis=1)


def _attn_mask(sq: int, sk: int, *, causal: bool, window: Optional[int],
               q_offset: int = 0) -> jax.Array:
    """(sq, sk) bool mask; True = attend.  ``q_offset`` maps local query row i
    to absolute position ``q_offset + i`` (decode / blocked prefill)."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


def splitmax_attention_ref(
    q_q: jax.Array, k_q: jax.Array, v_q: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cfg: LUTConfig, exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_k: int = 128,
    exact_recip: bool = False,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Blocked split-softmax attention oracle.

    Datapath per (head, q-row):
      1. z32 = q_q . k_q^T (int32 MACs — the CIM array)
      2. z_q  = requant(z32 * m_z) to int8 (32b->8b quantization unit),
         m_z = s_q*s_k / (sqrt(D) * s_z)
      3. e = ExpLUT[z_q]  (int32, <= 2^f_e; masked lanes -> 0)
      4. acc_v += e . V  and  acc_s += sum(e)   — the *split*: both accumulate
         in the same k pass, per k-tile in exact int32, across tiles in f32
      5. out = acc_v * RecipLUT(acc_s) * s_v    — one multiply, no division
    """
    b, hq, sq, d = q_q.shape
    k_q = _expand_gqa(k_q, hq)
    v_q = _expand_gqa(v_q, hq)
    sk = k_q.shape[2]
    n_tiles = -(-sk // block_k)

    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)).astype(
        jnp.float32)

    # 1-2: scores -> int8 (whole-row at once: requant is elementwise so
    # blocking does not change it)
    z32 = jax.lax.dot_general(
        q_q, k_q, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32)                    # (B,Hq,Sq,Sk)
    z_q = qlib.requantize_int32(z32, m_z)

    # 3: LUT + mask
    e = lut_lib.exp_lookup(z_q, exp_lut)                     # int32
    full_mask = _attn_mask(sq, sk, causal=causal, window=window)
    if mask is not None:
        full_mask = full_mask & mask
    e = jnp.where(full_mask, e, 0)

    # 4: split accumulation, tiled like the kernel (a ragged last tile is
    # zero-padded, as the kernel pads its inputs)
    e_t = jnp.pad(e, ((0, 0),) * 3 + ((0, n_tiles * block_k - sk),))
    e_t = e_t.reshape(b, hq, sq, n_tiles, block_k)
    s_tile = jnp.sum(e_t, axis=-1, dtype=jnp.int32)          # exact per tile
    acc_s = jnp.sum(s_tile.astype(jnp.float32), axis=-1)     # f32 across tiles
    # e <= 2^15 is not bf16-exact: HIGHEST keeps the f32 dot exact on TPU,
    # whose default f32 precision rounds operands to bf16
    acc_v = jax.lax.dot_general(
        e.astype(jnp.float32), v_q.astype(jnp.float32),
        (((3,), (2,)), ((0, 1), (0, 1))),
        precision=jax.lax.Precision.HIGHEST)                 # (B,Hq,Sq,D)

    # 5: reciprocal
    acc_s = jnp.maximum(acc_s, 1.0)[..., None]
    if exact_recip:
        out = acc_v / acc_s
    else:
        r, e2 = lut_lib.recip_lookup(acc_s.astype(jnp.int32), recip_lut, cfg)
        out = lut_lib.recip_apply(acc_v, r, e2)
    return out * s_v


def splitmax_decode_ref(
    q_q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cache_len: jax.Array,
    cfg: LUTConfig, exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    window: Optional[int] = None,
    exact_recip: bool = False,
) -> jax.Array:
    """One-token decode against an int8 KV cache (paper Eq. 3 streaming).

    q_q     : (B, Hq, D) int8 — the new token's query
    k/v_cache: (B, Hkv, S_max, D) int8
    cache_len: (B,) int32 — number of valid cache entries (includes the
               current token, already written at position cache_len - 1)
    """
    b, hq, d = q_q.shape
    s_max = k_cache.shape[2]
    kpos = jnp.arange(s_max)[None, :]                         # (1, S)
    valid = kpos < cache_len[:, None]                         # (B, S)
    if window is not None:
        valid &= kpos > (cache_len[:, None] - 1 - window)
    valid = valid[:, None, None, :]                           # (B,1,1,S)
    out = splitmax_attention_ref(
        q_q[:, :, None, :], k_cache, v_cache, s_q, s_k, s_v,
        cfg, exp_lut, recip_lut, causal=False, window=None,
        block_k=min(128, s_max), exact_recip=exact_recip, mask=valid)
    return out[:, :, 0, :]                                    # (B, Hq, D)


# ---------------------------------------------------------------------------
# float / fakequant attention baselines (paper's comparison point)
# ---------------------------------------------------------------------------

def safe_softmax_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                               *, causal: bool = True,
                               window: Optional[int] = None,
                               mask: Optional[jax.Array] = None) -> jax.Array:
    """Float 3-pass safe-softmax attention (B,Hq,Sq,D) x (B,Hkv,Sk,D)."""
    b, hq, sq, d = q.shape
    k = _expand_gqa(k, hq)
    v = _expand_gqa(v, hq)
    sk = k.shape[2]
    z = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    m = _attn_mask(sq, sk, causal=causal, window=window)
    if mask is not None:
        m = m & mask
    z = jnp.where(m, z, -jnp.inf)
    p = jax.nn.softmax(z, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)        # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
