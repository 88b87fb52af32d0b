"""Dispatch wrappers: one public op per kernel, with automatic backend choice.

``impl`` semantics:
  * ``"auto"``      — Pallas on TPU, blocked-scan XLA elsewhere (same math,
                      so CPU dry-runs and TPU production share numerics).
  * ``"pallas"``    — force the compiled Pallas kernel (TPU).
  * ``"xla"``       — blocked (lax.scan) pure-XLA path: production numerics
                      with O(Sq * block_k) score memory; what the multi-pod
                      dry-run lowers.
  * ``"interpret"`` — Pallas kernel body executed by the interpreter (CPU
                      correctness testing of the *kernel code itself*).
  * ``"ref"``       — force the materializing pure-jnp oracle (tests only).

All ops take/return the layouts documented in ``kernels/ref.py``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import lut as lut_lib
from repro.core import paged_kv
from repro.core import quantization as qlib
from repro.core.lut import LUTConfig
from repro.kernels import autotune
from repro.kernels import blocked as blocked_lib
from repro.kernels import ref as ref_lib
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.paged_append import paged_kv_append_pallas
from repro.kernels.pallas_compat import pallas_supported
from repro.kernels.splitmax_attn import splitmax_attention_pallas
from repro.kernels.splitmax_decode import (
    splitmax_decode_fused_paged_pallas, splitmax_decode_fused_pallas,
    splitmax_decode_fused_verify_paged_pallas,
    splitmax_decode_fused_verify_pallas, splitmax_decode_paged_pallas,
    splitmax_decode_pallas)


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if pallas_supported() else "xla"
    return impl


# ---------------------------------------------------------------------------
# split-softmax attention (prefill / encoder / training forward)
# ---------------------------------------------------------------------------

def splitmax_attention(
    q_q: jax.Array, k_q: jax.Array, v_q: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    cfg: LUTConfig,
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid_len: Optional[jax.Array] = None,
    block_q: int = 128,
    block_k: int = 128,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    impl: str = "auto",
) -> jax.Array:
    """(B,Hq,Sq,D) int8 x (B,Hkv,Sk,D) int8 -> (B,Hq,Sq,D) f32."""
    impl = _resolve(impl)
    d = q_q.shape[-1]
    sk = k_q.shape[2]
    if kv_valid_len is None:
        kv_valid_len = jnp.int32(sk)
    if impl == "ref":
        mask = (jnp.arange(sk) < kv_valid_len)[None, None, None, :]
        return ref_lib.splitmax_attention_ref(
            q_q, k_q, v_q, s_q, s_k, s_v, cfg, exp_lut, recip_lut,
            causal=causal, window=window, block_k=min(block_k, sk),
            exact_recip=exact_recip, mask=mask)
    if impl == "xla":
        return blocked_lib.blocked_splitmax_attention(
            q_q, k_q, v_q, s_q, s_k, s_v, cfg, exp_lut, recip_lut,
            causal=causal, window=window, kv_valid_len=kv_valid_len,
            block_k=max(block_k, 512), exact_recip=exact_recip)
    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)
           ).astype(jnp.float32)
    return splitmax_attention_pallas(
        q_q, k_q, v_q, m_z, s_v, kv_valid_len, exp_lut, recip_lut,
        cfg=cfg, causal=causal, window=window, block_q=block_q,
        block_k=block_k, lut_mode=lut_mode, exact_recip=exact_recip,
        interpret=(impl == "interpret"))


# ---------------------------------------------------------------------------
# split-softmax decode (one token vs int8 KV cache)
# ---------------------------------------------------------------------------

def _per_slot_scale(s_q, b: int) -> jax.Array:
    """Normalize a q quantization scale to per-slot (B,) f32.

    Serving calibrates ``s_q`` per batch row (the absmax of that slot's own
    query), so one slot's int8 grid never depends on its batch neighbours —
    the property that makes continuous batching and speculative decoding
    bit-reproducible under churn.  Scalar callers (tests, sweeps) broadcast
    to identical per-slot values, which is bit-identical to the old scalar
    path.  Accepts scalar, (1,), (B,), or keepdims shapes like (B, 1, 1).
    """
    s = jnp.asarray(s_q, jnp.float32).reshape(-1)
    return jnp.broadcast_to(s, (b,))


def _per_token_scale(s_q, b: int, t: int) -> jax.Array:
    """Normalize a verify q scale to (B, T) f32 (accepts scalar/(T,)/(B,T))."""
    s = jnp.asarray(s_q, jnp.float32)
    if s.ndim < 2:
        s = s.reshape(1, -1)
    return jnp.broadcast_to(s, (b, t))


def splitmax_decode(
    q_q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cache_len: jax.Array,
    exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    block_k: Optional[int] = 128,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    impl: str = "auto",
) -> jax.Array:
    """(B,Hq,D) int8 x (B,Hkv,S,D) int8 cache -> (B,Hq,D) f32.

    ``s_q`` may be a scalar or per-slot (B,) — see :func:`_per_slot_scale`.
    ``block_k=None`` delegates the k-tile choice to ``kernels/autotune``.
    """
    impl = _resolve(impl)
    b = q_q.shape[0]
    s_q = _per_slot_scale(s_q, b)
    if impl == "ref":
        return ref_lib.splitmax_decode_ref(
            q_q, k_cache, v_cache, s_q.reshape(b, 1, 1, 1), s_k, s_v,
            cache_len, cfg,
            exp_lut, recip_lut, window=window, exact_recip=exact_recip)
    if impl == "xla":
        return blocked_lib.grouped_splitmax_decode(
            q_q, k_cache, v_cache, s_q.reshape(b, 1, 1, 1), s_k, s_v,
            cache_len, cfg,
            exp_lut, recip_lut, window=window, exact_recip=exact_recip)
    d = q_q.shape[-1]
    g_pad_min = 8
    if block_k is None:
        block_k, g_pad_min = autotune.decode_tile(d, k_cache.shape[2], impl)
    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)
           ).astype(jnp.float32)
    return splitmax_decode_pallas(
        q_q, k_cache, v_cache, m_z, s_v, cache_len, exp_lut, recip_lut,
        cfg=cfg, window=window, block_k=block_k, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip,
        interpret=(impl == "interpret"))


def splitmax_decode_fused(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cache_len: jax.Array,
    exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    block_k: Optional[int] = None,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    impl: str = "auto",
) -> jax.Array:
    """Fused decode: fp (B,Hq,D) q x int8 cache -> (B,Hq,D) f32.

    The Pallas path quantizes q *inside* the kernel (scalar-prefetched
    ``s_q``) and streams quantize -> QK^T -> LUT split-softmax -> PV with no
    HBM writes between stages.  The ref/XLA fallbacks quantize first and run
    the composed path — the identical round+clip, so every impl bit-matches
    the composed pipeline.  ``s_q`` may be a scalar or per-slot (B,).
    ``block_k=None`` (the default) asks ``kernels/autotune`` for the k-tile.
    """
    impl = _resolve(impl)
    b = q.shape[0]
    s_q = _per_slot_scale(s_q, b)
    if impl in ("ref", "xla"):
        q_q = qlib.quantize(q, s_q[:, None, None])
        fn = (ref_lib.splitmax_decode_ref if impl == "ref"
              else blocked_lib.grouped_splitmax_decode)
        return fn(q_q, k_cache, v_cache, s_q.reshape(b, 1, 1, 1), s_k, s_v,
                  cache_len, cfg,
                  exp_lut, recip_lut, window=window, exact_recip=exact_recip)
    d = q.shape[-1]
    g_pad_min = 8
    if block_k is None:
        block_k, g_pad_min = autotune.decode_tile(d, k_cache.shape[2], impl)
    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)
           ).astype(jnp.float32)
    return splitmax_decode_fused_pallas(
        q, k_cache, v_cache, m_z, s_q, s_v, cache_len, exp_lut, recip_lut,
        cfg=cfg, window=window, block_k=block_k, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip,
        interpret=(impl == "interpret"))


def splitmax_decode_paged(
    q_q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    block_table: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cache_len: jax.Array,
    exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    impl: str = "auto",
) -> jax.Array:
    """(B,Hq,D) int8 x paged int8 pool + (B,mb) block table -> (B,Hq,D) f32.

    The Pallas path gathers K/V tiles through the table inside the kernel's
    index map; the XLA/ref fallbacks materialize contiguous K/V with
    :func:`repro.core.paged_kv.gather_kv` first and then reuse the dense
    decode — same numerics, so the paged and dense paths bit-match.
    ``s_q`` may be a scalar or per-slot (B,).
    """
    impl = _resolve(impl)
    b = q_q.shape[0]
    s_q = _per_slot_scale(s_q, b)
    if impl in ("ref", "xla"):
        k_cache = paged_kv.gather_kv(k_pages, block_table)
        v_cache = paged_kv.gather_kv(v_pages, block_table)
        fn = (ref_lib.splitmax_decode_ref if impl == "ref"
              else blocked_lib.grouped_splitmax_decode)
        return fn(q_q, k_cache, v_cache, s_q.reshape(b, 1, 1, 1), s_k, s_v,
                  cache_len, cfg,
                  exp_lut, recip_lut, window=window, exact_recip=exact_recip)
    d = q_q.shape[-1]
    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)
           ).astype(jnp.float32)
    return splitmax_decode_paged_pallas(
        q_q, k_pages, v_pages, block_table, m_z, s_v, cache_len,
        exp_lut, recip_lut, cfg=cfg, window=window, lut_mode=lut_mode,
        exact_recip=exact_recip, interpret=(impl == "interpret"))


def splitmax_decode_fused_paged(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    block_table: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cache_len: jax.Array,
    exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    impl: str = "auto",
) -> jax.Array:
    """Fused paged decode: fp q + in-kernel quantize + block-table gather.

    Pallas path = one kernel launch for the whole serving datapath (the pool
    tile gather rides the BlockSpec index map, the quantize rides scalar
    prefetch).  Ref/XLA fallbacks materialize the gather, quantize, and run
    the composed dense decode — bit-matching the composed paged path.
    ``block_k`` is fixed by the pool layout, so only the accumulator pad is
    tunable here.  ``s_q`` may be a scalar or per-slot (B,).
    """
    impl = _resolve(impl)
    b = q.shape[0]
    s_q = _per_slot_scale(s_q, b)
    if impl in ("ref", "xla"):
        q_q = qlib.quantize(q, s_q[:, None, None])
        k_cache = paged_kv.gather_kv(k_pages, block_table)
        v_cache = paged_kv.gather_kv(v_pages, block_table)
        fn = (ref_lib.splitmax_decode_ref if impl == "ref"
              else blocked_lib.grouped_splitmax_decode)
        return fn(q_q, k_cache, v_cache, s_q.reshape(b, 1, 1, 1), s_k, s_v,
                  cache_len, cfg,
                  exp_lut, recip_lut, window=window, exact_recip=exact_recip)
    d = q.shape[-1]
    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)
           ).astype(jnp.float32)
    return splitmax_decode_fused_paged_pallas(
        q, k_pages, v_pages, block_table, m_z, s_q, s_v, cache_len,
        exp_lut, recip_lut, cfg=cfg, window=window, lut_mode=lut_mode,
        exact_recip=exact_recip, interpret=(impl == "interpret"))


def paged_kv_append(
    k_pages: jax.Array, v_pages: jax.Array,
    blk: jax.Array, off: jax.Array,
    k_new: jax.Array, v_new: jax.Array,
    *,
    impl: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Write slot ``b``'s new int8 K/V row (B, Hkv, D) at
    ``pages[blk[b], :, off[b], :]`` of both (num_blocks, Hkv, block_k, D)
    pools.

    The Pallas path updates the pools in place (aliased operands, one
    8-row group per slot read and written back); the ref/XLA fallbacks are
    the scatter, which on TPU makes XLA copy the whole pool.  Rows of slots
    that share a block (retired slots on the trash block) land in no
    defined order.
    """
    impl = _resolve(impl)
    if impl in ("ref", "xla"):
        return (k_pages.at[blk, :, off, :].set(k_new),
                v_pages.at[blk, :, off, :].set(v_new))
    return paged_kv_append_pallas(k_pages, v_pages, blk, off, k_new, v_new,
                                  interpret=(impl == "interpret"))


# ---------------------------------------------------------------------------
# speculative verify: gamma draft tokens vs the int8 KV cache, one launch
# ---------------------------------------------------------------------------

def _verify_fallback(fn, q, k_cache, v_cache, s_q, s_k, s_v, cache_len, cfg,
                     exp_lut, recip_lut, *, window, exact_recip):
    """Ref/XLA verify = literally the sequential decode, once per draft
    token at its effective length — the parity oracle *by construction*:
    token t's attention call is byte-for-byte the call the non-speculative
    scheduler would have made at that step.  ``s_q`` is (B, T)."""
    b, _, t, _ = q.shape
    outs = []
    for i in range(t):
        eff = cache_len - (t - 1 - i)
        q_q = qlib.quantize(q[:, :, i, :], s_q[:, i][:, None, None])
        outs.append(fn(q_q, k_cache, v_cache,
                       s_q[:, i].reshape(b, 1, 1, 1), s_k, s_v, eff, cfg,
                       exp_lut, recip_lut, window=window,
                       exact_recip=exact_recip))
    return jnp.stack(outs, axis=2)


def splitmax_decode_fused_verify(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cache_len: jax.Array,
    exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    block_k: Optional[int] = None,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    impl: str = "auto",
) -> jax.Array:
    """Fused multi-token verify: fp (B,Hq,T,D) draft queries x int8 cache
    -> (B,Hq,T,D) f32.

    ``s_q`` is (T,) or (B, T) — one absmax scale per (slot,) draft token,
    matching the per-slot per-step calibration of the sequential path —
    and ``cache_len`` counts
    ALL T verify tokens (their K/V must already be in the cache; the
    per-row causal mask hides token t's successors).  The Pallas path runs
    all gamma queries in one launch; ref/XLA fall back to the per-token
    sequential decode, which is the bitwise contract the speculative
    scheduler relies on.  ``block_k=None`` asks ``autotune.verify_tile``.
    """
    impl = _resolve(impl)
    s_q = _per_token_scale(s_q, q.shape[0], q.shape[2])
    if impl in ("ref", "xla"):
        fn = (ref_lib.splitmax_decode_ref if impl == "ref"
              else blocked_lib.grouped_splitmax_decode)
        return _verify_fallback(fn, q, k_cache, v_cache, s_q, s_k, s_v,
                                cache_len, cfg, exp_lut, recip_lut,
                                window=window, exact_recip=exact_recip)
    d = q.shape[-1]
    g_pad_min = 8
    if block_k is None:
        block_k, g_pad_min = autotune.verify_tile(d, k_cache.shape[2],
                                                  q.shape[2])
    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)
           ).astype(jnp.float32)
    return splitmax_decode_fused_verify_pallas(
        q, k_cache, v_cache, m_z, s_q, s_v, cache_len, exp_lut, recip_lut,
        cfg=cfg, window=window, block_k=block_k, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip,
        interpret=(impl == "interpret"))


def splitmax_decode_fused_verify_paged(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    block_table: jax.Array,
    s_q: jax.Array, s_k: jax.Array, s_v: jax.Array,
    cache_len: jax.Array,
    exp_lut: jax.Array, recip_lut: jax.Array,
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    impl: str = "auto",
) -> jax.Array:
    """Paged fused verify: gamma draft queries vs the block pool, gathered
    through the table inside the kernel.  Ref/XLA fallbacks materialize the
    gather and loop the sequential decode per token — the same bitwise
    contract as the dense entry."""
    impl = _resolve(impl)
    s_q = _per_token_scale(s_q, q.shape[0], q.shape[2])
    if impl in ("ref", "xla"):
        k_cache = paged_kv.gather_kv(k_pages, block_table)
        v_cache = paged_kv.gather_kv(v_pages, block_table)
        fn = (ref_lib.splitmax_decode_ref if impl == "ref"
              else blocked_lib.grouped_splitmax_decode)
        return _verify_fallback(fn, q, k_cache, v_cache, s_q, s_k, s_v,
                                cache_len, cfg, exp_lut, recip_lut,
                                window=window, exact_recip=exact_recip)
    d = q.shape[-1]
    _, g_pad_min = autotune.verify_tile(d, k_pages.shape[2]
                                        * block_table.shape[1], q.shape[2])
    m_z = (s_q * s_k / (jnp.sqrt(jnp.float32(d)) * cfg.scale_z)
           ).astype(jnp.float32)
    return splitmax_decode_fused_verify_paged_pallas(
        q, k_pages, v_pages, block_table, m_z, s_q, s_v, cache_len,
        exp_lut, recip_lut, cfg=cfg, window=window, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip,
        interpret=(impl == "interpret"))


# ---------------------------------------------------------------------------
# int8 GEMM
# ---------------------------------------------------------------------------

def int8_matmul(x_q: jax.Array, w_q: jax.Array,
                multiplier: Optional[jax.Array] = None,
                *, block_m: int = 256, block_n: int = 256, block_k: int = 256,
                impl: str = "auto") -> jax.Array:
    """(M,K) int8 @ (K,N) int8 -> int32 (or int8 with fused requant)."""
    impl = _resolve(impl)
    if impl == "ref":
        if multiplier is None:
            return ref_lib.int8_matmul_ref(x_q, w_q)
        return ref_lib.int8_matmul_requant_ref(x_q, w_q, multiplier)
    m, k = x_q.shape
    _, n = w_q.shape
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))
    return int8_matmul_pallas(
        x_q, w_q, multiplier, block_m=bm, block_n=bn, block_k=bk,
        interpret=(impl == "interpret"))
