"""CIMpleAttention — the paper's attention datapath as a composable primitive.

Three execution modes, one numerics story:

  * ``"float"``     — 3-pass safe-softmax attention (the paper's baseline,
                      PyTorch-LogSoftmax-equivalent).
  * ``"fakequant"`` — training mode (QAT): scores snap to the int8 grid via a
                      straight-through estimator and softmax uses the static
                      ``z_quant_max`` ceiling instead of the row max — the
                      differentiable twin of the deployed LUT datapath.
  * ``"int8"``      — deployment mode: Q/K/V quantized to int8, scores through
                      the 32b->8b requant unit, exp + reciprocal LUTs, split
                      numerator/denominator accumulation (Pallas kernels on
                      TPU, the same math via XLA elsewhere).

The mode is a config switch, so a model trained with ``fakequant`` serves with
``int8`` — that is the point of the paper's |accuracy drop| <= 0.6% claim, and
benchmarks/softmax_accuracy.py measures exactly this transition.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import lut as lut_lib
from repro.core import quantization as qlib
from repro.core import split_softmax as ss
from repro.core.lut import LUTConfig
from repro.kernels import blocked as blocked_lib
from repro.kernels import ops
from repro.kernels import ref as ref_lib


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """Static attention configuration (hashable; safe as a jit static arg)."""
    mode: str = "fakequant"            # float | fakequant | int8
    scale_z: float = 8.0 / 127         # score quant scale (clip ~ +-8)
    window: Optional[int] = None       # sliding-window size (SWA), None = full
    causal: bool = True
    impl: str = "auto"                 # kernel dispatch (see kernels/ops.py)
    fused: bool = True                 # decode: fused quantize->QK^T->LUT->PV
    lut_mode: str = "onehot"
    exact_recip: bool = False
    block_q: int = 128
    block_k: int = 128
    # perf levers (baseline = paper-faithful defaults; see §Perf)
    score_dtype: str = "float32"       # f32 | bfloat16 score chain
    triangular: bool = False           # causal triangular chunk schedule

    @property
    def lut_config(self) -> LUTConfig:
        return LUTConfig(scale_z=self.scale_z)


@functools.lru_cache(maxsize=32)
def _luts_for(scale_z: float):
    """LUT pair as *numpy* host constants — cached device arrays created
    inside a traced scope would leak tracers into later traces."""
    cfg = LUTConfig(scale_z=scale_z)
    return lut_lib.build_exp_lut(cfg), lut_lib.build_recip_lut(cfg)


# ---------------------------------------------------------------------------
# Full-sequence attention (training / prefill / encoder)
# ---------------------------------------------------------------------------

def attention(q: jax.Array, k: jax.Array, v: jax.Array, spec: AttentionSpec,
              *, kv_valid_len: Optional[jax.Array] = None,
              mask: Optional[jax.Array] = None) -> jax.Array:
    """(B,Hq,Sq,D) x (B,Hkv,Sk,D) -> (B,Hq,Sq,D), dtype of q.

    Float inputs; quantization (when the mode asks for it) happens inside,
    with absmax calibration under stop-gradient — i.e. what a calibration
    pass over the deployed activations produces.
    """
    in_dtype = q.dtype
    if spec.mode == "float":
        out = ref_lib.safe_softmax_attention_ref(
            q, k, v, causal=spec.causal, window=spec.window, mask=mask)
        return out.astype(in_dtype)

    if spec.mode == "fakequant":
        # blocked scan + remat: production training path (O(Sq*block_k)
        # score memory); the einsum twin in split_softmax.py is its oracle.
        out = blocked_lib.blocked_fakequant_attention(
            q, k, v, spec.lut_config, causal=spec.causal,
            window=spec.window, kv_valid_len=kv_valid_len,
            block_k=max(spec.block_k, 512),
            score_dtype=jnp.dtype(spec.score_dtype),
            triangular=spec.triangular)
        return out.astype(in_dtype)

    assert spec.mode == "int8", spec.mode
    s_q = jax.lax.stop_gradient(qlib.absmax_scale(q))
    s_k = jax.lax.stop_gradient(qlib.absmax_scale(k))
    s_v = jax.lax.stop_gradient(qlib.absmax_scale(v))
    exp_lut, recip_lut = _luts_for(spec.scale_z)
    out = ops.splitmax_attention(
        qlib.quantize(q, s_q), qlib.quantize(k, s_k), qlib.quantize(v, s_v),
        s_q, s_k, s_v, exp_lut, recip_lut, cfg=spec.lut_config,
        causal=spec.causal, window=spec.window, kv_valid_len=kv_valid_len,
        block_q=spec.block_q, block_k=spec.block_k, lut_mode=spec.lut_mode,
        exact_recip=spec.exact_recip, impl=spec.impl)
    return out.astype(in_dtype)


# ---------------------------------------------------------------------------
# Decode attention (one token vs quantized KV cache) — paper Eq. 3
# ---------------------------------------------------------------------------

def decode_attention(q: jax.Array, k_cache_q: jax.Array, v_cache_q: jax.Array,
                     s_k: jax.Array, s_v: jax.Array, cache_len: jax.Array,
                     spec: AttentionSpec) -> jax.Array:
    """(B,Hq,D) query vs int8 (B,Hkv,S,D) caches -> (B,Hq,D).

    The cache *is* int8 (CIMple stores K and V in the CIM array in int8 with
    static scales); float/fakequant modes dequantize it for their baselines.
    """
    in_dtype = q.dtype
    if spec.mode in ("float", "fakequant"):
        kf = qlib.dequantize(k_cache_q, s_k)
        vf = qlib.dequantize(v_cache_q, s_v)
        s_max = kf.shape[2]
        kpos = jnp.arange(s_max)[None, :]
        valid = kpos < cache_len[:, None]
        if spec.window is not None:
            valid &= kpos > cache_len[:, None] - 1 - spec.window
        out = ref_lib.safe_softmax_attention_ref(
            q[:, :, None, :], kf, vf, causal=False,
            mask=valid[:, None, None, :])[:, :, 0, :]
        return out.astype(in_dtype)

    assert spec.mode == "int8", spec.mode
    # per-slot calibration: each batch row's quantization grid depends only
    # on its own query, so continuous batching / speculative churn never
    # perturbs a neighbouring slot's numerics.
    s_q = jax.lax.stop_gradient(qlib.absmax_scale(q, axis=(1, 2)))  # (B,1,1)
    exp_lut, recip_lut = _luts_for(spec.scale_z)
    if spec.fused:
        # single-launch datapath: fp q enters the kernel, quantization
        # happens in VMEM (no int8 q round-trip through HBM).
        out = ops.splitmax_decode_fused(
            q, k_cache_q, v_cache_q, s_q, s_k, s_v,
            cache_len, exp_lut, recip_lut, cfg=spec.lut_config,
            window=spec.window, block_k=None, lut_mode=spec.lut_mode,
            exact_recip=spec.exact_recip, impl=spec.impl)
    else:
        out = ops.splitmax_decode(
            qlib.quantize(q, s_q), k_cache_q, v_cache_q, s_q, s_k, s_v,
            cache_len, exp_lut, recip_lut, cfg=spec.lut_config,
            window=spec.window, block_k=spec.block_k, lut_mode=spec.lut_mode,
            exact_recip=spec.exact_recip, impl=spec.impl)
    return out.astype(in_dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_table: jax.Array,
                           s_k: jax.Array, s_v: jax.Array,
                           cache_len: jax.Array, spec: AttentionSpec
                           ) -> jax.Array:
    """(B,Hq,D) query vs a paged int8 pool addressed by a block table.

    Pool layout is ``(num_blocks, Hkv, block_k, D)`` with per-slot rows
    ``block_table (B, max_blocks)`` (see :mod:`repro.core.paged_kv`).  The
    int8 path gathers K/V tiles through the table inside the Pallas kernel;
    float/fakequant baselines materialize the gather and reuse
    :func:`decode_attention`, so all modes see identical cache contents.
    """
    in_dtype = q.dtype
    if spec.mode in ("float", "fakequant"):
        from repro.core import paged_kv
        k_cache_q = paged_kv.gather_kv(k_pages, block_table)
        v_cache_q = paged_kv.gather_kv(v_pages, block_table)
        return decode_attention(q, k_cache_q, v_cache_q, s_k, s_v,
                                cache_len, spec)

    assert spec.mode == "int8", spec.mode
    s_q = jax.lax.stop_gradient(qlib.absmax_scale(q, axis=(1, 2)))  # (B,1,1)
    exp_lut, recip_lut = _luts_for(spec.scale_z)
    if spec.fused:
        out = ops.splitmax_decode_fused_paged(
            q, k_pages, v_pages, block_table,
            s_q, s_k, s_v, cache_len, exp_lut, recip_lut, cfg=spec.lut_config,
            window=spec.window, lut_mode=spec.lut_mode,
            exact_recip=spec.exact_recip, impl=spec.impl)
    else:
        out = ops.splitmax_decode_paged(
            qlib.quantize(q, s_q), k_pages, v_pages, block_table,
            s_q, s_k, s_v, cache_len, exp_lut, recip_lut, cfg=spec.lut_config,
            window=spec.window, lut_mode=spec.lut_mode,
            exact_recip=spec.exact_recip, impl=spec.impl)
    return out.astype(in_dtype)


def paged_append(k_pages: jax.Array, v_pages: jax.Array, blk: jax.Array,
                 off: jax.Array, k_new: jax.Array, v_new: jax.Array,
                 spec: AttentionSpec) -> Tuple[jax.Array, jax.Array]:
    """Both pools with slot ``b``'s new int8 K/V row (B, Hkv, D) written at
    ``pages[blk[b], :, off[b], :]``.

    The Pallas path writes the rows in place (aliased pools); the XLA
    fallback is a scatter, which on TPU copies the whole pool.
    """
    return ops.paged_kv_append(k_pages, v_pages, blk, off, k_new, v_new,
                               impl=spec.impl)


def paged_verify_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_table: jax.Array,
                           s_k: jax.Array, s_v: jax.Array,
                           cache_len: jax.Array, spec: AttentionSpec
                           ) -> jax.Array:
    """(B,Hq,T,D) draft queries vs the paged int8 pool -> (B,Hq,T,D).

    The speculative verify pass: all ``T`` draft tokens' K/V are already in
    the pool (``cache_len`` counts them) and each query ``t`` attends up to
    its own position — ``cache_len - (T-1) + t`` entries.  Per-(slot, token)
    ``s_q[b, t]`` is the absmax scale of that slot's token-``t`` query slab,
    exactly what the sequential decode would have computed for that slot at
    that step; that, plus the per-token fallback inside
    :func:`repro.kernels.ops`, is what makes the verify output bitwise
    identical to ``T`` sequential decode steps.
    """
    in_dtype = q.dtype
    t = q.shape[2]
    if spec.mode in ("float", "fakequant"):
        from repro.core import paged_kv
        k_cache_q = paged_kv.gather_kv(k_pages, block_table)
        v_cache_q = paged_kv.gather_kv(v_pages, block_table)
        outs = [decode_attention(q[:, :, i, :], k_cache_q, v_cache_q,
                                 s_k, s_v, cache_len - (t - 1 - i), spec)
                for i in range(t)]
        return jnp.stack(outs, axis=2).astype(in_dtype)

    assert spec.mode == "int8", spec.mode
    # (B, T): slot b / token i gets the absmax of its own query slab —
    # exactly the per-slot scale the sequential decode computes at step i.
    s_q = jax.lax.stop_gradient(
        qlib.absmax_scale(q, axis=(1, 3))[:, 0, :, 0])
    exp_lut, recip_lut = _luts_for(spec.scale_z)
    out = ops.splitmax_decode_fused_verify_paged(
        q, k_pages, v_pages, block_table, s_q, s_k, s_v, cache_len,
        exp_lut, recip_lut, cfg=spec.lut_config, window=spec.window,
        lut_mode=spec.lut_mode, exact_recip=spec.exact_recip, impl=spec.impl)
    return out.astype(in_dtype)
