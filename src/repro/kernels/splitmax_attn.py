"""Pallas TPU kernel: one-pass split-softmax attention (prefill / encoder).

CIMple's split softmax maps onto the TPU as a *deferred-normalization
streaming attention*: because the scores entering softmax are int8-quantized,
``z_quant_max = 127`` bounds them and ``e^(z - 127) <= 1`` — no running max
(FlashAttention's online renormalization) is needed.  The kernel therefore
streams K/V tiles HBM->VMEM once, accumulating

    acc_v += ExpLUT[z_q] . V        (numerator, exact bf16->f32 MXU matmul)
    acc_s += sum_k ExpLUT[z_q]      (denominator, exact int32 per tile)

and applies the reciprocal-LUT multiply exactly once per row at the last
k-tile.  This is the paper's pipelining trick (QK^T -> exp -> .V never stalls
on the row reduction) realized as a Pallas grid.

Hardware mapping notes
----------------------
* The dual-banked "simultaneous read+write" of the CIM array corresponds to
  the automatic double-buffering of BlockSpec tiles (compute on tile i while
  tile i+1 DMAs in).
* The exp LUT is read exactly (``lut_mode='onehot'``, bit-identical to
  ``jnp.take`` in the oracle) as an in-register lane gather: the 256-entry
  table sits on the lanes of two vregs and each 128-lane slice of the score
  tile picks its entries with ``take_along_axis``.  ``lut_mode='compute'``
  recomputes the entry in f32 instead (cheaper, <=1 LSB deviation).
* Every matmul is exact on the MXU: QK^T takes int8 operands with int32
  accumulation, and e.V splits the <=2^15 LUT values into two bf16-exact
  halves (see :func:`_exact_pv`), so no f32 dot depends on the chip's
  default matmul precision.
* The 32b->8b quantization unit is fused into the tile epilogue (requant of
  the z accumulator before the LUT).

Grid: (B*Hq, Sq/block_q, Sk/block_k), k innermost ("arbitrary"), carries in
VMEM scratch.  Causally dead k-tiles are skipped with ``pl.when``.  Any
Sq/Sk is accepted: the launcher pads to the tile and ``kv_valid_len`` masks
the padded keys.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import LUTConfig

NEG_DOMAIN = 128  # index offset: z_q in [-128, 127] -> [0, 255]
LANES = 128       # vreg lane width; a LUT of 256 entries spans two vregs
TABLE_SUBLANES = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _lane_gather(table_ref, idx: jax.Array) -> jax.Array:
    """(rows, 128) int32 indices in [0, 256) -> (rows, 128) f32 entries.

    ``tpu.dynamic_gather`` gathers within one vreg along the lanes, so the
    table is read as two 128-lane halves and the index's top bit selects.
    """
    rows = idx.shape[0]
    lo = jnp.broadcast_to(table_ref[0:1, 0:LANES], (rows, LANES))
    hi = jnp.broadcast_to(table_ref[0:1, LANES:2 * LANES], (rows, LANES))
    low = jnp.bitwise_and(idx, LANES - 1)
    return jnp.where(
        idx >= LANES,
        jnp.take_along_axis(hi, low, axis=1, mode="promise_in_bounds"),
        jnp.take_along_axis(lo, low, axis=1, mode="promise_in_bounds"))


def _table_lookup(idx: jax.Array, table_ref) -> jax.Array:
    """Exact LUT read: (rows, cols) int32 in [0, 256) -> (rows, cols) f32.

    ``table_ref`` is a (8, 256) f32 ref whose rows all hold the table (see
    :func:`_replicate_table`).  The tile is gathered 128 lanes at a time; a
    tile narrower than a multiple of 128 is padded with index 0 first.
    """
    rows, cols = idx.shape
    width = _round_up(cols, LANES)
    if width != cols:
        idx = jnp.concatenate(
            [idx, jnp.zeros((rows, width - cols), jnp.int32)], axis=1)
    parts = [_lane_gather(table_ref, idx[:, j:j + LANES])
             for j in range(0, width, LANES)]
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return out[:, :cols] if width != cols else out


def _recip_lut_inline(s_f32: jax.Array, recip_ref, cfg: LUTConfig) -> jax.Array:
    """Reciprocal-LUT approximation of 1/s — *identical* bit path to
    ``lut_lib.recip_lookup`` (IEEE-754 exponent/mantissa extraction; float
    log2/exp2 are an ulp off at bin boundaries and flip the index), with the
    table read done as a lane gather.  s_f32: (bq, 1) f32 > 0."""
    from repro.core import lut as lut_lib
    idx, expo = lut_lib.recip_mantissa_index(s_f32, cfg.recip_index_bits)
    rows = idx.shape[0]
    r = _lane_gather(recip_ref, jnp.broadcast_to(idx, (rows, LANES)))[:, :1]
    return r * lut_lib.exp2_int(-expo - cfg.recip_frac_bits)


def _qk_scores(q_i8: jax.Array, k_i8: jax.Array) -> jax.Array:
    """The "CIM array": (R, D) x (C, D) int8 -> (R, C) int32 on the MXU."""
    return jax.lax.dot_general(q_i8, k_i8, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _exact_pv(e: jax.Array, v_i8: jax.Array) -> jax.Array:
    """e . V with e (R, C) f32 integers in [0, 2^15] and V (C, D) int8.

    bf16 holds every integer up to 256 exactly, so ``e = 256 * hi + lo``
    with ``hi <= 128`` and ``lo < 256`` gives two bf16-exact operands.
    Products and partial sums stay below 2^24 for C <= 512, so both f32
    accumulations are exact in any order and the result does not depend on
    the matmul precision the backend defaults to.
    """
    hi = jnp.floor(e * (1.0 / 256))
    lo = e - hi * 256
    v = v_i8.astype(jnp.float32).astype(jnp.bfloat16)

    def dot(a):
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return dot(hi) * 256 + dot(lo)


def _splitmax_kernel(
    # scalar-prefetch
    scalars_ref,            # SMEM (4,) f32: [m_z, s_v, kv_valid_len, unused]
    # inputs
    q_ref,                  # (1, block_q, D) int8
    k_ref,                  # (1, block_k, D) int8
    v_ref,                  # (1, block_k, D) int8
    exp_ref,                # (8, 256) f32 — exp LUT on the lanes
    recip_ref,              # (8, 256) f32 — recip LUT on the lanes
    # outputs
    out_ref,                # (1, block_q, D) f32
    # scratch
    acc_ref,                # (block_q, D) f32
    s_ref,                  # (block_q, 128) f32 (col 0 used)
    *,
    cfg: LUTConfig,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    lut_mode: str,
    exact_recip: bool,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    m_z = scalars_ref[0]
    s_v = scalars_ref[1]
    kv_valid = scalars_ref[2].astype(jnp.int32)

    q_start = qi * block_q
    k_start = ki * block_k

    # --- causal / window tile-level liveness: skip dead tiles entirely ------
    live = jnp.asarray(True)
    if causal:
        # dead if every col > every row: k_start > q_start + block_q - 1
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window is not None:
        # dead if every col <= every row - window:
        # k_start + block_k - 1 <= (q_start) - window
        live = jnp.logical_and(live,
                               k_start + block_k - 1 > q_start - window)

    @pl.when(jnp.asarray(live))
    def _compute():
        # 1. the "CIM array": int8 MACs with int32 accumulation
        z32 = _qk_scores(q_ref[0], k_ref[0])                 # (bq, bk)
        # 2. 32b -> 8b quantization unit
        z_q = jnp.clip(jnp.round(z32.astype(jnp.float32) * m_z),
                       -128, 127).astype(jnp.int32)
        # 3. exp LUT
        if lut_mode == "onehot":
            e = _table_lookup(z_q + NEG_DOMAIN, exp_ref)     # exact, f32 ints
        else:  # "compute": arithmetic reconstruction, <=1 LSB off the table
            e = jnp.round(jnp.exp((z_q - 127).astype(jnp.float32)
                                  * cfg.scale_z)
                          * (1 << cfg.exp_frac_bits))
        # 4. masks (within-tile)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = cols < kv_valid
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        e = jnp.where(mask, e, 0.0)
        # 5. split accumulation
        acc_ref[...] += _exact_pv(e, v_ref[0])               # (bq, D)
        s_ref[:, :1] += jnp.sum(e, axis=1, keepdims=True)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        s = jnp.maximum(s_ref[:, :1], 1.0)                   # (bq, 1)
        if exact_recip:
            r = 1.0 / s
        else:
            r = _recip_lut_inline(s, recip_ref, cfg)
        out_ref[0] = acc_ref[...] * r * s_v


def _replicate_table(t: jax.Array) -> jax.Array:
    """(n <= 256,) int32 table -> (8, 256) f32 with the table on every row,
    zero-padded to two full vregs of lanes for :func:`_lane_gather`."""
    n = t.shape[0]
    assert n <= 2 * LANES, n
    row = jnp.pad(t.astype(jnp.float32), (0, 2 * LANES - n))
    return jnp.broadcast_to(row[None, :], (TABLE_SUBLANES, 2 * LANES))


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "causal", "window", "block_q", "block_k",
                     "lut_mode", "exact_recip", "interpret"))
def splitmax_attention_pallas(
    q_q: jax.Array,            # (B, Hq, Sq, D) int8
    k_q: jax.Array,            # (B, Hkv, Sk, D) int8
    v_q: jax.Array,            # (B, Hkv, Sk, D) int8
    m_z: jax.Array,            # scalar f32: s_q*s_k/(sqrt(D)*s_z)
    s_v: jax.Array,            # scalar f32
    kv_valid_len: jax.Array,   # scalar int32 (<= Sk; padding mask)
    exp_lut: jax.Array,        # (256,) int32
    recip_lut: jax.Array,      # (256,) int32
    *,
    cfg: LUTConfig,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, Hq, Sq, D) float32 attention output (dequantized).

    Any Sq/Sk: tiles shrink to the (32-row aligned) sequence when it is
    shorter than ``block_q``/``block_k``, the inputs are zero-padded to a
    whole number of tiles, padded keys sit past ``kv_valid_len`` and are
    masked, and padded query rows are sliced off the output.
    """
    b, hq, sq, d = q_q.shape
    _, hkv, sk, _ = k_q.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    block_q = min(block_q, _round_up(sq, 32))
    block_k = min(block_k, _round_up(sk, 32))
    sq_pad, sk_pad = _round_up(sq, block_q), _round_up(sk, block_k)
    if sq_pad != sq:
        q_q = jnp.pad(q_q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
    if sk_pad != sk:
        pad = ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0))
        k_q, v_q = jnp.pad(k_q, pad), jnp.pad(v_q, pad)
    nq, nk = sq_pad // block_q, sk_pad // block_k

    qf = q_q.reshape(b * hq, sq_pad, d)
    kf = k_q.reshape(b * hkv, sk_pad, d)
    vf = v_q.reshape(b * hkv, sk_pad, d)

    # NB: with PrefetchScalarGridSpec the index maps receive the scalar refs
    # as trailing arguments.
    def q_index(bh, qi, ki, *_):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki, *_):
        # map flattened q-head index -> flattened kv-head index (GQA)
        bidx = bh // hq
        hidx = bh % hq
        return (bidx * hkv + hidx // group, ki, 0)

    def out_index(bh, qi, ki, *_):
        return (bh, qi, 0)

    scalars = jnp.stack([
        jnp.asarray(m_z, jnp.float32),
        jnp.asarray(s_v, jnp.float32),
        jnp.asarray(kv_valid_len, jnp.float32),
        jnp.float32(0.0),
    ])

    kernel = functools.partial(
        _splitmax_kernel, cfg=cfg, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
        lut_mode=lut_mode, exact_recip=exact_recip)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((TABLE_SUBLANES, 2 * LANES), lambda *_: (0, 0)),
            pl.BlockSpec((TABLE_SUBLANES, 2 * LANES), lambda *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), out_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        name="splitmax_attention_pallas",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(scalars, qf, kf, vf, _replicate_table(exp_lut),
      _replicate_table(recip_lut))

    return out.reshape(b, hq, sq_pad, d)[:, :, :sq]
