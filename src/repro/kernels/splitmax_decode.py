"""Pallas TPU kernel: split-softmax *decode* (paper Eq. 3 streaming).

Decoder-only mapping in CIMple: the attention output for the new token n is

    softmax(Q_n K^T) V  =  ( sum_i E[z_i] V_i ) * RecipLUT( sum_i E[z_i] )

streamed over the cached K_i/V_i one block at a time — the split softmax means
each E[z_i].V_i partial product accumulates the moment z_i exists, which is
exactly how the silicon pipelines the decoder flow (green path, Fig. 1).

The GQA group of query heads sharing one KV head forms the sublane dimension
of the q tile.  On a dense cache one kernel instance serves a (batch,
kv-head) pair:

  grid = (B * Hkv, S_max / block_k)
  q    : (1, G_pad, D) int8 — or float32 on the *fused* entry points
  k/v  : (1, block_k, D) int8    (the int8 KV cache — CIMple stores K,V in
                                  the CIM array in int8)
  out  : (1, G_pad, D) f32

On a paged cache one grid step serves a slot and a chunk of ``pages`` table
entries, for all KV heads at once (``_chunk_pages`` sizes the chunk from the
shapes: 16 pages of 32 tokens at the served configurations):

  grid = (B, cdiv(max_blocks, pages))
  q    : (1, Hkv, G_pad, D)
  k/v  : pages x (1, Hkv, block_k, D) int8 — whole pool blocks, one each
  out  : (1, Hkv, G_pad, D) f32

Per-batch valid cache lengths arrive via scalar prefetch (SMEM), giving the
ragged masking a real serving system needs.

Fused datapath (``splitmax_decode_fused_pallas`` and the paged twin)
--------------------------------------------------------------------
The fused entry points take the *float* query and run the whole CIM datapath
— quantize -> QK^T -> 32b->8b requant -> exp-LUT split accumulation -> PV ->
reciprocal LUT — inside one kernel instance, with no HBM writes between
stages.  The absmax scale ``s_q`` rides in scalar prefetch; the int8 grid
snap happens once per instance (per slot on a paged cache) at its first
k-step into an int8 VMEM scratch tile, bit-identical to ``repro.core.quantization.quantize``
(same round + clip), so the fused path and the composed path (quantize op,
then the int8 kernel) agree to the bit.  This mirrors CIMple's dual-banked
macro, where scores never leave the array between QK^T and PV, and is the
repo's hottest serving kernel.

Tile shapes (``block_k`` and the sublane floor ``g_pad_min`` of the
accumulator) are selection knobs; :mod:`repro.kernels.autotune` owns the
per-(head_dim, seq_len) defaults and the sweep that overrides them.

Two cache layouts share the kernel math:

  * dense  — K/V per batch row are contiguous ``(B, Hkv, S_max, D)``; the
    k-tile index map is the identity walk ``ki -> ki``.
  * paged  — K/V live in a block pool ``(num_blocks, Hkv, block_k, D)`` and a
    per-slot block table ``(B, max_blocks)`` names each slot's tiles.  The
    BlockSpec index maps read a scalar-prefetched copy of the table, so the
    gather happens *inside the DMA engine* — contiguous K/V is never
    materialized in HBM, mirroring how the CIM array reads whichever bank
    the row decoder selects.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut import LUTConfig
from repro.kernels.splitmax_attn import (LANES, TABLE_SUBLANES, _exact_pv,
                                         _qk_scores, _recip_lut_inline,
                                         _replicate_table, _table_lookup)


def _table_spec() -> pl.BlockSpec:
    """BlockSpec of an (8, 256) f32 LUT ref, resident for the whole grid."""
    return pl.BlockSpec((TABLE_SUBLANES, 2 * LANES), lambda *_: (0, 0))


def _exp_scores(q, k, *, m_z, exp_ref, cfg: LUTConfig, lut_mode: str):
    """QK^T -> 32b->8b requant -> exp LUT: (R, D) x (C, D) int8 -> (R, C) f32
    integers, each element on its own."""
    z32 = _qk_scores(q, k)
    z_q = jnp.clip(jnp.round(z32.astype(jnp.float32) * m_z),
                   -128, 127).astype(jnp.int32)
    if lut_mode == "onehot":
        return _table_lookup(z_q + 128, exp_ref)
    return jnp.round(jnp.exp((z_q - 127).astype(jnp.float32) * cfg.scale_z)
                     * (1 << cfg.exp_frac_bits))


def _accumulate_tile(q, k, v, *, m_z, cache_len, k_start, window, windowed,
                     acc_ref, s_ref, exp_ref, cfg: LUTConfig, g_pad: int,
                     block_k: int, lut_mode: str):
    """One k-tile of the split-softmax accumulation (dense and verify).

    q (G_pad, D), k/v (block_k, D) int8 tiles; ``k_start`` is the tile's
    absolute position in the slot's logical sequence.
    """
    e = _exp_scores(q, k, m_z=m_z, exp_ref=exp_ref, cfg=cfg,
                    lut_mode=lut_mode)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                              (g_pad, block_k), 1)
    mask = cols < cache_len
    if windowed:
        mask &= cols > cache_len - 1 - window
    e = jnp.where(mask, e, 0.0)
    acc_ref[...] += _exact_pv(e, v)
    s_ref[:, :1] += jnp.sum(e, axis=1, keepdims=True)


def _normalize(acc, s, recip_ref, *, s_v, cfg: LUTConfig,
               exact_recip: bool):
    """Reciprocal-LUT epilogue: acc (G_pad, D) over s (G_pad, 1), times s_v."""
    s = jnp.maximum(s, 1.0)
    if exact_recip:
        r = 1.0 / s
    else:
        r = _recip_lut_inline(s, recip_ref, cfg)
    return acc * r * s_v


def _finalize_tile(out_ref, acc_ref, s_ref, recip_ref, *, s_v,
                   cfg: LUTConfig, exact_recip: bool):
    """Reciprocal-LUT epilogue, applied once at the last k-tile."""
    out_ref[0] = _normalize(acc_ref[...], s_ref[:, :1], recip_ref, s_v=s_v,
                            cfg=cfg, exact_recip=exact_recip)


def _quantize_q_tile(q_f32, s_q):
    """In-kernel stage 0 of the fused datapath: fp q tile -> int8 grid.

    Bit-identical to :func:`repro.core.quantization.quantize` (round to
    nearest even, saturate), held as int8 because that is what the MXU
    matmul consumes.
    """
    return jnp.clip(jnp.round(q_f32.astype(jnp.float32) / s_q),
                    -128, 127).astype(jnp.int32).astype(jnp.int8)


def _decode_kernel(
    # scalar prefetch
    lens_ref,               # SMEM (B,) int32 — valid cache length per batch
    scalars_ref,            # SMEM (2,) f32 — [s_v, window]
    mz_ref,                 # SMEM (B,) f32 — per-slot requant multiplier
    sq_ref,                 # SMEM (B,) f32 — per-slot q absmax scale (fused)
    # inputs
    q_ref,                  # (1, G_pad, D) int8 (composed) / f32 (fused)
    k_ref,                  # (1, block_k, D) int8
    v_ref,                  # (1, block_k, D) int8
    exp_ref, recip_ref,     # (8, 256) f32
    # output
    out_ref,                # (1, G_pad, D) f32
    # scratch
    acc_ref,                # (G_pad, D) f32
    s_ref,                  # (G_pad, 128) f32
    *extra_scratch,         # fused only: (G_pad, D) int8 quantized q
    cfg: LUTConfig,
    hkv: int,
    block_k: int,
    num_k_blocks: int,
    g_pad: int,
    windowed: bool,
    lut_mode: str,
    exact_recip: bool,
    fused: bool,
):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    b = bh // hkv
    qq_ref = extra_scratch[0] if fused else None

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        if fused:
            # quantize once per instance; every k-tile reuses the VMEM copy
            qq_ref[...] = _quantize_q_tile(q_ref[0], sq_ref[b])

    m_z = mz_ref[b]
    s_v = scalars_ref[0]
    window = scalars_ref[1].astype(jnp.int32)
    cache_len = lens_ref[b]
    k_start = ki * block_k

    live = k_start < cache_len
    if windowed:
        live = jnp.logical_and(live,
                               k_start + block_k - 1 >= cache_len - window)

    @pl.when(live)
    def _compute():
        q = qq_ref[...] if fused else q_ref[0]
        _accumulate_tile(
            q, k_ref[0], v_ref[0],
            m_z=m_z, cache_len=cache_len, k_start=k_start, window=window,
            windowed=windowed, acc_ref=acc_ref, s_ref=s_ref, exp_ref=exp_ref,
            cfg=cfg, g_pad=g_pad, block_k=block_k, lut_mode=lut_mode)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        _finalize_tile(out_ref, acc_ref, s_ref, recip_ref, s_v=s_v,
                       cfg=cfg, exact_recip=exact_recip)


def _paged_decode_kernel(
    # scalar prefetch
    lens_ref,               # SMEM (B,) int32 — valid length per slot
    fetch_ref,              # SMEM (B, n_chunks * pages) int32 — see _fetch_table
    scalars_ref,            # SMEM (2,) f32 — [s_v, window]
    mz_ref,                 # SMEM (B,) f32 — per-slot requant multiplier
    sq_ref,                 # SMEM (B,) f32 — per-slot q absmax scale (fused)
    # inputs
    q_ref,                  # (1, Hkv, G_pad, D) int8 (composed) / f32 (fused)
    *refs,                  # pages x k, pages x v: (1, Hkv, block_k, D) int8
                            # pool blocks; exp, recip LUTs (8, 256) f32;
                            # out (1, Hkv, G_pad, D) f32; scratch: acc
                            # (Hkv, G_pad, D) f32, s (Hkv, G_pad, 128) f32,
                            # fused only: (Hkv, G_pad, D) int8 quantized q
    cfg: LUTConfig,
    hkv: int,
    block_k: int,
    max_blocks: int,
    pages: int,
    group_pages: int,
    g_pad: int,
    windowed: bool,
    lut_mode: str,
    exact_recip: bool,
    fused: bool,
):
    """Block-table decode of one slot's chunk of ``pages`` table entries, all
    KV heads at once.

    Each of the chunk's pages is one input whose index map reads the fetch
    table, so the pipeline moves whole pool blocks (every head of a page),
    one DMA per page, for the next step while this one computes.  Pages
    before the window or at or past ``cdiv(cache_len, block_k)`` are never
    fetched (:func:`_fetch_table`), and a score tile without a live page is
    skipped.

    ``group_pages`` pages span one 128-lane score tile: QK^T, requant, LUT
    and mask run on the tile, and a block-diagonal copy of its LUT values
    (page p's columns on rows [p*G_pad, (p+1)*G_pad)) makes one MXU product
    give each page's ``_exact_pv`` partial on its own rows.  The partials
    are added into ``acc``/``s`` one page at a time, in table order: the
    additions, and their order, of one page per grid step, so the output is
    bitwise the dense kernel's at ``block_k``.
    """
    del fetch_ref  # consumed by the index maps
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    exp_ref, recip_ref, out_ref, acc_ref, s_ref, *extra = refs[2 * pages:]
    qq_ref = extra[0] if fused else None
    b = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        if fused:
            qq_ref[...] = _quantize_q_tile(q_ref[0], sq_ref[b])

    m_z = mz_ref[b]
    window = scalars_ref[1].astype(jnp.int32)
    cache_len = lens_ref[b]
    lo, hi = _live_pages(cache_len, window if windowed else None, block_k,
                         max_blocks)
    # pages past the table's end never count, whatever the length says
    limit = jnp.minimum(cache_len, max_blocks * block_k)
    # a few heads' independent chains per loop iteration keep the MXU busy;
    # unrolling all of them makes the kernel slow to lower
    heads_per_iter = math.gcd(hkv, _HEADS_PER_ITER)
    rows = group_pages * g_pad
    width = group_pages * block_k
    # row r of a block-diagonal tile keeps page r // G_pad's columns
    page_start = jax.lax.broadcasted_iota(
        jnp.int32, (group_pages, g_pad, width), 0) * block_k
    col = jax.lax.broadcasted_iota(jnp.int32, (group_pages, g_pad, width), 2)
    diag = jnp.logical_and(col >= page_start, col < page_start + block_k)

    def block_diag(x):
        x = jnp.broadcast_to(x[None], (group_pages, g_pad, width))
        return jnp.where(diag, x, 0.0).reshape(rows, width)

    for p0 in range(0, pages, group_pages):
        first = c * pages + p0                    # table entry of the tile

        # a dead page in a live tile is masked to zero, and adding its zero
        # partial leaves acc and s as they were
        @pl.when(jnp.logical_and(first < hi, first + group_pages > lo))
        def _tile():
            cols = first * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (g_pad, width), 1)
            mask = cols < limit
            if windowed:
                mask &= cols > cache_len - 1 - window

            def head(h):
                q = qq_ref[h] if fused else q_ref[0, h]
                k = jnp.concatenate(
                    [k_refs[p0 + p][0, h] for p in range(group_pages)])
                v = jnp.concatenate(
                    [v_refs[p0 + p][0, h] for p in range(group_pages)])
                e = jnp.where(mask, _exp_scores(q, k, m_z=m_z,
                                                exp_ref=exp_ref, cfg=cfg,
                                                lut_mode=lut_mode), 0.0)
                # _exact_pv's split, one page per row block
                e_hi = jnp.floor(e * (1.0 / 256))
                e_lo = e - e_hi * 256
                lhs = jnp.concatenate([block_diag(e_hi), block_diag(e_lo)])
                pv = jax.lax.dot_general(
                    lhs.astype(jnp.bfloat16),
                    v.astype(jnp.float32).astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                part = pv[:rows] * 256 + pv[rows:]
                e_sum = jnp.sum(block_diag(e), axis=1, keepdims=True)
                acc = acc_ref[h]
                s = s_ref[h]
                for p in range(group_pages):
                    acc = acc + part[p * g_pad:(p + 1) * g_pad]
                    s = s + e_sum[p * g_pad:(p + 1) * g_pad]
                acc_ref[h] = acc
                s_ref[h] = s

            def heads(i, carry):
                for u in range(heads_per_iter):
                    head(i * heads_per_iter + u)
                return carry

            jax.lax.fori_loop(0, hkv // heads_per_iter, heads, 0)

    @pl.when(c == pl.num_programs(1) - 1)
    def _finalize():
        s_v = scalars_ref[0]
        for h in range(hkv):
            out_ref[0, h] = _normalize(acc_ref[h], s_ref[h][:, :1], recip_ref,
                                       s_v=s_v, cfg=cfg,
                                       exact_recip=exact_recip)


# ---------------------------------------------------------------------------
# speculative verify kernels: gamma draft queries in one launch
# ---------------------------------------------------------------------------

def _per_row(values_ref, b, t_tokens: int, g_pad: int, dtype):
    """(B, T) SMEM array -> slot b's (T*g_pad, 1) per-row column, t-major.

    The verify kernels fold the gamma draft tokens onto the sublane dim
    (row r belongs to token ``r // g_pad``), so per-(slot, token) scalars
    (requant multiplier, quantization scale, causal length offsets) become
    per-row broadcast columns.  T is static and tiny, so the unrolled
    concat is cheap and keeps SMEM indexing static.
    """
    return jnp.concatenate(
        [jnp.full((g_pad, 1), values_ref[b, t], dtype)
         for t in range(t_tokens)],
        axis=0)


def _verify_body(lens_ref, scalars_ref, mz_ref, sq_ref, q_ref, k_ref, v_ref,
                 exp_ref, recip_ref, out_ref, acc_ref, s_ref, qq_ref, *,
                 cfg: LUTConfig, hkv: int, block_k: int, num_k_blocks: int,
                 g_pad: int, t_tokens: int, windowed: bool, lut_mode: str,
                 exact_recip: bool, k_tile, v_tile):
    """Shared dense/paged verify-kernel body.

    One instance serves a (batch, kv-head) pair for all ``t_tokens`` draft
    queries at once: the q tile is (T*g_pad, D) with token t on rows
    [t*g_pad, (t+1)*g_pad).  Query t may only see cache positions
    ``< cache_len - (T-1-t)`` — its own K/V entry is the newest it attends
    to — which is exactly the sequential decode's visibility at step t, so
    each row bit-matches the one-token kernel on its effective length.
    """
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    b = bh // hkv

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        # quantize all gamma queries once per instance, each row with its
        # own slot's per-token absmax scale (the sequential path calibrates
        # per slot per step)
        qq_ref[...] = _quantize_q_tile(
            q_ref[0], _per_row(sq_ref, b, t_tokens, g_pad, jnp.float32))

    s_v = scalars_ref[0]
    window = scalars_ref[1].astype(jnp.int32)
    cache_len = lens_ref[b]
    k_start = ki * block_k
    rows = t_tokens * g_pad

    # per-row effective length: token t sees cache_len - (T-1-t) positions
    t_of_row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // g_pad
    eff = cache_len - (t_tokens - 1) + t_of_row

    live = k_start < cache_len          # max effective length (t = T-1)
    if windowed:
        # min effective length (t = 0) bounds the window's left edge
        live = jnp.logical_and(
            live,
            k_start + block_k - 1 >= cache_len - (t_tokens - 1) - window)

    @pl.when(live)
    def _compute():
        _accumulate_tile(
            qq_ref[...], k_tile(k_ref), v_tile(v_ref),
            m_z=_per_row(mz_ref, b, t_tokens, g_pad, jnp.float32),
            cache_len=eff, k_start=k_start, window=window, windowed=windowed,
            acc_ref=acc_ref, s_ref=s_ref, exp_ref=exp_ref, cfg=cfg,
            g_pad=rows, block_k=block_k, lut_mode=lut_mode)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        _finalize_tile(out_ref, acc_ref, s_ref, recip_ref, s_v=s_v,
                       cfg=cfg, exact_recip=exact_recip)


def _verify_kernel(lens_ref, scalars_ref, mz_ref, sq_ref, *refs, **kw):
    return _verify_body(lens_ref, scalars_ref, mz_ref, sq_ref, *refs,
                        k_tile=lambda r: r[0], v_tile=lambda r: r[0], **kw)


def _paged_verify_kernel(lens_ref, table_ref, scalars_ref, mz_ref, sq_ref,
                         *refs, **kw):
    del table_ref  # consumed by the index maps, not the body
    return _verify_body(lens_ref, scalars_ref, mz_ref, sq_ref, *refs,
                        k_tile=lambda r: r[0, 0], v_tile=lambda r: r[0, 0],
                        **kw)


# ---------------------------------------------------------------------------
# launchers (shared between composed int8 entry and fused fp entry)
# ---------------------------------------------------------------------------

def _pad_q_groups(q, hkv: int, g_pad: int):
    """(B, Hq, D) -> (B*Hkv, G_pad, D): GQA groups on the sublane dim."""
    b, hq, d = q.shape
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d)
    if g_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    return qg.reshape(b * hkv, g_pad, d)


def _sv_window_scalars(s_v, window):
    return jnp.stack([
        jnp.asarray(s_v, jnp.float32),
        jnp.asarray(window if window is not None else 0, jnp.float32),
    ])


def _per_slot(v, b: int):
    """Scalar / (1,) / (B,) -> (B,) f32 scalar-prefetch vector.

    Serving calibrates ``s_q`` (hence ``m_z``) per slot so one slot's
    quantization grid never depends on its batch neighbours; scalar callers
    broadcast to identical per-slot values, bit-matching the old scalar
    prefetch.
    """
    if v is None:
        return jnp.zeros((b,), jnp.float32)
    v = jnp.asarray(v, jnp.float32).reshape(-1)
    return jnp.broadcast_to(v, (b,))


def _per_slot_token(v, b: int, t: int):
    """Scalar / (T,) / (B, T) -> (B, T) f32 for the verify kernels."""
    v = jnp.asarray(v, jnp.float32)
    if v.ndim < 2:
        v = v.reshape(1, -1)
    return jnp.broadcast_to(v, (b, t))


def _dense_decode_call(q, k_cache, v_cache, m_z, s_q, s_v, cache_len,
                       exp_lut, recip_lut, *, cfg, window, block_k, g_pad_min,
                       lut_mode, exact_recip, interpret, fused):
    b, hq, d = q.shape
    _, hkv, s_max, _ = k_cache.shape
    group = hq // hkv
    g_pad = max(g_pad_min, 8, group)          # sublane-align the q tile
    assert s_max % block_k == 0, (s_max, block_k)
    nk = s_max // block_k

    if fused:
        q = q.astype(jnp.float32)
    qf = _pad_q_groups(q, hkv, g_pad)
    kf = k_cache.reshape(b * hkv, s_max, d)
    vf = v_cache.reshape(b * hkv, s_max, d)

    kernel = functools.partial(
        _decode_kernel, cfg=cfg, hkv=hkv, block_k=block_k, num_k_blocks=nk,
        g_pad=g_pad, windowed=window is not None, lut_mode=lut_mode,
        exact_recip=exact_recip, fused=fused)

    scratch = [
        pltpu.VMEM((g_pad, d), jnp.float32),
        pltpu.VMEM((g_pad, 128), jnp.float32),
    ]
    if fused:
        scratch.append(pltpu.VMEM((g_pad, d), jnp.int8))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b * hkv, nk),
        in_specs=[
            pl.BlockSpec((1, g_pad, d), lambda bh, ki, *_: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, *_: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, *_: (bh, ki, 0)),
            _table_spec(),
            _table_spec(),
        ],
        out_specs=pl.BlockSpec((1, g_pad, d), lambda bh, ki, *_: (bh, 0, 0)),
        scratch_shapes=scratch,
    )

    out = pl.pallas_call(
        kernel,
        name=("splitmax_decode_fused_pallas" if fused
              else "splitmax_decode_pallas"),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), _sv_window_scalars(s_v, window),
      _per_slot(m_z, b), _per_slot(s_q, b),
      qf, kf, vf, _replicate_table(exp_lut), _replicate_table(recip_lut))

    out = out.reshape(b, hkv, g_pad, d)[:, :, :group, :]
    return out.reshape(b, hq, d)


_HEADS_PER_ITER = 4
_CHUNK_VMEM_BYTES = 4 << 20   # both buffers of a chunk's K and V pages


def _chunk_pages(hkv: int, block_k: int, d: int, max_blocks: int):
    """Table entries per paged-decode grid step, and pages per score tile.

    A score tile is 128 lanes, so it spans ``128 // block_k`` pages (one page
    if the page is wider).  A chunk is a whole number of tiles, at most 512
    positions, at most what the table holds, and its K and V pages, double
    buffered, fit ``_CHUNK_VMEM_BYTES`` of VMEM (int8 tiles are (32, 128)).
    """
    group = max(1, LANES // block_k)
    page = hkv * -(-block_k // 32) * 32 * -(-d // LANES) * LANES
    pages = min(_CHUNK_VMEM_BYTES // (4 * page), max(1, 512 // block_k),
                -(-max_blocks // group) * group)
    return max(group, pages // group * group), group


def _live_pages(cache_len, window, block_k: int, max_blocks: int):
    """Table entries [lo, hi) that hold a position the query attends to."""
    hi = jnp.minimum((cache_len + block_k - 1) // block_k, max_blocks)
    if window is None:
        return jnp.zeros_like(hi), hi
    return jnp.maximum(cache_len - window, 0) // block_k, hi


def _fetch_table(block_table, cache_len, window, block_k: int, pages: int,
                 n_chunks: int):
    """(B, max_blocks) block table -> (B, n_chunks * pages) pool block that
    input ``j % pages`` holds at grid step (b, j // pages).

    A live entry names its own block.  A dead one names a block the input
    holds anyway, so the pipeline, which copies only when an input's block
    changes, never fetches it: before the window, the first live block of
    the same input; past the end, its last; in a slot where the input has no
    live page, the block it kept from the slots before.
    """
    b, max_blocks = block_table.shape
    lens = cache_len.astype(jnp.int32)[:, None]
    lo, hi = _live_pages(lens, window, block_k, max_blocks)
    j = jnp.arange(n_chunks * pages, dtype=jnp.int32)[None, :]
    i = j % pages
    first = lo + (i - lo) % pages             # first live entry of input i
    last = hi - 1 - (hi - 1 - i) % pages      # its last
    src = jnp.where(j < lo, first, jnp.where(j >= hi, last, j))
    fetch = jnp.take_along_axis(block_table.astype(jnp.int32),
                                jnp.clip(src, 0, max_blocks - 1), axis=1)
    has = first < hi                          # (B, n) per input, repeated
    held = fetch[:, -pages:]                  # each input's block at the end
    owner = jax.lax.cummax(
        jnp.where(has[:, :pages], jnp.arange(b)[:, None], -1), axis=0)
    kept = jnp.where(owner >= 0,
                     jnp.take_along_axis(held, jnp.maximum(owner, 0), axis=0),
                     0)
    return jnp.where(has, fetch, jnp.tile(kept, (1, n_chunks)))


def _paged_decode_call(q, k_pages, v_pages, block_table, m_z, s_q, s_v,
                       cache_len, exp_lut, recip_lut, *, cfg, window,
                       g_pad_min, lut_mode, exact_recip, interpret, fused):
    b, hq, d = q.shape
    _, hkv, block_k, _ = k_pages.shape
    _, max_blocks = block_table.shape
    group = hq // hkv
    g_pad = -(-max(g_pad_min, 8, group) // 8) * 8   # whole f32 sublane tiles
    pages, group_pages = _chunk_pages(hkv, block_k, d, max_blocks)
    n_chunks = -(-max_blocks // pages)

    if fused:
        q = q.astype(jnp.float32)
    qf = _pad_q_groups(q, hkv, g_pad).reshape(b, hkv, g_pad, d)
    fetch = _fetch_table(block_table, cache_len, window, block_k, pages,
                         n_chunks)

    kernel = functools.partial(
        _paged_decode_kernel, cfg=cfg, hkv=hkv, block_k=block_k,
        max_blocks=max_blocks, pages=pages, group_pages=group_pages,
        g_pad=g_pad, windowed=window is not None, lut_mode=lut_mode,
        exact_recip=exact_recip, fused=fused)

    def page_spec(p):
        return pl.BlockSpec(
            (1, hkv, block_k, d),
            lambda bi, ci, lens_ref, fetch_ref, *_:
                (fetch_ref[bi, ci * pages + p], 0, 0, 0))

    scratch = [
        pltpu.VMEM((hkv, g_pad, d), jnp.float32),
        pltpu.VMEM((hkv, g_pad, 128), jnp.float32),
    ]
    if fused:
        scratch.append(pltpu.VMEM((hkv, g_pad, d), jnp.int8))

    slot_block = pl.BlockSpec((1, hkv, g_pad, d),
                              lambda bi, ci, *_: (bi, 0, 0, 0))
    page_specs = [page_spec(p) for p in range(pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, n_chunks),
        in_specs=[slot_block, *page_specs, *page_specs, _table_spec(),
                  _table_spec()],
        out_specs=slot_block,
        scratch_shapes=scratch,
    )

    out = pl.pallas_call(
        kernel,
        name=("splitmax_decode_fused_paged_pallas" if fused
              else "splitmax_decode_paged_pallas"),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), fetch, _sv_window_scalars(s_v, window),
      _per_slot(m_z, b), _per_slot(s_q, b), qf,
      *([k_pages] * pages), *([v_pages] * pages),
      _replicate_table(exp_lut), _replicate_table(recip_lut))

    out = out[:, :, :group, :]
    return out.reshape(b, hq, d)


def _pad_verify_q(q, hkv: int, g_pad: int):
    """(B, Hq, T, D) -> (B*Hkv, T*g_pad, D), token-major rows."""
    b, hq, t, d = q.shape
    group = hq // hkv
    qg = q.reshape(b, hkv, group, t, d).transpose(0, 1, 3, 2, 4)
    if g_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, g_pad - group),
                          (0, 0)))
    return qg.reshape(b * hkv, t * g_pad, d)


def _unpad_verify_out(out, b: int, hkv: int, group: int, t: int,
                      g_pad: int, d: int):
    out = out.reshape(b, hkv, t, g_pad, d)[:, :, :, :group, :]
    return out.transpose(0, 1, 3, 2, 4).reshape(b, hkv * group, t, d)


def _dense_verify_call(q, k_cache, v_cache, m_z, s_q, s_v, cache_len,
                       exp_lut, recip_lut, *, cfg, window, block_k,
                       g_pad_min, lut_mode, exact_recip, interpret):
    b, hq, t, d = q.shape
    _, hkv, s_max, _ = k_cache.shape
    group = hq // hkv
    g_pad = max(g_pad_min, 8, group)
    assert s_max % block_k == 0, (s_max, block_k)
    nk = s_max // block_k

    qf = _pad_verify_q(q.astype(jnp.float32), hkv, g_pad)
    kf = k_cache.reshape(b * hkv, s_max, d)
    vf = v_cache.reshape(b * hkv, s_max, d)
    rows = t * g_pad

    kernel = functools.partial(
        _verify_kernel, cfg=cfg, hkv=hkv, block_k=block_k, num_k_blocks=nk,
        g_pad=g_pad, t_tokens=t, windowed=window is not None,
        lut_mode=lut_mode, exact_recip=exact_recip)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b * hkv, nk),
        in_specs=[
            pl.BlockSpec((1, rows, d), lambda bh, ki, *_: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, *_: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, *_: (bh, ki, 0)),
            _table_spec(),
            _table_spec(),
        ],
        out_specs=pl.BlockSpec((1, rows, d), lambda bh, ki, *_: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.int8),
        ],
    )

    out = pl.pallas_call(
        kernel,
        name="splitmax_decode_fused_verify_pallas",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), _sv_window_scalars(s_v, window),
      _per_slot_token(m_z, b, t), _per_slot_token(s_q, b, t),
      qf, kf, vf, _replicate_table(exp_lut), _replicate_table(recip_lut))

    return _unpad_verify_out(out, b, hkv, group, t, g_pad, d)


def _paged_verify_call(q, k_pages, v_pages, block_table, m_z, s_q, s_v,
                       cache_len, exp_lut, recip_lut, *, cfg, window,
                       g_pad_min, lut_mode, exact_recip, interpret):
    b, hq, t, d = q.shape
    num_blocks, hkv, block_k, _ = k_pages.shape
    _, max_blocks = block_table.shape
    group = hq // hkv
    g_pad = max(g_pad_min, 8, group)

    qf = _pad_verify_q(q.astype(jnp.float32), hkv, g_pad)
    rows = t * g_pad

    kernel = functools.partial(
        _paged_verify_kernel, cfg=cfg, hkv=hkv, block_k=block_k,
        num_k_blocks=max_blocks, g_pad=g_pad, t_tokens=t,
        windowed=window is not None, lut_mode=lut_mode,
        exact_recip=exact_recip)

    def kv_index(bh, ki, lens_ref, table_ref, *_):
        del lens_ref
        return (table_ref[bh // hkv, ki], bh % hkv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b * hkv, max_blocks),
        in_specs=[
            pl.BlockSpec((1, rows, d), lambda bh, ki, *_: (bh, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            _table_spec(),
            _table_spec(),
        ],
        out_specs=pl.BlockSpec((1, rows, d), lambda bh, ki, *_: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.int8),
        ],
    )

    out = pl.pallas_call(
        kernel,
        name="splitmax_decode_fused_verify_paged_pallas",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), block_table.astype(jnp.int32),
      _sv_window_scalars(s_v, window), _per_slot_token(m_z, b, t),
      _per_slot_token(s_q, b, t), qf, k_pages, v_pages,
      _replicate_table(exp_lut), _replicate_table(recip_lut))

    return _unpad_verify_out(out, b, hkv, group, t, g_pad, d)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("cfg", "window", "block_k", "g_pad_min", "lut_mode",
                     "exact_recip", "interpret"))
def splitmax_decode_pallas(
    q_q: jax.Array,            # (B, Hq, D) int8 — one new token
    k_cache: jax.Array,        # (B, Hkv, S_max, D) int8
    v_cache: jax.Array,        # (B, Hkv, S_max, D) int8
    m_z: jax.Array,            # scalar or (B,) f32 — per-slot requant mult
    s_v: jax.Array,            # scalar f32
    cache_len: jax.Array,      # (B,) int32 — valid entries incl. current token
    exp_lut: jax.Array,        # (256,) int32
    recip_lut: jax.Array,      # (256,) int32
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    block_k: int = 128,
    g_pad_min: int = 8,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Composed entry: pre-quantized int8 q.  Returns (B, Hq, D) float32."""
    return _dense_decode_call(
        q_q, k_cache, v_cache, m_z, None, s_v, cache_len, exp_lut, recip_lut,
        cfg=cfg, window=window, block_k=block_k, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip, interpret=interpret,
        fused=False)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "window", "block_k", "g_pad_min", "lut_mode",
                     "exact_recip", "interpret"))
def splitmax_decode_fused_pallas(
    q: jax.Array,              # (B, Hq, D) float — one new token, UNquantized
    k_cache: jax.Array,        # (B, Hkv, S_max, D) int8
    v_cache: jax.Array,        # (B, Hkv, S_max, D) int8
    m_z: jax.Array,            # scalar or (B,) f32 — per-slot requant mult
    s_q: jax.Array,            # scalar or (B,) f32 — q absmax scale
    s_v: jax.Array,            # scalar f32
    cache_len: jax.Array,      # (B,) int32 — valid entries incl. current token
    exp_lut: jax.Array,        # (256,) int32
    recip_lut: jax.Array,      # (256,) int32
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    block_k: int = 128,
    g_pad_min: int = 8,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Fused entry: quantize -> QK^T -> LUT split-softmax -> PV in one kernel.

    Takes the *float* query; ``s_q`` (absmax scale, a scalar reduction done by
    the caller) rides in scalar prefetch and the int8 snap happens in VMEM at
    ``ki == 0`` — no quantized-q HBM round-trip.  Bit-matches
    ``quantize(q, s_q)`` + :func:`splitmax_decode_pallas` by construction.
    """
    return _dense_decode_call(
        q, k_cache, v_cache, m_z, s_q, s_v, cache_len, exp_lut, recip_lut,
        cfg=cfg, window=window, block_k=block_k, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip, interpret=interpret,
        fused=True)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "window", "g_pad_min", "lut_mode", "exact_recip",
                     "interpret"))
def splitmax_decode_paged_pallas(
    q_q: jax.Array,            # (B, Hq, D) int8 — one new token per slot
    k_pages: jax.Array,        # (num_blocks, Hkv, block_k, D) int8 pool
    v_pages: jax.Array,        # (num_blocks, Hkv, block_k, D) int8 pool
    block_table: jax.Array,    # (B, max_blocks) int32 — per-slot block ids
    m_z: jax.Array,            # scalar or (B,) f32 — per-slot requant mult
    s_v: jax.Array,            # scalar f32
    cache_len: jax.Array,      # (B,) int32 — valid entries incl. current token
    exp_lut: jax.Array,        # (256,) int32
    recip_lut: jax.Array,      # (256,) int32
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    g_pad_min: int = 8,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Composed paged entry — decode attention gathered through the block
    table.

    The per-slot block indices ride in scalar prefetch next to ``lens_ref``;
    the K/V BlockSpec index maps read them, so each grid step DMAs exactly
    the pool blocks the table names for its chunk: whole blocks, every KV
    head of a page in one copy.
    """
    return _paged_decode_call(
        q_q, k_pages, v_pages, block_table, m_z, None, s_v, cache_len,
        exp_lut, recip_lut, cfg=cfg, window=window, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip, interpret=interpret,
        fused=False)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "window", "g_pad_min", "lut_mode", "exact_recip",
                     "interpret"))
def splitmax_decode_fused_paged_pallas(
    q: jax.Array,              # (B, Hq, D) float — one new token, UNquantized
    k_pages: jax.Array,        # (num_blocks, Hkv, block_k, D) int8 pool
    v_pages: jax.Array,        # (num_blocks, Hkv, block_k, D) int8 pool
    block_table: jax.Array,    # (B, max_blocks) int32 — per-slot block ids
    m_z: jax.Array,            # scalar or (B,) f32 — per-slot requant mult
    s_q: jax.Array,            # scalar or (B,) f32 — q absmax scale
    s_v: jax.Array,            # scalar f32
    cache_len: jax.Array,      # (B,) int32 — valid entries incl. current token
    exp_lut: jax.Array,        # (256,) int32
    recip_lut: jax.Array,      # (256,) int32
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    g_pad_min: int = 8,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Fused paged entry: in-kernel q quantization + block-table gather —
    the full serving datapath (fp activations vs the paged int8 pool) in one
    kernel launch."""
    return _paged_decode_call(
        q, k_pages, v_pages, block_table, m_z, s_q, s_v, cache_len,
        exp_lut, recip_lut, cfg=cfg, window=window, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip, interpret=interpret,
        fused=True)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "window", "block_k", "g_pad_min", "lut_mode",
                     "exact_recip", "interpret"))
def splitmax_decode_fused_verify_pallas(
    q: jax.Array,              # (B, Hq, T, D) float — gamma draft queries
    k_cache: jax.Array,        # (B, Hkv, S_max, D) int8 — incl. the T tokens
    v_cache: jax.Array,        # (B, Hkv, S_max, D) int8
    m_z: jax.Array,            # (T,) or (B,T) f32 — per-token requant mults
    s_q: jax.Array,            # (T,) or (B,T) f32 — per-token q scales
    s_v: jax.Array,            # scalar f32
    cache_len: jax.Array,      # (B,) int32 — length incl. ALL T verify tokens
    exp_lut: jax.Array,        # (256,) int32
    recip_lut: jax.Array,      # (256,) int32
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    block_k: int = 128,
    g_pad_min: int = 8,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Speculative-verify entry: gamma draft tokens in ONE kernel launch.

    The caller appends all T draft K/V entries to the cache first;
    ``cache_len`` counts them.  Query t attends to ``cache_len - (T-1-t)``
    positions — its own entry and everything older — via a per-row causal
    mask, so every row reproduces the sequential one-token kernel bit for
    bit.  The gamma queries are quantized once per (batch, kv-head) grid
    instance (per-token scales ride scalar prefetch); K/V tiles stream
    through the LUT split-softmax exactly once for all gamma outputs — no
    per-token re-launch, no HBM intermediates.  Returns (B, Hq, T, D) f32.
    """
    return _dense_verify_call(
        q, k_cache, v_cache, m_z, s_q, s_v, cache_len, exp_lut, recip_lut,
        cfg=cfg, window=window, block_k=block_k, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "window", "g_pad_min", "lut_mode", "exact_recip",
                     "interpret"))
def splitmax_decode_fused_verify_paged_pallas(
    q: jax.Array,              # (B, Hq, T, D) float — gamma draft queries
    k_pages: jax.Array,        # (num_blocks, Hkv, block_k, D) int8 pool
    v_pages: jax.Array,        # (num_blocks, Hkv, block_k, D) int8 pool
    block_table: jax.Array,    # (B, max_blocks) int32
    m_z: jax.Array,            # (T,) or (B,T) f32
    s_q: jax.Array,            # (T,) or (B,T) f32
    s_v: jax.Array,            # scalar f32
    cache_len: jax.Array,      # (B,) int32 — length incl. ALL T verify tokens
    exp_lut: jax.Array,        # (256,) int32
    recip_lut: jax.Array,      # (256,) int32
    *,
    cfg: LUTConfig,
    window: Optional[int] = None,
    g_pad_min: int = 8,
    lut_mode: str = "onehot",
    exact_recip: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Paged speculative-verify entry: the verify kernel above, with K/V
    tiles (the cached history *and* the in-flight draft tokens' blocks)
    gathered through the block table by the BlockSpec index map.  One
    launch serves all gamma draft queries of every slot."""
    return _paged_verify_call(
        q, k_pages, v_pages, block_table, m_z, s_q, s_v, cache_len,
        exp_lut, recip_lut, cfg=cfg, window=window, g_pad_min=g_pad_min,
        lut_mode=lut_mode, exact_recip=exact_recip, interpret=interpret)
