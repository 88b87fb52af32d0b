"""Pallas TPU kernel: append one token's int8 K/V to the paged pool in place.

The decode step writes each slot's new K and V row into the slot's tail page
(``pages[blk[b], :, off[b], :]``) before attention reads the pool.  Written
as an XLA scatter, that one row costs a copy of the whole pool: for the
scatter XLA lays the pool out with each page's heads inside its rows
(minor-to-major ``{3,1,2,0}``), the decode kernel takes it row-major, and
XLA converts every page between the two.  This kernel takes the pool as an
operand aliased to its output (``input_output_aliases``), so the only bytes
it moves are the rows' own neighbourhoods.

Grid: one step per slot ("arbitrary").  A step's pool block is the aligned
group of ``rows`` page rows, over every KV head, that holds the slot's new
row (the pool's int8 tiling is 8 rows: Mosaic refuses a block of fewer); its
index map reads the scalar-prefetched block id and offset, so the pipeline
fetches the next slot's groups while this one's are written.  The step
selects the new row into the group and writes the group back.  Retired
slots point at the trash block and may share a group, so their rows there
land in no defined order; nobody reads it.  Two live slots never share a
page, so no live group is written twice.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 8                      # int8 pool tiling along the page's rows


def _append_kernel(blk_ref, off_ref, k_new_ref, v_new_ref, k_in_ref,
                   v_in_ref, k_out_ref, v_out_ref, *, rows: int):
    del blk_ref                  # consumed by the index maps
    row = jax.lax.broadcasted_iota(jnp.int32, k_in_ref.shape[1:], 1)
    hit = row == off_ref[pl.program_id(0)] % rows
    k_out_ref[0] = jnp.where(hit, k_new_ref[0], k_in_ref[0])
    v_out_ref[0] = jnp.where(hit, v_new_ref[0], v_in_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_append_pallas(
    k_pages: jax.Array,        # (num_blocks, Hkv, block_k, D) int8 pool
    v_pages: jax.Array,        # (num_blocks, Hkv, block_k, D) int8 pool
    blk: jax.Array,            # (B,) int32 — each slot's tail block
    off: jax.Array,            # (B,) int32 — the new row within it
    k_new: jax.Array,          # (B, Hkv, D) int8
    v_new: jax.Array,          # (B, Hkv, D) int8
    *,
    interpret: bool = False,
):
    """Both pools with ``pages[blk[b], :, off[b], :]`` set to slot ``b``'s
    new row, written in place."""
    _, hkv, block_k, d = k_pages.shape
    b = blk.shape[0]
    rows = math.gcd(_ROWS, block_k)
    pool_spec = pl.BlockSpec(
        (1, hkv, rows, d),
        lambda s, blk_ref, off_ref: (blk_ref[s], 0, off_ref[s] // rows, 0))
    new_spec = pl.BlockSpec((1, hkv, 1, d), lambda s, *_: (s, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_append_kernel, rows=rows),
        name="paged_kv_append_pallas",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[new_spec, new_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(blk.astype(jnp.int32), off.astype(jnp.int32), k_new[:, :, None, :],
      v_new[:, :, None, :], k_pages, v_pages)
