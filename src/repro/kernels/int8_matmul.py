"""Pallas TPU kernel: int8 x int8 -> int32 GEMM — "the CIM core" on TPU.

CIMple's array computes 8b MACs by nibble-splitting weights across dual SRAM
banks and shift-adding 4b partial products over 8 cycles.  The TPU MXU does
int8 x int8 -> int32 natively in one pass; tests prove the two datapaths are
bit-identical (``core/cim.py:nibble_split_matmul``), so the production kernel
simply tiles the native path.

The optional fused requant epilogue is the 32b->8b quantization unit: when
``multiplier`` is given, the int32 accumulator is requantized to int8 before
leaving VMEM — mirroring how CIMple keeps all inter-stage traffic 8-bit.

Grid (M/bm, N/bn, K/bk), k innermost, int32 accumulator scratch in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int8_matmul_kernel(scalars_ref, x_ref, w_ref, out_ref, acc_ref, *,
                        num_k_blocks: int, requant: bool):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        if requant:
            m = scalars_ref[0]
            y = jnp.round(acc_ref[...].astype(jnp.float32) * m)
            out_ref[...] = jnp.clip(y, -128, 127).astype(jnp.int8)
        else:
            out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"))
def int8_matmul_pallas(
    x_q: jax.Array,                 # (M, K) int8
    w_q: jax.Array,                 # (K, N) int8
    multiplier: Optional[jax.Array] = None,   # scalar f32 -> fused requant
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """int8 GEMM; returns int32 (M, N), or int8 when ``multiplier`` given."""
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, k, n), (block_m, block_k, block_n))
    requant = multiplier is not None
    scalars = jnp.stack([jnp.asarray(multiplier if requant else 1.0,
                                     jnp.float32)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // block_m, n // block_n, k // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki, *_: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki, *_: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki, *_: (mi, ni)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
    )

    out_dtype = jnp.int8 if requant else jnp.int32
    return pl.pallas_call(
        functools.partial(_int8_matmul_kernel,
                          num_k_blocks=k // block_k, requant=requant),
        name="int8_matmul_pallas",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(scalars, x_q, w_q)
