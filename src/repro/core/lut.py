"""LUT construction for CIMple's split softmax.

Two single-dimensional, full-precision (w.r.t. their 8-bit index domain) LUTs:

  * **exp LUT** ``E``: 256 entries.  Input is an int8 attention score ``z_q``
    (the 32b->8b quantization unit's output).  The table stores

        E[z_q] = round( exp((z_q - z_quant_max) * s_z) * 2^f_e )

    with ``s_z`` the score quantization scale and ``z_quant_max = 127``.
    Because ``z_q - 127 <= 0`` every entry is <= 2^f_e — the quantization
    ceiling replaces the row max of safe softmax (the paper's key trick: no
    max pass, no stall).

  * **reciprocal LUT** ``M``: approximates ``1/S`` for the accumulated
    denominator ``S = sum_j E[z_q_j]``.  ``S`` is normalized to ``[1, 2)`` by
    a leading-one shift (hardware: priority encoder), the top ``m`` mantissa
    bits index a 2^m-entry table of ``round(2^f_m / mantissa)``; one multiply
    plus shifts then replaces the division.

The paper uses "full-precision tables to isolate the effect of the softmax
approximation from that of quantization" — we mirror that: the exp table is
exact-to-rounding over its whole domain, and the reciprocal table precision is
configurable (``recip_bits``), default 8 index bits.

All functions are pure jnp, jit-safe, and shared verbatim between the Pallas
kernels (via closure constants) and the ref oracles, so bit-exactness between
the two is by construction.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Z_QUANT_MAX = 127  # top of the symmetric int8 domain — replaces the row max

# Fixed-point fraction bits.
EXP_FRAC_BITS = 15     # exp LUT entries in [0, 2^15]
RECIP_FRAC_BITS = 15   # reciprocal mantissa table entries in (2^14, 2^15]


@dataclasses.dataclass(frozen=True)
class LUTConfig:
    """Static configuration of the split-softmax LUT pair."""
    scale_z: float                  # attention-score quantization scale s_z
    exp_frac_bits: int = EXP_FRAC_BITS
    recip_index_bits: int = 8       # mantissa bits indexing the recip table
    recip_frac_bits: int = RECIP_FRAC_BITS

    @property
    def exp_table_size(self) -> int:
        return 256

    @property
    def recip_table_size(self) -> int:
        return 1 << self.recip_index_bits

    @property
    def lut_bytes(self) -> int:
        """Total LUT footprint (4B entries) — fits trivially in VMEM/SRAM."""
        return 4 * (self.exp_table_size + self.recip_table_size)


def build_exp_lut(cfg: LUTConfig) -> np.ndarray:
    """256-entry exp table, indexed by ``z_q + 128`` (int8 -> [0, 255]).

    E[idx] = round(exp((idx - 128 - 127) * s_z) * 2^f_e), so index 255
    (z_q = +127 = z_quant_max) maps exactly to 2^f_e (e^0 = 1.0).
    """
    idx = np.arange(256, dtype=np.float64)
    z = idx - 128.0 - float(Z_QUANT_MAX)          # z_q - z_quant_max  in [-255, 0]
    vals = np.round(np.exp(z * cfg.scale_z) * (1 << cfg.exp_frac_bits))
    # numpy on purpose: tables are host-side constants; returning device
    # arrays from inside a traced scope would leak tracers via caches.
    return vals.astype(np.int32)


def build_recip_lut(cfg: LUTConfig) -> np.ndarray:
    """2^m-entry reciprocal-mantissa table.

    Entry i approximates 1/(1 + (i + 0.5)/2^m) in Q(recip_frac_bits):
        M[i] = round(2^f_m / (1 + (i + 0.5) / 2^m))
    (mid-rise quantization of the mantissa interval gives max relative error
    2^-(m+1), ~0.2% at m=8.)
    """
    m = cfg.recip_index_bits
    i = np.arange(1 << m, dtype=np.float64)
    mant = 1.0 + (i + 0.5) / (1 << m)
    vals = np.round((1 << cfg.recip_frac_bits) / mant)
    return vals.astype(np.int32)


def exp_lookup(z_q: jax.Array, exp_lut: jax.Array) -> jax.Array:
    """E[z_q] — int8 scores -> int32 fixed-point exponentials."""
    idx = z_q.astype(jnp.int32) + 128
    return jnp.take(exp_lut, idx, axis=0)


def recip_mantissa_index(s: jax.Array, mbits: int
                         ) -> Tuple[jax.Array, jax.Array]:
    """Exact exponent/mantissa split of a positive value, via IEEE-754 bits.

    This is the hardware-faithful normalization (a priority encoder reads the
    leading-one position; here the f32 exponent field *is* that encoder) and
    — critically — it is *exact*: XLA's float ``log2``/``exp2`` are off by an
    ulp even at powers of two, which flips the LUT index at bin boundaries
    (discovered the hard way; see tests/test_lut.py::test_recip_boundaries).

    Returns ``(idx, expo)`` where ``s = (1 + frac) * 2^expo``, ``frac`` in
    [0, 1), and ``idx`` is the top ``mbits`` bits of ``frac``.
    """
    s_f = jnp.maximum(s.astype(jnp.float32), 1.0)
    bits = jax.lax.bitcast_convert_type(s_f, jnp.int32)
    expo = jnp.bitwise_and(jnp.right_shift(bits, 23), 0xFF) - 127
    idx = jnp.bitwise_and(jnp.right_shift(bits, 23 - mbits),
                          (1 << mbits) - 1)
    return idx, expo


def recip_lookup(s: jax.Array, recip_lut: jax.Array, cfg: LUTConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """1/s via the reciprocal LUT.

    ``s = (1 + frac) * 2^expo``; the top ``recip_index_bits`` of ``frac``
    index the table, so ``1/s ~= M[idx] * 2^(-f_m - expo)``.

    Returns ``(r, e)`` with ``1/s ~= r * 2^e`` (``r`` int32 table value,
    ``e`` int32 exponent); callers compute ``x / s ~= x * r * 2^e``.
    Integer ``s`` is converted through f32 — exact below 2^24, and above
    that the f32 rounding is the shared semantics of kernel and oracle.
    """
    idx, expo = recip_mantissa_index(s, cfg.recip_index_bits)
    r = jnp.take(recip_lut, idx, axis=0)
    e = -expo - cfg.recip_frac_bits
    return r, e


def exp2_int(e: jax.Array) -> jax.Array:
    """Exact 2^e for integer e in [-126, 127], by building the f32 bits.

    XLA's ``exp2`` can be an ulp off even at integer inputs; assembling the
    exponent field directly is exact (and is one bitshift in hardware).
    """
    bits = jnp.left_shift(e.astype(jnp.int32) + 127, 23)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def recip_apply(x: jax.Array, r: jax.Array, e: jax.Array) -> jax.Array:
    """x / s  ~=  x * r * 2^e   (float32 result; x int32/float32)."""
    return x.astype(jnp.float32) * r.astype(jnp.float32) * exp2_int(e)


def recip_float(s: jax.Array, recip_lut: jax.Array, cfg: LUTConfig) -> jax.Array:
    """Scalar convenience: LUT-approximated 1/s as float32."""
    r, e = recip_lookup(s, recip_lut, cfg)
    return r.astype(jnp.float32) * jnp.exp2(e.astype(jnp.float32))
