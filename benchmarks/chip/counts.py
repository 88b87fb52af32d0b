"""Operations and bytes the algorithm needs, from the model's sizes alone.

These are the yardstick of every roofline share and utilization the
benchmark reports: counted from shapes and live lengths, never from the
compiled program, so a change to the program cannot move them.  One
multiply-add is two operations.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict

from model_weights import Model

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def layer_params(m: Model) -> int:
    """Weights one token multiplies through in the decoder layers."""
    hd = m.head_dim
    attn = m.d_model * hd * (m.n_heads + 2 * m.n_kv_heads) \
        + m.n_heads * hd * m.d_model
    mlp = 3 * m.d_model * m.d_ff
    return m.n_layers * (attn + mlp)


def matmul_params(m: Model) -> int:
    """Weights one decoded token multiplies through: layers and LM head."""
    return layer_params(m) + m.d_model * m.vocab_size


def attn_pairs_causal(s: int) -> int:
    """Query-key pairs of a causal prefill of ``s`` tokens."""
    return s * (s + 1) // 2


def prefill_flops(m: Model, s: int) -> int:
    """Model FLOPs of prefilling one prompt of ``s`` tokens: QK^T and PV
    over the causal pairs, the layers' projections at every row, and the
    LM head at the last row only, the one whose logits serving needs."""
    attn = 4 * m.n_layers * m.n_heads * m.head_dim * attn_pairs_causal(s)
    return 2 * layer_params(m) * s + 2 * m.d_model * m.vocab_size + attn


def decode_flops(m: Model, live_tokens: int, rows: int) -> int:
    """Model FLOPs of one decode call over ``rows`` slots whose attention
    lengths (current token included) sum to ``live_tokens``."""
    attn = 4 * m.n_layers * m.n_heads * m.head_dim * live_tokens
    return 2 * matmul_params(m) * rows + attn


def prefill_attn_seconds(m: Model, s: int, pk: Dict[str, float]) -> float:
    """Least time of the prefill attention of one prompt on one chip:
    QK^T at the int8 peak plus PV at the bf16 peak over the causal pairs,
    or the int8 q/k/v reads and f32 output write at HBM peak if longer."""
    per_pv = 2 * m.n_heads * m.head_dim * attn_pairs_causal(s)
    ops_s = per_pv / pk["int8_ops"] + per_pv / pk["bf16_flops"]
    nbytes = s * m.head_dim * (m.n_heads + 2 * m.n_kv_heads) \
        + 4 * s * m.n_heads * m.head_dim
    return m.n_layers * max(ops_s, nbytes / pk["hbm_bytes_per_s"])


def decode_attn_bytes(m: Model, live_tokens: int, rows: int) -> int:
    """HBM bytes the decode attention of one call must move: int8 K and V
    of every live position, the f32 query in and the f32 output out."""
    kv = 2 * m.n_kv_heads * m.head_dim * live_tokens
    qo = 2 * 4 * m.n_heads * m.head_dim * rows
    return m.n_layers * (kv + qo)
