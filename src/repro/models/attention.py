"""Multi-head attention block wired to the CIMple datapath.

Projections run in the model's compute dtype; the score->softmax->AV epilogue
runs through :mod:`repro.core.attention` in whichever mode the config selects
(float / fakequant / int8-LUT).  The KV cache is **int8 with static per-layer
scales** — exactly the paper's decoder mapping, where K and V live in the CIM
array in int8 and the current token streams against them (Eq. 3).

Decode steps default to the **fused datapath** (``cfg.attn_fused``): the fp
query goes straight into one kernel that quantizes it in VMEM, runs the int8
QK^T tiles, the LUT split-softmax accumulation, and PV — the software mirror
of the paper's never-leaves-the-array dual-banked macro.  Setting
``attn_fused=False`` (or ``--fused off`` in serving) restores the composed
quantize -> decode-kernel pipeline for A/B comparison.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import attention as core_attn
from repro.core import paged_kv
from repro.core import quantization as qlib
from repro.core.attention import AttentionSpec
from repro.dist.sharding import shard
from repro.models import layers as L
from repro.models.config import ModelConfig


def attn_block_init(key, cfg: ModelConfig, *, d_input: Optional[int] = None
                    ) -> Dict:
    """QKV + output projections (+ optional per-head q/k RMSNorm)."""
    d_in = d_input or cfg.d_model
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    p = {
        "wq": L.linear_init(ks[0], d_in, hq * hd),
        "wk": L.linear_init(ks[1], d_in, hkv * hd),
        "wv": L.linear_init(ks[2], d_in, hkv * hd),
        "wo": L.linear_init(ks[3], hq * hd, cfg.d_model,
                            std=(hq * hd) ** -0.5 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.rmsnorm_init(ks[4], hd)
        p["k_norm"] = L.rmsnorm_init(ks[5], hd)
    return p


def _project_qkv(params, x, cfg: ModelConfig, positions):
    """x: (B, S, d_in) -> q (B,Hq,S,hd), k/v (B,Hkv,S,hd), roped."""
    b, s, _ = x.shape
    dt = cfg.compute_dtype
    hd = cfg.hd
    q = L.linear_apply(params["wq"], x, dtype=dt)
    k = L.linear_apply(params["wk"], x, dtype=dt)
    v = L.linear_apply(params["wv"], x, dtype=dt)
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = L.rmsnorm_apply(params["q_norm"], q)
        k = L.rmsnorm_apply(params["k_norm"], k)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "heads", None, None)
    k = shard(k, "batch", "heads", None, None)
    v = shard(v, "batch", "heads", None, None)
    return q, k, v


def attn_block_apply(params, x, cfg: ModelConfig, *,
                     spec: Optional[AttentionSpec] = None,
                     positions: Optional[jax.Array] = None,
                     causal: bool = True) -> jax.Array:
    """Full-sequence attention (training / prefill / encoder)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    spec = spec or cfg.attn_spec()
    if not causal:
        spec = core_attn.AttentionSpec(**{**spec.__dict__, "causal": False})
    q, k, v = _project_qkv(params, x, cfg, positions)
    out = core_attn.attention(q, k, v, spec)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    out = shard(out, "batch", None, "embed")
    return L.linear_apply(params["wo"], out, dtype=cfg.compute_dtype)


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder): K/V from encoder memory
# ---------------------------------------------------------------------------

def cross_attn_apply(params, x, memory, cfg: ModelConfig, *,
                     spec: Optional[AttentionSpec] = None,
                     memory_valid_len: Optional[jax.Array] = None
                     ) -> jax.Array:
    b, s, _ = x.shape
    dt = cfg.compute_dtype
    hd = cfg.hd
    spec = spec or cfg.attn_spec()
    spec = core_attn.AttentionSpec(**{**spec.__dict__, "causal": False})
    q = L.linear_apply(params["wq"], x, dtype=dt)
    k = L.linear_apply(params["wk"], memory, dtype=dt)
    v = L.linear_apply(params["wv"], memory, dtype=dt)
    sm = memory.shape[1]
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, sm, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, sm, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    out = core_attn.attention(q, k, v, spec, kv_valid_len=memory_valid_len)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * hd)
    return L.linear_apply(params["wo"], out, dtype=dt)


# ---------------------------------------------------------------------------
# int8 KV cache (CIMple decoder mapping)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: Optional[int] = None) -> Dict:
    """Stacked-by-layer int8 cache.  ``scale_k/scale_v`` are static per-layer
    quantization scales, fixed at prefill (calibration) time."""
    nl = n_layers if n_layers is not None else cfg.n_layers
    shape = (nl, batch, cfg.n_kv_heads, max_len, cfg.hd)
    return {
        "k_q": jnp.zeros(shape, jnp.int8),
        "v_q": jnp.zeros(shape, jnp.int8),
        "scale_k": jnp.full((nl, 1, 1, 1, 1), 1e-2, jnp.float32),
        "scale_v": jnp.full((nl, 1, 1, 1, 1), 1e-2, jnp.float32),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def prefill_into_cache(layer_cache: Dict, k: jax.Array, v: jax.Array,
                       valid_len: jax.Array) -> Dict:
    """Quantize the prefilled K/V (B,Hkv,S,hd) into one layer's cache slice.

    ``layer_cache`` holds this layer's views: k_q/v_q (B,Hkv,S_max,hd) and
    scalar scales.  Calibration: absmax over the prefill."""
    s = k.shape[2]
    s_k = qlib.absmax_scale(k)
    s_v = qlib.absmax_scale(v)
    k_q = layer_cache["k_q"].at[:, :, :s, :].set(qlib.quantize(k, s_k))
    v_q = layer_cache["v_q"].at[:, :, :s, :].set(qlib.quantize(v, s_v))
    return {"k_q": k_q, "v_q": v_q,
            "scale_k": jnp.reshape(s_k, (1, 1, 1, 1)),
            "scale_v": jnp.reshape(s_v, (1, 1, 1, 1)),
            "length": valid_len}


def init_paged_kv_cache(cfg: ModelConfig, num_blocks: int, slots: int,
                        blocks_per_slot: int, block_k: int,
                        n_layers: Optional[int] = None) -> Dict:
    """Stacked-by-layer paged int8 pool (see :mod:`repro.core.paged_kv`).

    Same static per-layer scales as :func:`init_kv_cache`; the dense
    ``(slots, max_len)`` rows are replaced by a block pool plus per-slot
    block tables, so admission never touches another slot's cache."""
    nl = n_layers if n_layers is not None else cfg.n_layers
    return paged_kv.init_kv_pages(nl, num_blocks, cfg.n_kv_heads, block_k,
                                  cfg.hd, slots, blocks_per_slot)


def attn_block_decode_paged(params, x, layer_cache: Dict, cfg: ModelConfig, *,
                            spec: Optional[AttentionSpec] = None
                            ) -> Tuple[jax.Array, Dict]:
    """One-token decode against one layer's slice of the paged pool.

    ``layer_cache``: k_pages/v_pages (num_blocks, Hkv, block_k, hd), scalar
    scales, block_table (B, max_blocks), length (B,).  The new token's K/V
    are quantized with the static scales and written, in place, into the
    slot's *current tail block* (table[b, pos // block_k]); retired slots
    point at the trash block, so their writes are harmless.
    """
    b = x.shape[0]
    dt = cfg.compute_dtype
    hd = cfg.hd
    spec = spec or cfg.attn_spec(serve=True)
    table = layer_cache["block_table"]
    mb = table.shape[1]
    block_k = layer_cache["k_pages"].shape[2]
    new_len = layer_cache["length"] + 1            # includes current token
    positions = (new_len - 1)[:, None]             # (B, 1) absolute (RoPE)
    q, k, v = _project_qkv(params, x, cfg, positions)
    s_k = layer_cache["scale_k"].reshape(())
    s_v = layer_cache["scale_v"].reshape(())
    k_new = qlib.quantize(k[:, :, 0, :], s_k)      # (B, Hkv, hd)
    v_new = qlib.quantize(v[:, :, 0, :], s_v)
    # tail-block address; clamp so an over-run slot (retired but still
    # stepping) stays inside its table row instead of reading OOB
    pos = jnp.minimum(new_len - 1, mb * block_k - 1)
    b_idx = jnp.arange(b)
    blk = table[b_idx, pos // block_k]             # (B,) pool block ids
    off = pos % block_k
    k_pages, v_pages = core_attn.paged_append(
        layer_cache["k_pages"], layer_cache["v_pages"], blk, off, k_new,
        v_new, spec)
    out = core_attn.paged_decode_attention(
        q[:, :, 0, :], k_pages, v_pages, table, s_k, s_v, new_len, spec)
    out = out.reshape(b, 1, cfg.n_heads * hd)
    out = L.linear_apply(params["wo"], out, dtype=dt)
    new_cache = dict(layer_cache, k_pages=k_pages, v_pages=v_pages,
                     length=new_len)
    return out, new_cache


def attn_block_verify_paged(params, x, layer_cache: Dict, cfg: ModelConfig, *,
                            spec: Optional[AttentionSpec] = None
                            ) -> Tuple[jax.Array, Dict]:
    """T-token speculative verify against one layer's paged pool slice.

    ``x (B*T, 1, d_in)`` carries the T verify tokens of each slot (last
    accepted token + the drafts) as slot-major rows, in decode's activation
    shape (see ``transformer.verify_step``); their K/V are quantized with
    the static scales, scattered through the block table at positions
    ``length + t``, and all T queries stream against the pool in one fused
    verify launch with per-token causal lengths.  The T-token twin of
    :func:`attn_block_decode_paged` — rejected tokens are rolled back later
    by the scheduler via ``paged_kv.truncate_lengths``, never here.
    """
    dt = cfg.compute_dtype
    hd = cfg.hd
    spec = spec or cfg.attn_spec(serve=True)
    table = layer_cache["block_table"]
    base_len = layer_cache["length"]
    b = base_len.shape[0]
    t = x.shape[0] // b
    positions = (base_len[:, None] + jnp.arange(t)[None, :]).reshape(b * t, 1)
    q, k, v = jax.lax.optimization_barrier(
        _project_qkv(params, x, cfg, positions))            # (B*T, H, 1, hd)
    q = q.reshape(b, t, cfg.n_heads, hd).transpose(0, 2, 1, 3)   # (B,H,T,hd)
    s_k = layer_cache["scale_k"].reshape(())
    s_v = layer_cache["scale_v"].reshape(())
    k_new = qlib.quantize(k, s_k).reshape(b, t, cfg.n_kv_heads, hd)
    v_new = qlib.quantize(v, s_v).reshape(b, t, cfg.n_kv_heads, hd)
    k_pages = paged_kv.append_kv(layer_cache["k_pages"], table, base_len,
                                 k_new)
    v_pages = paged_kv.append_kv(layer_cache["v_pages"], table, base_len,
                                 v_new)
    new_len = base_len + t                         # includes all T tokens
    out = core_attn.paged_verify_attention(
        q, k_pages, v_pages, table, s_k, s_v, new_len, spec)
    out = out.transpose(0, 2, 1, 3).reshape(b * t, 1, cfg.n_heads * hd)
    out = L.linear_apply(params["wo"], out, dtype=dt)
    new_cache = dict(layer_cache, k_pages=k_pages, v_pages=v_pages,
                     length=new_len)
    return out, new_cache


def attn_block_decode(params, x, layer_cache: Dict, cfg: ModelConfig, *,
                      spec: Optional[AttentionSpec] = None
                      ) -> Tuple[jax.Array, Dict]:
    """One-token decode: x (B, 1, d_in) + cache -> (B, 1, d_model), new cache.

    The new token's K/V are quantized with the cache's *static* scales and
    written in place (the CIM simultaneous-read-write), then the query streams
    against the whole int8 cache via the split-softmax decode kernel.
    """
    b = x.shape[0]
    dt = cfg.compute_dtype
    hd = cfg.hd
    spec = spec or cfg.attn_spec(serve=True)
    cache_size = layer_cache["k_q"].shape[2]
    new_len = layer_cache["length"] + 1            # includes current token
    positions = (new_len - 1)[:, None]             # (B, 1) absolute (RoPE)
    q, k, v = _project_qkv(params, x, cfg, positions)
    s_k = layer_cache["scale_k"].reshape(())
    s_v = layer_cache["scale_v"].reshape(())
    k_new = qlib.quantize(k[:, :, 0, :], s_k)      # (B, Hkv, hd)
    v_new = qlib.quantize(v[:, :, 0, :], s_v)
    if spec.window is not None:
        # SWA ring buffer: the cache holds exactly the last `cache_size`
        # (== window) positions; no window mask needed at score time.
        pos = (new_len - 1) % cache_size
        attn_len = jnp.minimum(new_len, cache_size)
        spec = core_attn.AttentionSpec(**{**spec.__dict__, "window": None})
    else:
        pos = new_len - 1
        attn_len = new_len
    b_idx = jnp.arange(b)
    k_q = layer_cache["k_q"].at[b_idx, :, pos, :].set(k_new)
    v_q = layer_cache["v_q"].at[b_idx, :, pos, :].set(v_new)
    out = core_attn.decode_attention(
        q[:, :, 0, :], k_q, v_q, s_k, s_v, attn_len, spec)
    out = out.reshape(b, 1, cfg.n_heads * hd)
    out = L.linear_apply(params["wo"], out, dtype=dt)
    new_cache = dict(layer_cache, k_q=k_q, v_q=v_q, length=new_len)
    return out, new_cache
