"""Decoder-only model assembly for all families (dense / moe / ssm / hybrid).

Layers with identical structure are *stacked* along a leading axis and driven
by ``lax.scan`` (MaxText-style): compile time stays flat in depth — essential
when dry-running 95-layer models — and each block is ``jax.checkpoint``-ed so
training memory holds only layer-boundary residuals.

Three entry points per model:
  * :func:`forward`      — full-sequence logits (training / encoder-style)
  * :func:`prefill`      — forward + populate the int8 KV cache / SSM state
  * :func:`decode_step`  — one token in, logits + updated cache out (Eq. 3)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import attention as core_attn
from repro.core import paged_kv
from repro.core import quantization as qlib
from repro.dist.sharding import shard
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mlp as M
from repro.models import moe as MOE
from repro.models import ssm as S
from repro.models.config import ModelConfig

Params = Dict[str, Any]


def maybe_scan(body, carry, xs, cfg: ModelConfig):
    """lax.scan when ``cfg.scan_layers`` else a Python unroll (see
    _scan_segment docstring for why the dry-run needs the unroll)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys_list = []
    for i in range(n):
        carry, ys = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys_list.append(ys)
    if ys_list and jax.tree.leaves(ys_list[0]):
        stacked = jax.tree.map(lambda *v: jnp.stack(v), *ys_list)
    else:
        stacked = ys_list[0] if ys_list else None
    return carry, stacked


# ---------------------------------------------------------------------------
# per-family block init/apply
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, kind: str) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": L.NORM_INIT[cfg.norm](ks[0], cfg.d_model)}
    if kind == "dense":
        p["attn"] = A.attn_block_init(ks[1], cfg)
        p["norm2"] = L.NORM_INIT[cfg.norm](ks[2], cfg.d_model)
        p["mlp"] = M.mlp_init(ks[3], cfg)
    elif kind == "moe":
        p["attn"] = A.attn_block_init(ks[1], cfg)
        p["norm2"] = L.NORM_INIT[cfg.norm](ks[2], cfg.d_model)
        p["moe"] = MOE.moe_init(ks[3], cfg)
    elif kind == "mamba1":
        p["ssm"] = S.mamba1_init(ks[1], cfg)
    elif kind == "mamba2":
        p["ssm"] = S.mamba2_init(ks[1], cfg)
    else:
        raise ValueError(kind)
    return p


def _norm(cfg):
    return L.NORM_APPLY[cfg.norm]


def _block_apply(params, x, cfg: ModelConfig, kind: str, *, serve: bool
                 ) -> Tuple[jax.Array, Dict]:
    """Full-sequence block.  Returns (x, aux) where aux carries MoE losses
    and (in serve mode) this layer's K/V for cache prefill."""
    aux: Dict[str, Any] = {}
    norm = _norm(cfg)
    if kind in ("dense", "moe"):
        h = norm(params["norm1"], x)
        spec = cfg.attn_spec(serve=serve)
        if serve:
            # prefill returns raw K/V so the caller can quantize into cache
            b, s, _ = h.shape
            q, k, v = A._project_qkv(params["attn"], h, cfg, jnp.arange(s))
            o = core_attn.attention(q, k, v, spec)
            o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
            attn_out = L.linear_apply(params["attn"]["wo"], o,
                                      dtype=cfg.compute_dtype)
            aux["kv"] = (k, v)
        else:
            attn_out = A.attn_block_apply(params["attn"], h, cfg, spec=spec)
        x = x + attn_out
        h = norm(params["norm2"], x)
        if kind == "dense":
            x = x + M.mlp_apply(params["mlp"], h, cfg)
        else:
            out, moe_aux = MOE.moe_apply(params["moe"], h, cfg)
            x = x + out
            aux.update(moe_aux)
    else:  # mamba1 / mamba2
        h = norm(params["norm1"], x)
        fn = S.mamba1_apply if kind == "mamba1" else S.mamba2_apply
        # serve mode threads a zero initial state so the final recurrent
        # state comes back for the decode cache (single pass, no rerun)
        st0 = _zero_ssm_state(cfg, x.shape[0]) if serve else None
        out, st = fn(params["ssm"], h, cfg, state=st0)
        if serve:
            aux["ssm"] = st
        x = x + out
    x = shard(x, "batch", "seq" if cfg.seq_sharding else None, "embed")
    return x, aux


def _block_decode(params, x, cache_slice, cfg: ModelConfig, kind: str
                  ) -> Tuple[jax.Array, Dict]:
    """One-token block step against this layer's cache slice."""
    norm = _norm(cfg)
    if kind in ("dense", "moe"):
        h = norm(params["norm1"], x)
        attn_out, new_kv = A.attn_block_decode(params["attn"], h,
                                               cache_slice["kv"], cfg)
        x = x + attn_out
        h = norm(params["norm2"], x)
        if kind == "dense":
            x = x + M.mlp_apply(params["mlp"], h, cfg)
        else:
            out, _ = MOE.moe_apply(params["moe"], h, cfg)
            x = x + out
        return x, dict(cache_slice, kv=new_kv)
    h = norm(params["norm1"], x)
    fn = S.mamba1_apply if kind == "mamba1" else S.mamba2_apply
    out, new_state = fn(params["ssm"], h, cfg, state=cache_slice["ssm"])
    return x + out, dict(cache_slice, ssm=new_state)


def _block_decode_paged(params, x, cache_slice, cfg: ModelConfig, kind: str
                        ) -> Tuple[jax.Array, Dict]:
    """One-token dense/moe block step against the paged pool slice."""
    norm = _norm(cfg)
    h = norm(params["norm1"], x)
    attn_out, new_kv = A.attn_block_decode_paged(params["attn"], h,
                                                 cache_slice["kv"], cfg)
    x = x + attn_out
    h = norm(params["norm2"], x)
    if kind == "dense":
        x = x + M.mlp_apply(params["mlp"], h, cfg)
    else:
        out, _ = MOE.moe_apply(params["moe"], h, cfg)
        x = x + out
    return x, dict(cache_slice, kv=new_kv)


def _block_verify_paged(params, x, cache_slice, cfg: ModelConfig, kind: str
                        ) -> Tuple[jax.Array, Dict]:
    """T-token speculative-verify block step against the paged pool slice."""
    norm = _norm(cfg)
    h = norm(params["norm1"], x)
    attn_out, new_kv = A.attn_block_verify_paged(params["attn"], h,
                                                 cache_slice["kv"], cfg)
    x = x + attn_out
    h = norm(params["norm2"], x)
    if kind == "dense":
        x = x + M.mlp_apply(params["mlp"], h, cfg)
    else:
        out, _ = MOE.moe_apply(params["moe"], h, cfg)
        x = x + out
    return x, dict(cache_slice, kv=new_kv)


def _layer_kinds(cfg: ModelConfig):
    """(kind, count) segments, in order.  Homogeneous segments get scanned."""
    if cfg.family == "dense":
        return [("dense", cfg.n_layers)]
    if cfg.family == "moe":
        fd = cfg.moe.first_dense_layers
        seg = []
        if fd:
            seg.append(("dense", fd))
        seg.append(("moe", cfg.n_layers - fd))
        return seg
    if cfg.family == "ssm":
        return [("mamba1" if cfg.ssm.kind == "mamba1" else "mamba2",
                 cfg.n_layers)]
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stacked_init(key, cfg: ModelConfig, kind: str, n: int) -> Params:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _block_init(k, cfg, kind))(keys)


def init_params(key, cfg: ModelConfig) -> Params:
    kv, kb, kh, kf = jax.random.split(key, 4)
    vp = L.pad_vocab(cfg.vocab_size, cfg.vocab_pad_multiple)
    p: Params = {"embed": L.embedding_init(kv, vp, cfg.d_model)}
    if cfg.family == "hybrid":
        p.update(_hybrid_init(kb, cfg))
    else:
        segs = _layer_kinds(cfg)
        p["segments"] = [
            _stacked_init(jax.random.fold_in(kb, i), cfg, kind, n)
            for i, (kind, n) in enumerate(segs)]
    p["final_norm"] = L.NORM_INIT[cfg.norm](kf, cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"] = L.linear_init(kh, cfg.d_model, vp)
    return p


def _hybrid_init(key, cfg: ModelConfig) -> Params:
    """zamba2: stacked mamba2 blocks + ONE shared attention block applied
    every ``hybrid_attn_every`` layers on concat(hidden, embeddings)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    n = cfg.n_layers
    every = cfg.hybrid_attn_every
    assert n % every == 0, (n, every)
    groups, per = n // every, every
    keys = jax.random.split(k1, n)
    mamba = jax.vmap(lambda k: _block_init(k, cfg, "mamba2"))(keys)
    # reshape stacked leaves to (groups, per, ...)
    mamba = jax.tree.map(
        lambda a: a.reshape((groups, per) + a.shape[1:]), mamba)
    shared = {
        "norm": L.NORM_INIT[cfg.norm](k2, 2 * cfg.d_model),
        "attn": A.attn_block_init(k3, cfg, d_input=2 * cfg.d_model),
        "mlp_norm": L.NORM_INIT[cfg.norm](k4, cfg.d_model),
        "mlp": M.mlp_init(jax.random.fold_in(k4, 1), cfg),
    }
    return {"mamba_groups": mamba, "shared_attn": shared}


# ---------------------------------------------------------------------------
# forward (training / full sequence)
# ---------------------------------------------------------------------------

def _scan_segment(params_stacked, x, cfg, kind, *, serve: bool):
    """Run a homogeneous stack of blocks; accumulates MoE aux losses.
    In serve mode also returns stacked per-layer (k, v) for cache prefill.

    ``cfg.scan_layers`` picks lax.scan (flat compile time — production) vs a
    Python unroll (dry-run/roofline: XLA's cost_analysis counts a while body
    once regardless of trip count, so only unrolled modules give true
    whole-step FLOP/byte/collective counts).
    """

    def body(x, layer_params):
        x, aux = _block_apply(layer_params, x, cfg, kind, serve=serve)
        ys = {k: aux[k] for k in ("kv", "ssm") if k in aux}
        losses = jnp.stack([aux.get("aux_loss", jnp.float32(0)),
                            aux.get("z_loss", jnp.float32(0))])
        return x, (ys, losses)

    if cfg.remat and not serve:
        body = jax.checkpoint(body)
    if cfg.scan_layers:
        x, (ys, losses) = jax.lax.scan(body, x, params_stacked)
        return x, ys, jnp.sum(losses, axis=0)
    n = jax.tree.leaves(params_stacked)[0].shape[0]
    ys_list, losses = [], jnp.zeros((2,), jnp.float32)
    for i in range(n):
        layer = jax.tree.map(lambda a: a[i], params_stacked)
        x, (ys_i, l_i) = body(x, layer)
        ys_list.append(ys_i)
        losses = losses + l_i
    ys = (jax.tree.map(lambda *xs: jnp.stack(xs), *ys_list)
          if ys_list and ys_list[0] else {})
    return x, ys, losses


def embed_tokens(params, tokens, cfg: ModelConfig,
                 embed_override: Optional[jax.Array] = None) -> jax.Array:
    """Token ids -> (B, S, d).  ``embed_override`` feeds precomputed frontend
    embeddings (audio frames / vision patches) instead of table lookups."""
    if embed_override is not None:
        return embed_override.astype(cfg.compute_dtype)
    x = L.embedding_apply(params["embed"], tokens, dtype=cfg.compute_dtype)
    return shard(x, "batch", "seq" if cfg.seq_sharding else None, "embed")


def unembed(params, x, cfg: ModelConfig) -> jax.Array:
    x = _norm(cfg)(params["final_norm"], x)
    ldt = jnp.dtype(cfg.logits_dtype) if cfg.logits_dtype else jnp.float32
    if cfg.tie_embeddings:
        logits = L.unembed_apply(params["embed"], x,
                                 logical_vocab=cfg.vocab_size, dtype=ldt)
    else:
        logits = L.linear_apply(params["lm_head"], x, dtype=ldt)
    return shard(logits, "batch", None, "vocab")


def forward(params, tokens, cfg: ModelConfig, *,
            embed_override: Optional[jax.Array] = None,
            serve: bool = False) -> Tuple[jax.Array, Dict]:
    """tokens (B, S) -> logits (B, S, vocab_padded), aux losses."""
    x = embed_tokens(params, tokens, cfg, embed_override)
    aux = {"aux_loss": jnp.float32(0), "z_loss": jnp.float32(0)}
    if cfg.family == "hybrid":
        x, kvs, states = _hybrid_forward(params, x, cfg, serve=serve)
        if serve:
            aux["kv"] = kvs
            aux["ssm"] = states
    else:
        segs = _layer_kinds(cfg)
        kvs, states = [], []
        for seg_params, (kind, _) in zip(params["segments"], segs):
            x, ys, losses = _scan_segment(seg_params, x, cfg, kind,
                                          serve=serve)
            aux["aux_loss"] += losses[0]
            aux["z_loss"] += losses[1]
            if serve and "kv" in ys:
                kvs.append(ys["kv"])
            if serve and "ssm" in ys:
                states.append(ys["ssm"])
        if serve and kvs:
            aux["kv"] = kvs
        if serve and states:
            aux["ssm"] = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                      *states)
    logits = unembed(params, x, cfg)
    return logits, aux


def _hybrid_forward(params, x, cfg: ModelConfig, *, serve: bool):
    """zamba2 layout: [shared attn -> every mamba blocks] x groups."""
    x0 = x  # original embeddings, re-fed to every shared-attn invocation
    groups = cfg.n_layers // cfg.hybrid_attn_every
    sp = params["shared_attn"]
    kvs, states = [], []

    def attn_invoke(x):
        h = jnp.concatenate([x, x0], axis=-1)
        h = _norm(cfg)(sp["norm"], h)
        spec = cfg.attn_spec(serve=serve)
        if serve:
            b, s, _ = h.shape
            q, k, v = A._project_qkv(sp["attn"], h, cfg, jnp.arange(s))
            o = core_attn.attention(q, k, v, spec)
            o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
            out = L.linear_apply(sp["attn"]["wo"], o, dtype=cfg.compute_dtype)
            kvs.append((k, v))
        else:
            out = A.attn_block_apply(sp["attn"], h, cfg, spec=spec)
        x = x + out
        h = _norm(cfg)(sp["mlp_norm"], x)
        return x + M.mlp_apply(sp["mlp"], h, cfg)

    def group_body(x, group_params):
        def inner(x, layer_params):
            x, aux = _block_apply(layer_params, x, cfg, "mamba2",
                                  serve=serve)
            return x, aux.get("ssm")
        if cfg.remat and not serve:
            inner = jax.checkpoint(inner)
        x, sts = maybe_scan(inner, x, group_params, cfg)
        return x, sts

    mamba = params["mamba_groups"]
    for g in range(groups):
        x = attn_invoke(x)
        gp = jax.tree.map(lambda a: a[g], mamba)
        x, sts = group_body(x, gp)
        if serve:
            states.append(sts)
    if serve:
        kvs = (jnp.stack([kv[0] for kv in kvs]),
               jnp.stack([kv[1] for kv in kvs]))
        states = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *states)
    return x, kvs, states


# ---------------------------------------------------------------------------
# serve: prefill + decode
# ---------------------------------------------------------------------------

def make_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Family-appropriate decode cache (int8 KV and/or SSM state)."""
    cache: Dict[str, Any] = {"length": jnp.zeros((batch,), jnp.int32)}
    if cfg.family in ("dense", "moe"):
        cache["kv"] = A.init_kv_cache(cfg, batch, max_len)
    elif cfg.family == "ssm":
        cache["ssm"] = S.init_ssm_state(cfg, batch, cfg.n_layers)
    elif cfg.family == "hybrid":
        groups = cfg.n_layers // cfg.hybrid_attn_every
        cache["kv"] = A.init_kv_cache(cfg, batch, max_len, n_layers=groups)
        cache["ssm"] = S.init_ssm_state(cfg, batch, cfg.n_layers)
    return cache


def make_paged_cache(cfg: ModelConfig, slots: int, max_len: int, *,
                     block_k: int = 32,
                     num_blocks: Optional[int] = None) -> Dict:
    """Paged decode cache: int8 KV block pool + per-slot block tables.

    Each slot can hold up to ``max_len`` positions spread over
    ``ceil(max_len / block_k)`` pool blocks; the default pool size reserves
    exactly that per slot plus the trash block (id 0).  SSM state stays
    per-slot dense (it is O(1) per slot — nothing to page).
    """
    assert cfg.family in ("dense", "moe", "ssm"), (
        f"paged cache supports dense/moe/ssm, not {cfg.family}")
    cache: Dict[str, Any] = {"length": jnp.zeros((slots,), jnp.int32)}
    if cfg.family in ("dense", "moe"):
        bps = paged_kv.blocks_per_seq(max_len, block_k)
        if num_blocks is None:
            num_blocks = 1 + slots * bps
        cache["kv"] = A.init_paged_kv_cache(cfg, num_blocks, slots, bps,
                                            block_k)
    else:
        cache["ssm"] = S.init_ssm_state(cfg, slots, cfg.n_layers)
    return cache


def prefill_paged(params, tokens, cfg: ModelConfig, cache: Dict,
                  slot_ids: jax.Array, block_ids: jax.Array, *,
                  valid_len: Optional[jax.Array] = None,
                  calibrate: bool = False) -> Tuple[jax.Array, Dict]:
    """Prefill ``tokens (B, S)`` into the paged cache, touching only the
    given slots' blocks — the per-slot admission primitive.

    ``slot_ids (B,)`` are the table rows being (re)filled; ``block_ids
    (B, blocks_per_slot)`` is each slot's full block reservation from the
    allocator (prompt K/V lands in the leading ``ceil(S / block_k)`` blocks,
    decode appends into the rest).  ``calibrate=True`` (first wave only)
    sets the pool's static per-layer scales from this batch's absmax;
    afterwards new requests quantize with the existing scales, exactly like
    decode — the CIM array's calibration is a deploy-time constant.
    """
    b, s = tokens.shape[:2]
    if valid_len is None:
        valid_len = jnp.full((b,), s, jnp.int32)
    logits, aux = forward(params, tokens, cfg, serve=True)
    cache = dict(cache, length=cache["length"].at[slot_ids].set(valid_len))
    if "kv" in aux:
        kvc = cache["kv"]
        block_k = kvc["k_pages"].shape[3]
        mb = kvc["block_table"].shape[1]
        assert block_ids.shape[1] == mb, (block_ids.shape, mb)
        n_blk = paged_kv.blocks_per_seq(s, block_k)
        assert n_blk <= mb, (s, block_k, mb)
        k_all = jnp.concatenate([kv[0] for kv in _as_list(aux["kv"])], 0)
        v_all = jnp.concatenate([kv[1] for kv in _as_list(aux["kv"])], 0)
        pad = n_blk * block_k - s
        if pad:
            k_all = jnp.pad(k_all, ((0, 0),) * 3 + ((0, pad), (0, 0)))
            v_all = jnp.pad(v_all, ((0, 0),) * 3 + ((0, pad), (0, 0)))
        if calibrate:
            s_k = qlib.absmax_scale(k_all, axis=(1, 2, 3, 4))  # (L,1,1,1,1)
            s_v = qlib.absmax_scale(v_all, axis=(1, 2, 3, 4))
        else:
            s_k, s_v = kvc["scale_k"], kvc["scale_v"]

        def to_blocks(x_q):
            # (L, B, Hkv, n_blk*bk, hd) -> (L, B*n_blk, Hkv, bk, hd)
            nl, _, hkv, _, hd = x_q.shape
            x_q = x_q.reshape(nl, b, hkv, n_blk, block_k, hd)
            return x_q.transpose(0, 1, 3, 2, 4, 5).reshape(
                nl, b * n_blk, hkv, block_k, hd)

        flat_ids = block_ids[:, :n_blk].reshape(-1)
        kvc = dict(
            kvc,
            k_pages=kvc["k_pages"].at[:, flat_ids].set(
                to_blocks(qlib.quantize(k_all, s_k))),
            v_pages=kvc["v_pages"].at[:, flat_ids].set(
                to_blocks(qlib.quantize(v_all, s_v))),
            scale_k=s_k, scale_v=s_v,
            block_table=kvc["block_table"].at[slot_ids].set(block_ids),
            length=kvc["length"].at[slot_ids].set(valid_len))
        cache["kv"] = kvc
    if "ssm" in aux:
        ssc = jax.tree.map(lambda pool, st: pool.at[:, slot_ids].set(st),
                           cache["ssm"], aux["ssm"])
        cache = dict(cache, ssm=ssc)
    idx = jnp.maximum(valid_len - 1, 0)
    last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
    return last, cache


def prefill(params, tokens, cfg: ModelConfig, cache: Dict, *,
            valid_len: Optional[jax.Array] = None,
            embed_override: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict]:
    """Run the prompt, fill the cache, return last-position logits."""
    if embed_override is not None:
        b, s = embed_override.shape[:2]
    else:
        b, s = tokens.shape[:2]
    if valid_len is None:
        valid_len = jnp.full((b,), s, jnp.int32)
    logits, aux = forward(params, tokens, cfg, embed_override=embed_override,
                          serve=True)
    cache = dict(cache, length=valid_len)
    if "kv" in aux:
        # aux["kv"]: list of stacked (L_seg, B, Hkv, S, hd) pairs
        k_all = jnp.concatenate([kv[0] for kv in _as_list(aux["kv"])], 0)
        v_all = jnp.concatenate([kv[1] for kv in _as_list(aux["kv"])], 0)
        kvc = cache["kv"]
        cache_size = kvc["k_q"].shape[3]
        if cache_size < s:
            # SWA ring cache: keep only the last `cache_size` positions.
            # They land at ring indices (s - C .. s - 1) mod C, which is a
            # contiguous [((s - C) % C) ..] rotation; for C | s it is 0..C-1.
            assert s % cache_size == 0, (s, cache_size)
            k_all = k_all[:, :, :, -cache_size:, :]
            v_all = v_all[:, :, :, -cache_size:, :]
        w = k_all.shape[3]
        s_k = qlib.absmax_scale(k_all, axis=(1, 2, 3, 4))   # (L,1,1,1,1)
        s_v = qlib.absmax_scale(v_all, axis=(1, 2, 3, 4))
        kvc = dict(
            kvc,
            k_q=kvc["k_q"].at[:, :, :, :w, :].set(qlib.quantize(k_all, s_k)),
            v_q=kvc["v_q"].at[:, :, :, :w, :].set(qlib.quantize(v_all, s_v)),
            scale_k=s_k, scale_v=s_v,
            length=valid_len)
        cache["kv"] = kvc
    if "ssm" in aux:
        cache = dict(cache, ssm=aux["ssm"])
    idx = jnp.maximum(valid_len - 1, 0)
    last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
    return last, cache


def _as_list(x):
    return x if isinstance(x, list) else [x]


def _zero_ssm_state(cfg: ModelConfig, batch: int) -> Dict:
    sc = cfg.ssm
    if sc.kind == "mamba1":
        return {"conv": jnp.zeros((batch, sc.d_conv - 1, cfg.d_inner),
                                  cfg.compute_dtype),
                "h": jnp.zeros((batch, cfg.d_inner, sc.d_state),
                               jnp.float32)}
    conv_c = cfg.d_inner + 2 * sc.d_state
    return {"conv": jnp.zeros((batch, sc.d_conv - 1, conv_c),
                              cfg.compute_dtype),
            "h": jnp.zeros((batch, cfg.d_inner // sc.headdim, sc.d_state,
                            sc.headdim), jnp.float32)}


def decode_step(params, token, cfg: ModelConfig, cache: Dict
                ) -> Tuple[jax.Array, Dict]:
    """token (B,) int32 -> logits (B, vocab_padded), updated cache."""
    b = token.shape[0]
    x = embed_tokens(params, token[:, None], cfg)       # (B, 1, d)
    if cfg.family == "hybrid":
        x, cache = _hybrid_decode(params, x, cfg, cache)
    else:
        segs = _layer_kinds(cfg)
        offset = 0
        for seg_params, (kind, n) in zip(params["segments"], segs):
            x, cache = _decode_segment(seg_params, x, cfg, kind, n, offset,
                                       cache)
            offset += n
        cache = dict(cache, length=cache["length"] + 1)
        if "kv" in cache:
            cache["kv"] = dict(cache["kv"], length=cache["kv"]["length"] + 1)
    logits = unembed(params, x, cfg)[:, 0]
    return logits, cache


def _decode_segment(seg_params, x, cfg, kind, n, offset, cache):
    """Scan one homogeneous segment in decode mode, updating cache slices."""

    if kind in ("dense", "moe"):
        kvc = cache["kv"]
        sl = slice(offset, offset + n)
        if "k_pages" in kvc:                       # paged block pool
            # The whole (L, N, ...) pools ride in the carry, and layer l
            # sees them as one (L*N, ...) pool whose block ids are offset by
            # l*N: the reshape is free and the append writes in place, so no
            # layer's pages are sliced out or copied back.  Layer l's own
            # block 0 is its trash block.
            shape = kvc["k_pages"].shape
            nb = shape[1]
            flat = (shape[0] * nb,) + shape[2:]

            def body(carry, xs):
                x, kp, vp = carry
                layer_params, s_k, s_v, layer = xs
                slice_ = {"kv": {"k_pages": kp, "v_pages": vp,
                                 "scale_k": s_k, "scale_v": s_v,
                                 "block_table": kvc["block_table"]
                                 + layer * nb,
                                 "length": kvc["length"]}}
                x, new_slice = _block_decode_paged(layer_params, x, slice_,
                                                   cfg, kind)
                nkv = new_slice["kv"]
                return (x, nkv["k_pages"], nkv["v_pages"]), None

            (x, kp, vp), _ = maybe_scan(
                body, (x, kvc["k_pages"].reshape(flat),
                       kvc["v_pages"].reshape(flat)),
                (seg_params, kvc["scale_k"][sl], kvc["scale_v"][sl],
                 jnp.arange(offset, offset + n)), cfg)
            cache = dict(cache, kv=dict(kvc, k_pages=kp.reshape(shape),
                                        v_pages=vp.reshape(shape)))
            return x, cache

        def body(x, xs):
            layer_params, k_q, v_q, s_k, s_v = xs
            slice_ = {"kv": {"k_q": k_q, "v_q": v_q,
                             "scale_k": s_k, "scale_v": s_v,
                             "length": kvc["length"]}}
            x, new_slice = _block_decode(layer_params, x, slice_, cfg, kind)
            nkv = new_slice["kv"]
            return x, (nkv["k_q"], nkv["v_q"])

        x, (k_q, v_q) = maybe_scan(
            body, x, (seg_params, kvc["k_q"][sl], kvc["v_q"][sl],
                      kvc["scale_k"][sl], kvc["scale_v"][sl]), cfg)
        cache = dict(cache, kv=dict(
            kvc,
            k_q=kvc["k_q"].at[sl].set(k_q),
            v_q=kvc["v_q"].at[sl].set(v_q)))
        return x, cache

    ssc = cache["ssm"]

    def body(x, xs):
        layer_params, conv, h = xs
        slice_ = {"ssm": {"conv": conv, "h": h}}
        x, new_slice = _block_decode(layer_params, x, slice_, cfg, kind)
        st = new_slice["ssm"]
        return x, (st["conv"], st["h"])

    sl = slice(offset, offset + n)
    x, (conv, h) = maybe_scan(body, x,
                              (seg_params, ssc["conv"][sl], ssc["h"][sl]),
                              cfg)
    cache = dict(cache, ssm=dict(ssc,
                                 conv=ssc["conv"].at[sl].set(conv),
                                 h=ssc["h"].at[sl].set(h)))
    return x, cache


def verify_step(params, tokens, cfg: ModelConfig, cache: Dict
                ) -> Tuple[jax.Array, Dict]:
    """Speculative verify: tokens (B, T) -> logits (B, T, vocab_padded).

    The paged-cache, T-token twin of :func:`decode_step`: every layer
    appends all T tokens' K/V through the block table and runs the fused
    verify attention with per-token causal lengths, so ``logits[:, t]`` is
    bitwise what ``decode_step`` would have produced after accepting
    ``tokens[:, :t+1]``.  The cache comes back T tokens longer; the
    scheduler truncates it to the accepted prefix via
    ``paged_kv.truncate_lengths``.  Paged dense/moe families only.

    The T tokens travel as B*T rows of one token each, decode's own
    ``(rows, 1, d)`` activation shape, and meet only inside the attention
    kernel.  With a ``(B, T, d)`` activation XLA compiles the projections,
    RoPE, MLP and LM head into other fusions than decode's, which on TPU
    round some rows differently and break the bitwise contract even with
    ``steps.EXACT_ROUNDING``.  For the same reason the optimization
    barriers keep XLA from folding the ``(B*T, ...) -> (B, T, ...)``
    reshapes back into those fusions.
    """
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"speculative verify supports paged dense/moe, not {cfg.family}")
    b, t = tokens.shape
    x = embed_tokens(params, tokens.reshape(b * t, 1), cfg)   # (B*T, 1, d)
    segs = _layer_kinds(cfg)
    offset = 0
    for seg_params, (kind, n) in zip(params["segments"], segs):
        x, cache = _verify_segment(seg_params, x, cfg, kind, n, offset,
                                   cache)
        offset += n
    cache = dict(cache, length=cache["length"] + t)
    cache["kv"] = dict(cache["kv"], length=cache["kv"]["length"] + t)
    logits = jax.lax.optimization_barrier(unembed(params, x, cfg))
    return logits.reshape(b, t, -1), cache


def _verify_segment(seg_params, x, cfg, kind, n, offset, cache):
    """Scan one dense/moe segment in T-token verify mode (paged pool)."""
    kvc = cache["kv"]
    if "k_pages" not in kvc:
        raise NotImplementedError("speculative verify needs the paged cache")
    sl = slice(offset, offset + n)

    def body(x, xs):
        layer_params, kp, vp, s_k, s_v = xs
        slice_ = {"kv": {"k_pages": kp, "v_pages": vp,
                         "scale_k": s_k, "scale_v": s_v,
                         "block_table": kvc["block_table"],
                         "length": kvc["length"]}}
        x, new_slice = _block_verify_paged(layer_params, x, slice_, cfg,
                                           kind)
        nkv = new_slice["kv"]
        return x, (nkv["k_pages"], nkv["v_pages"])

    x, (kp, vp) = maybe_scan(
        body, x, (seg_params, kvc["k_pages"][sl], kvc["v_pages"][sl],
                  kvc["scale_k"][sl], kvc["scale_v"][sl]), cfg)
    cache = dict(cache, kv=dict(
        kvc,
        k_pages=kvc["k_pages"].at[sl].set(kp),
        v_pages=kvc["v_pages"].at[sl].set(vp)))
    return x, cache


def _hybrid_decode(params, x, cfg, cache):
    x0 = x
    groups = cfg.n_layers // cfg.hybrid_attn_every
    per = cfg.hybrid_attn_every
    sp = params["shared_attn"]
    norm = _norm(cfg)
    kvc = cache["kv"]
    ssc = cache["ssm"]
    new_k, new_v, new_conv, new_h = [], [], [], []
    for g in range(groups):
        h = jnp.concatenate([x, x0], axis=-1)
        h = norm(sp["norm"], h)
        slice_ = {"k_q": kvc["k_q"][g], "v_q": kvc["v_q"][g],
                  "scale_k": kvc["scale_k"][g], "scale_v": kvc["scale_v"][g],
                  "length": kvc["length"]}
        out, nkv = A.attn_block_decode(sp["attn"], h, slice_, cfg)
        new_k.append(nkv["k_q"])
        new_v.append(nkv["v_q"])
        x = x + out
        h = norm(sp["mlp_norm"], x)
        x = x + M.mlp_apply(sp["mlp"], h, cfg)
        gp = jax.tree.map(lambda a: a[g], params["mamba_groups"])

        def body(x, xs):
            layer_params, conv, hst = xs
            slice_ = {"ssm": {"conv": conv, "h": hst}}
            x, ns = _block_decode(layer_params, x, slice_, cfg, "mamba2")
            return x, (ns["ssm"]["conv"], ns["ssm"]["h"])

        sl = slice(g * per, (g + 1) * per)
        x, (conv, hst) = maybe_scan(
            body, x, (gp, ssc["conv"][sl], ssc["h"][sl]), cfg)
        new_conv.append(conv)
        new_h.append(hst)
    cache = dict(
        cache,
        length=cache["length"] + 1,
        kv=dict(kvc, k_q=jnp.stack(new_k), v_q=jnp.stack(new_v),
                length=kvc["length"] + 1),
        ssm=dict(ssc, conv=jnp.concatenate(new_conv, 0),
                 h=jnp.concatenate(new_h, 0)))
    return x, cache


# ---------------------------------------------------------------------------
# parameter counting (roofline bookkeeping)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Analytic parameter count; MoE ``active_only`` counts shared + top-k."""
    d, hd = cfg.d_model, cfg.hd
    attn_p = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads) + hd * cfg.n_heads * d
    mlp_p = d * cfg.d_ff * (3 if cfg.act == "silu" else 2)
    embed = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)

    def norm_p():
        return {"rmsnorm": d, "layernorm": 2 * d, "nonparam_ln": 0}[cfg.norm]

    if cfg.family == "dense":
        per_layer = attn_p + mlp_p + 2 * norm_p()
        return embed + cfg.n_layers * per_layer + norm_p()

    if cfg.family == "moe":
        mc = cfg.moe
        routed = 3 * d * mc.d_ff_expert
        n_routed = mc.top_k if active_only else mc.n_experts
        shared = 3 * d * mc.d_ff_expert * mc.n_shared
        router = d * mc.n_experts
        moe_layer = attn_p + routed * n_routed + shared + router + 2 * norm_p()
        dense_layer = attn_p + mlp_p + 2 * norm_p()
        fd = mc.first_dense_layers
        return (embed + fd * dense_layer
                + (cfg.n_layers - fd) * moe_layer + norm_p())

    if cfg.family == "ssm":
        sc = cfg.ssm
        di, n = cfg.d_inner, sc.d_state
        if sc.kind == "mamba1":
            dt_rank = sc.dt_rank or max(d // 16, 1)
            per = (d * 2 * di + sc.d_conv * di + di * (dt_rank + 2 * n)
                   + dt_rank * di + di + di * n + di + di * d)
        else:
            nh = di // sc.headdim
            per = (d * (2 * di + 2 * n + nh) + sc.d_conv * (di + 2 * n)
                   + 3 * nh + di + di * d)
        return embed + cfg.n_layers * (per + norm_p()) + norm_p()

    if cfg.family == "hybrid":
        sc = cfg.ssm
        di, n = cfg.d_inner, sc.d_state
        nh = di // sc.headdim
        per = (d * (2 * di + 2 * n + nh) + sc.d_conv * (di + 2 * n)
               + 3 * nh + di + di * d + norm_p())
        shared = (2 * d * hd * cfg.n_heads + 2 * d * hd * 2 * cfg.n_kv_heads
                  + hd * cfg.n_heads * d + mlp_p + 3 * norm_p())
        return embed + cfg.n_layers * per + shared + norm_p()

    if cfg.family == "encdec":
        n_enc = cfg.n_encoder_layers or cfg.n_layers
        enc_layer = attn_p + mlp_p + 2 * norm_p()
        dec_layer = 2 * attn_p + mlp_p + 3 * norm_p()
        return (embed + n_enc * enc_layer + cfg.n_layers * dec_layer
                + 2 * norm_p())

    raise ValueError(cfg.family)
