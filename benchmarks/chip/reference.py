"""Plain float32 reference of the dense decoder family, and its control.

The published architecture in straightforward ``jax.numpy``: token
embedding; per layer a pre-norm (OLMo's non-parametric LayerNorm or
RMSNorm with a gain), Q/K/V projections without bias, rotate-half RoPE,
causal softmax attention with grouped K/V heads (query head ``h`` reads K/V
head ``h // (n_heads / n_kv_heads)``), the output projection, a second
pre-norm and a SwiGLU MLP, ``x + attn`` and ``x + mlp`` residuals; a final
norm and the LM head (the embedding's transpose when tied).  No cache, no
kernel, no batching: a whole sequence at once, one layer at a time, with
each layer's weights made again from the seed (``model_weights``).  It
imports nothing of the program.  Matmuls run at ``highest`` precision.

Attention is computed in blocks of query rows so that the longest cells
fit.  A sequence is padded up to a multiple of ``BUCKET`` tokens; causal
masking keeps the padding out of every real position, and the bucket
keeps the number of compiled shapes small.

``control=True`` computes the same forward one step below the precision
the configurations state (bf16 matmuls, int8 K/V): every matmul operand
rounded to float8 e4m3 with a per-tensor scale, and K/V rounded to int4
with a per-layer scale.  It stands in for the program to show that the
correctness check fails such a run.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import model_weights as mw

BUCKET = 512
Q_BLOCK = 512
HEAD_ROWS = 256
HI = jax.lax.Precision.HIGHEST


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _int4(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 7.0
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _mm(x, w, control):
    if control:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HI)


def _norm(x, gain, m: mw.Model):
    if m.norm == "nonparam_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + m.norm_eps)
    assert m.norm == "rmsnorm", m.norm
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + m.norm_eps) * gain


def _rope(x, theta):
    """x (S, H, D), positions 0..S-1, rotate-half pairing (i, i + D/2)."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    c, sn = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * sn, x1 * sn + x2 * c], -1)


def _attention(q, k, v):
    """Causal softmax attention; q (S, Hq, D), k/v (S, Hkv, D)."""
    s, hq, d = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(s)

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(d)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(rows, jnp.arange(s // Q_BLOCK))
    return out.reshape(s, hq, d)


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _layer(key, layer, x, m: mw.Model, control: bool):
    w = mw.layer_weights(key, layer, m)
    s = x.shape[0]
    hd = m.head_dim
    h = _norm(x, w.get("norm1"), m)
    q = _mm(h, w["wq"], control).reshape(s, m.n_heads, hd)
    k = _mm(h, w["wk"], control).reshape(s, m.n_kv_heads, hd)
    v = _mm(h, w["wv"], control).reshape(s, m.n_kv_heads, hd)
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    if control:
        k, v = _int4(k), _int4(v)
    o = _attention(q, k, v).reshape(s, m.n_heads * hd)
    x = x + _mm(o, w["wo"], control)
    h = _norm(x, w.get("norm2"), m)
    a = jax.nn.silu(_mm(h, w["w_gate"], control)) * _mm(h, w["w_in"],
                                                          control)
    return x + _mm(a, w["w_out"], control)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, m: mw.Model):
    return mw.embed_table(key, m)[tokens]


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _logits(key, h, m: mw.Model, control: bool):
    head = mw.head_weights(key, m)
    h = _norm(h, head.get("final_norm"), m)
    w = (mw.embed_table(key, m).T if m.tie_embeddings else head["lm_head"])
    return _mm(h, w, control)


@jax.jit
def _gaps(ref, served, ctrl):
    """Per row: how far the served token's, and the control's first
    token's, reference logit lies below the reference's best."""
    best = ref.max(-1)
    rows = jnp.arange(ref.shape[0])
    gap = best - ref[rows, served]
    ctrl_gap = best - ref[rows, jnp.argmax(ctrl, -1)]
    return gap, ctrl_gap


def _hidden(key, tokens: np.ndarray, m: mw.Model, control: bool):
    n = len(tokens)
    pad = -(-n // BUCKET) * BUCKET
    toks = np.zeros((pad,), np.int32)
    toks[:n] = tokens
    x = _embed(key, jnp.asarray(toks), m)
    for layer in range(m.n_layers):
        x = _layer(key, jnp.int32(layer), x, m, control)
    return x


def compare(seed: int, m: mw.Model, prompt: np.ndarray,
            served: np.ndarray, *, control: bool = False
            ) -> Dict[str, np.ndarray]:
    """Run the reference once over ``prompt + served[:-1]``.

    Returns, for each served token, ``gap``: the reference's best logit at
    that position minus the reference's logit of the served token (0 where
    they agree).  With ``control``, also ``ctrl_gap``: the same for the
    token that the control ranks first at each position.
    """
    with jax.default_matmul_precision("highest"):
        key = mw.base_key(seed)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        first = len(prompt) - 1
        n = len(served)
        hs = {c: _hidden(key, seq, m, c) for c in {False, control}}
        gaps, ctrl = [], []
        for i in range(0, n, HEAD_ROWS):
            pos = np.arange(first + i, first + i + HEAD_ROWS)
            pos = np.minimum(pos, len(seq) - 1)
            tok = np.zeros((HEAD_ROWS,), np.int32)
            tok[:min(HEAD_ROWS, n - i)] = served[i:i + HEAD_ROWS]
            ref = _logits(key, hs[False][pos], m, False)
            ctl = _logits(key, hs[True][pos], m, True) if control else ref
            g, c = jax.device_get(_gaps(ref, jnp.asarray(tok), ctl))
            k = min(HEAD_ROWS, n - i)
            gaps.append(g[:k])
            ctrl.append(c[:k])
        out = {"gap": np.concatenate(gaps)}
        if control:
            out["ctrl_gap"] = np.concatenate(ctrl)
        return out
