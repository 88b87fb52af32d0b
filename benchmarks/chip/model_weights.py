"""Random weights of a dense decoder, made from the seed by the benchmark.

Each tensor is a pure function of ``(seed, layer, name)``, so the program's
parameters can be made in one jitted call on the device and the reference
can make the same tensors again one layer at a time, after the program's
state is freed.  The reference takes nothing the program made.

Scales follow the usual small-init recipe: inputs to a projection of width
``n`` draw with std ``n ** -0.5``, the two residual outputs (attention out,
MLP down) are further divided by ``sqrt(2 * n_layers)``, embeddings draw
with std 0.02, and RMSNorm gains are ``1 + 0.1 * N(0, 1)`` so that a gain
that is dropped or misplaced shows.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes a dense decoder is run at, from ``configs/<name>.json``."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    norm: str               # "rmsnorm" | "nonparam_ln"
    norm_eps: float
    rope_theta: float
    tie_embeddings: bool

    @classmethod
    def from_config(cls, conf: Dict) -> "Model":
        m = conf["model"]
        sections = sorted(k for k, v in m.items() if isinstance(v, dict))
        if sections:
            raise ValueError(f"a dense model has no sections: {sections}")
        if m["act"] != "silu":
            raise ValueError("the reference implements SwiGLU only")
        return cls(**{f.name: (conf["norm_eps"] if f.name == "norm_eps"
                               else m[f.name])
                      for f in dataclasses.fields(cls)})


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (all of its bits count)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    for word in range(1, 3):
        key = jax.random.fold_in(key, (seed >> (32 * word)) & 0xFFFFFFFF)
    return key


def _normal(key, i, shape, std):
    return jax.random.normal(jax.random.fold_in(key, i), shape,
                             jnp.float32) * std


def layer_weights(key, layer, m: Model) -> Dict[str, jax.Array]:
    """One decoder layer's f32 tensors; ``layer`` may be traced."""
    k = jax.random.fold_in(key, 1000 + layer)
    d, hq, hkv, hd, ff = (m.d_model, m.n_heads, m.n_kv_heads, m.head_dim,
                          m.d_ff)
    res = (2 * m.n_layers) ** -0.5
    w = {
        "wq": _normal(k, 0, (d, hq * hd), d ** -0.5),
        "wk": _normal(k, 1, (d, hkv * hd), d ** -0.5),
        "wv": _normal(k, 2, (d, hkv * hd), d ** -0.5),
        "wo": _normal(k, 3, (hq * hd, d), (hq * hd) ** -0.5 * res),
        "w_gate": _normal(k, 4, (d, ff), d ** -0.5),
        "w_in": _normal(k, 5, (d, ff), d ** -0.5),
        "w_out": _normal(k, 6, (ff, d), ff ** -0.5 * res),
    }
    if m.norm == "rmsnorm":
        w["norm1"] = 1.0 + _normal(k, 7, (d,), 0.1)
        w["norm2"] = 1.0 + _normal(k, 8, (d,), 0.1)
    return w


def embed_table(key, m: Model) -> jax.Array:
    return _normal(key, 0, (m.vocab_size, m.d_model), 0.02)


def head_weights(key, m: Model) -> Dict[str, jax.Array]:
    """Final-norm gain and, when untied, the LM head (d, V)."""
    out = {}
    if m.norm == "rmsnorm":
        out["final_norm"] = 1.0 + _normal(key, 1, (m.d_model,), 0.1)
    if not m.tie_embeddings:
        out["lm_head"] = _normal(key, 2, (m.d_model, m.vocab_size),
                                 m.d_model ** -0.5)
    return out


def program_params(key, m: Model, shapes) -> Dict:
    """The same tensors in the program's dense parameter layout
    (``repro.models.transformer.init_params``), cast to the dtype of each
    leaf of ``shapes`` (its ``jax.eval_shape``).  Jit it: one call makes
    every weight on the device."""
    layers = jax.vmap(lambda l: layer_weights(key, l, m))(
        jnp.arange(m.n_layers))

    def gain(name):
        return {"scale": layers[name]} if name in layers else {}

    head = head_weights(key, m)
    tree = {
        "embed": {"table": embed_table(key, m)},
        "segments": [{
            "norm1": gain("norm1"),
            "attn": {n: {"w": layers[n]} for n in ("wq", "wk", "wv", "wo")},
            "norm2": gain("norm2"),
            "mlp": {n: {"w": layers[n]} for n in ("w_in", "w_out",
                                                  "w_gate")},
        }],
        "final_norm": ({"scale": head["final_norm"]} if "final_norm" in head
                       else {}),
    }
    if "lm_head" in head:
        tree["lm_head"] = {"w": head["lm_head"]}
    want = jax.tree.structure(shapes)
    if jax.tree.structure(tree) != want:
        raise ValueError(f"program parameter layout changed: {want}")
    return jax.tree.map(lambda a, s: _fit(a, s, m.vocab_size), tree, shapes)


def _fit(a, s, vocab: int):
    """Cast to the program's dtype.  The program pads the vocabulary up to
    a multiple of 256; the rows (columns) no model has are zero, as a
    checkpoint loader leaves them, so their logits never come first."""
    pad = [(0, t - n) for n, t in zip(a.shape, s.shape)]
    if len(a.shape) != len(s.shape) or any(
            p and (p < 0 or n != vocab) for n, (_, p) in zip(a.shape, pad)):
        raise ValueError(f"program parameter shape {s.shape} != {a.shape}")
    return jnp.pad(a, pad).astype(s.dtype)
