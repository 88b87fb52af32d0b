"""jit-able train / serve steps, shared by the trainer, server and dry-run."""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist import compression as comp
from repro.models import encdec as E
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import adamw


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  logical_vocab: int) -> jax.Array:
    """Mean next-token CE over the *logical* vocab (padding lanes masked).

    logits: (B, S, V_padded) any float dtype; statistics in f32.

    Sharding-aware formulation: the vocab dim is model-sharded, so the gold
    logit is picked with a fused one-hot contraction (partial-sum + psum,
    bytes ~ B*S) instead of ``take_along_axis`` (which would all-gather the
    full (B,S,V) logits — 13 GiB/chip at deepseek-67b scale; observed in the
    first dry-run's collective term).  The padding lanes are masked with an
    iota compare, also elementwise-shardable.
    """
    vp = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vp != logical_vocab:
        lane = jax.lax.broadcasted_iota(jnp.int32, (vp,), 0)
        logits = jnp.where(lane >= logical_vocab, -1e30, logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, vp, dtype=logits.dtype)
    gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
    return jnp.mean(lse - gold)


def loss_fn(params, batch: Dict, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    if cfg.family == "encdec":
        logits, aux = E.forward(params, batch, cfg)
    else:
        logits, aux = T.forward(params, batch["tokens"], cfg)
    ce = cross_entropy(logits, batch["labels"], cfg.vocab_size)
    loss = ce + aux.get("aux_loss", 0.0) + aux.get("z_loss", 0.0)
    metrics = {"loss": loss, "ce": ce,
               "aux_loss": aux.get("aux_loss", jnp.float32(0)),
               "z_loss": aux.get("z_loss", jnp.float32(0))}
    return loss, metrics


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt_cfg: adamw.OptimizerConfig):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    Gradients are implicitly mean-reduced across the DP axes by GSPMD (the
    loss is a mean over the batch dim, which is sharded over data/pod); the
    int8 error-feedback variant is :func:`make_compressed_train_step`
    (``launch/train.py --compress-grads``); the hierarchical inter-pod
    shard_map reduce is a ROADMAP item.
    """

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, cfg)
        params, opt_state, opt_metrics = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        metrics.update(opt_metrics)
        return params, opt_state, metrics

    return train_step


def make_compressed_train_step(cfg: ModelConfig,
                               opt_cfg: adamw.OptimizerConfig):
    """(params, opt_state, err, batch) -> (params, opt_state, err, metrics).

    Like :func:`make_train_step` but the gradient passes through the int8
    error-feedback pipe (``repro.dist.compression``) before the optimizer:
    the update is computed from ``dequant(quant(g + e))`` and the residual
    ``e`` carries to the next step.  Cross-device mean-reduction stays with
    GSPMD (``axis_name=None``); the pipe applies the exact wire-format
    numerics, so convergence under compression is what this step measures.
    ``err`` comes from ``repro.dist.compression.init_error(params)``.
    """
    def train_step(params, opt_state, err, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, cfg)
        grads, err = comp.compressed_psum(grads, err, axis_name=None)
        params, opt_state, opt_metrics = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        metrics.update(opt_metrics)
        return params, opt_state, err, metrics

    return train_step


def make_grad_accum_train_step(cfg: ModelConfig,
                               opt_cfg: adamw.OptimizerConfig):
    """Microbatched variant: batch has a leading accum dim (A, B/A, S)."""

    def train_step(params, opt_state, batch):
        def micro(carry, mb):
            gsum, lsum = carry
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb, cfg)
            return (jax.tree.map(jnp.add, gsum, g), lsum + loss), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (gsum, lsum), _ = jax.lax.scan(micro, (zeros, jnp.float32(0)), batch)
        n = opt_cfg.accum_steps
        grads = jax.tree.map(lambda g: g / n, gsum)
        params, opt_state, opt_metrics = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        opt_metrics["loss"] = lsum / n
        return params, opt_state, opt_metrics

    return train_step


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, cache_len: int):
    """tokens (B,S) [+frames] -> (last_logits, cache)."""

    if cfg.family == "encdec":
        def prefill_step(params, batch):
            b, s = batch["tokens"].shape
            cache = E.make_cache(cfg, b, cache_len,
                                 enc_len=batch["frames"].shape[1])
            return E.prefill(params, batch["frames"], batch["tokens"], cfg,
                             cache)
        return prefill_step

    def prefill_step(params, batch):
        tokens = batch["tokens"]
        b = tokens.shape[0]
        cache = T.make_cache(cfg, b, cache_len)
        return T.prefill(params, tokens, cfg, cache)

    return prefill_step


def make_paged_prefill_step(cfg: ModelConfig, *, calibrate: bool):
    """(params, tokens (B,S), cache, slot_ids (B,), block_ids (B, mb))
    -> (last_logits, cache).

    The per-slot admission primitive for the paged serving path: writes only
    the named slots' blocks/table rows, so admitting one request never
    re-prefills the rest of the batch.  ``calibrate`` is static: the first
    wave fixes the pool's per-layer scales, admissions reuse them.
    ``make_decode_step`` already handles paged caches transparently.

    The encdec variant takes the encoder frames too:
    (params, frames (B,S_enc,d), tokens, cache, slot_ids, block_ids).
    """
    if cfg.family == "encdec":
        def prefill_step(params, frames, tokens, cache, slot_ids, block_ids):
            return E.prefill_paged(params, frames, tokens, cfg, cache,
                                   slot_ids, block_ids, calibrate=calibrate)
        return prefill_step

    def prefill_step(params, tokens, cache, slot_ids, block_ids):
        return T.prefill_paged(params, tokens, cfg, cache, slot_ids,
                               block_ids, calibrate=calibrate)

    return prefill_step


# ``jax.jit(..., compiler_options=EXACT_ROUNDING)`` for decode and verify,
# which must give a token the same K/V and logits bit for bit.  Their
# programs fuse differently, and by default XLA lets a fusion keep a bf16
# value in f32 ("excess precision"): on TPU the two programs then round the
# same token's K/V and logits differently.  Without it every bf16 value is
# rounded where the program says, whatever the fusion.
EXACT_ROUNDING = {"xla_allow_excess_precision": False}


def make_decode_step(cfg: ModelConfig):
    """(params, token (B,), cache) -> (logits (B, V), cache)."""

    if cfg.family == "encdec":
        def decode_step(params, token, cache):
            # trace-time dispatch on the cache layout: the paged serving
            # path carries the carved cross region's block table
            if "cross_table" in cache:
                return E.decode_step_paged(params, token, cfg, cache)
            return E.decode_step(params, token, cfg, cache)
        return decode_step

    def decode_step(params, token, cache):
        return T.decode_step(params, token, cfg, cache)

    return decode_step


def make_verify_step(cfg: ModelConfig):
    """(params, tokens (B,T), cache) -> (logits (B,T,V), cache).

    The speculative target step: one fused multi-token launch whose
    ``logits[:, t]`` is bitwise what ``make_decode_step`` would have
    produced after accepting ``tokens[:, :t+1]`` (paged caches only), when
    both are compiled with :data:`EXACT_ROUNDING`.
    """
    assert cfg.family != "encdec", "speculative serving is decoder-only"

    def verify_step(params, tokens, cache):
        return T.verify_step(params, tokens, cfg, cache)

    return verify_step


def make_draft_loop(cfg: ModelConfig, gamma: int):
    """(params, token (B,), cache) -> (drafts (B, gamma), cache).

    The drafter's gamma greedy decode steps fused into one ``lax.scan`` so
    a whole draft burst is a single jitted launch — on launch-bound hosts
    that is the difference between speculative decoding paying for itself
    and losing to per-step dispatch overhead.  ``drafts[:, 0]`` is the
    drafter's continuation of ``token``; the cache comes back gamma tokens
    longer and is truncated by the scheduler after verification.
    """
    assert cfg.family != "encdec", "speculative serving is decoder-only"

    def draft_loop(params, token, cache):
        def body(carry, _):
            tok, cache = carry
            logits, cache = T.decode_step(params, tok, cfg, cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, cache), nxt

        (_, cache), drafts = jax.lax.scan(body, (token, cache), None,
                                          length=gamma)
        return drafts.T, cache                      # (B, gamma)

    return draft_loop


def init_params_fn(cfg: ModelConfig):
    if cfg.family == "encdec":
        return functools.partial(E.init_params, cfg=cfg)
    return functools.partial(T.init_params, cfg=cfg)
