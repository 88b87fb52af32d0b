"""Dense/MoE cache engine: the int8 paged KV block pool.

A straight extraction of the paged half of the original monolithic
scheduler: same jitted executables with the same donation structure, same
allocator decisions in the same order, so the refactor is bitwise
behavior-preserving for the dense/MoE serving path (pinned by
``tests/test_overcommit.py`` / ``tests/test_speculative.py`` running
unmodified against this engine).

``cover_extra`` generalizes the admission coverage: the plain scheduler
admits with coverage for ``prompt + 1`` (this step's decode write); the
speculative scheduler needs ``prompt + gamma`` (the unaccepted draft tail
briefly occupies blocks before rollback) and extra jitted steps
(``truncate_step`` / ``rollback_step``) that the plain path never traces —
they are built lazily on first use.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import paged_kv
from repro.launch import steps as st
from repro.launch.engines import base
from repro.models import transformer as T


class PagedKVEngine(base.CacheEngine):
    pool_tag = "kv"

    def __init__(self, params, cfg, prompts: List[np.ndarray], *,
                 slots: int, max_len: int, block_k: int = 32,
                 pool_blocks: Optional[int] = None, cover_extra: int = 1):
        assert cfg.family in ("dense", "moe"), cfg.family
        self.family = cfg.family
        self.params = params
        self.cfg = cfg
        self.prompts = prompts
        self.slots = slots
        self.max_len = max_len
        self.block_k = block_k
        self.cover_extra = cover_extra
        self.bps = paged_kv.blocks_per_seq(max_len, block_k)
        if pool_blocks is not None and pool_blocks < 1 + self.bps:
            raise ValueError(
                f"pool_blocks={pool_blocks} cannot hold one sequence: need "
                f">= 1 + {self.bps} (trash + blocks_per_seq("
                f"max_len={max_len}))")
        self.pool_size = (pool_blocks if pool_blocks is not None
                          else 1 + slots * self.bps)
        self.alloc: Optional[paged_kv.BlockAllocator] = None
        self.pager: Optional[base.PoolManager] = None
        self.calib_rid: Optional[int] = None

        # every step that rewrites the cache donates it — the pool is the
        # big buffer and must never be copied; slot indices are traced
        # arrays so one executable serves every slot (a Python-int index
        # would bake the slot into the jaxpr and recompile per value).  The
        # calibrating and plain per-slot prefills are distinct executables;
        # each request is resumed through the same one that first admitted
        # it, which (same executable, same inputs) is what makes re-prefill
        # bitwise reproducible.
        self.calib_prefill = jax.jit(
            st.make_paged_prefill_step(cfg, calibrate=True),
            donate_argnums=(2,))
        self.slot_prefill = jax.jit(
            st.make_paged_prefill_step(cfg, calibrate=False),
            donate_argnums=(2,))
        self.decode_step = jax.jit(st.make_decode_step(cfg),
                                   donate_argnums=(2,),
                                   compiler_options=st.EXACT_ROUNDING)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def release_step(cache, slot):
            cache = dict(cache, length=cache["length"].at[slot].set(0))
            if "kv" in cache:
                cache["kv"] = paged_kv.release_slot(cache["kv"], slot)
            return cache

        @functools.partial(jax.jit, donate_argnums=(0,))
        def grow_step(cache, slot, idx, block):
            kv = cache["kv"]
            return dict(cache, kv=dict(
                kv, block_table=kv["block_table"].at[slot, idx].set(block)))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def truncate_step(cache, new_lens):
            cache = dict(cache, length=new_lens)
            cache["kv"] = paged_kv.truncate_lengths(cache["kv"], new_lens)
            return cache

        @functools.partial(jax.jit, donate_argnums=(0,))
        def rollback_step(cache, slot, new_len):
            # block-level rollback: trash the tail table entries past
            # new_len (the host frees the ids via paged_kv.tail_blocks)
            cache = dict(cache, length=cache["length"].at[slot].set(new_len))
            cache["kv"] = paged_kv.rollback_slot(cache["kv"], slot, new_len)
            return cache

        self.release_step = release_step
        self.grow_step = grow_step
        self.truncate_step = truncate_step
        self.rollback_step = rollback_step

    # ---- scheduler hooks ------------------------------------------------

    def make_cache(self):
        return T.make_paged_cache(self.cfg, self.slots, self.max_len,
                                  block_k=self.block_k,
                                  num_blocks=self.pool_size)

    def start_run(self):
        self.alloc = paged_kv.BlockAllocator(self.pool_size)
        self.pager = base.PoolManager(self.alloc, self.bps, self.block_k)
        self.calib_rid = None
        return self.make_cache()

    def warmup(self):
        # compile every trace against a scratch cache (donated
        # step-to-step); the scratch pool uses the same num_blocks so the
        # executables match
        w_cache = self.make_cache()
        w_row = np.full((self.bps,), paged_kv.TRASH_BLOCK, np.int32)
        w_row[:1] = 1
        w_prompt = jnp.asarray(self.prompts[0])[None]
        w_sid = jnp.asarray([0], jnp.int32)
        w_rowj = jnp.asarray(w_row[None], jnp.int32)
        _, w_cache = self.calib_prefill(self.params, w_prompt, w_cache,
                                        w_sid, w_rowj)
        w_l1, w_cache = self.slot_prefill(self.params, w_prompt, w_cache,
                                          w_sid, w_rowj)
        w_cache = self.grow_step(w_cache, jnp.int32(0), jnp.int32(1),
                                 jnp.int32(2))
        w_tok = jnp.zeros((self.slots,), jnp.int32)
        w_out, w_cache = self.decode_step(self.params, w_tok, w_cache)
        w_cache = self.release_step(w_cache, jnp.int32(0))
        jax.block_until_ready(w_out)
        return w_l1, w_out

    def admission_need(self, rid: int) -> int:
        return paged_kv.blocks_per_seq(
            len(self.prompts[rid]) + self.cover_extra, self.block_k)

    def admit(self, cache, slot: int, rid: int):
        row = self.pager.admit_row(
            slot, len(self.prompts[rid]) + self.cover_extra)
        if self.calib_rid is None:
            self.calib_rid = rid
        fn = self.calib_prefill if rid == self.calib_rid else \
            self.slot_prefill
        return fn(self.params, jnp.asarray(self.prompts[rid])[None], cache,
                  jnp.asarray([slot], jnp.int32),
                  jnp.asarray(row[None], jnp.int32))

    def short(self, slot: int, upto: int) -> int:
        return self.pager.short(slot, upto)

    def grow_blocks(self, slot: int, n: int):
        return self.pager.grow(slot, n)

    def grow_write(self, cache, slot: int, idx: int, block: int):
        return self.grow_step(cache, jnp.int32(slot), jnp.int32(idx),
                              jnp.int32(block))

    def decode(self, tokens, cache):
        return self.decode_step(self.params, tokens, cache)

    def release(self, cache, slot: int):
        self.pager.release(slot)
        return self.release_step(cache, jnp.int32(slot))

    def finalize(self, health, inj) -> None:
        inj.drain(self.alloc)
        health.pool(self.pool_tag, self.alloc)

    def leaked(self) -> int:
        return self.alloc.live_count

    def kv_bytes_per_step(self, gens) -> int:
        # analytic decode-read traffic (int8 K+V, mean live-block occupancy)
        nl = self.cfg.n_layers
        prompt_len = len(self.prompts[0])
        mean_gen = sum(gens) // (2 * len(gens))
        mean_blocks = paged_kv.blocks_per_seq(prompt_len + mean_gen,
                                              self.block_k)
        return (2 * nl * self.slots * self.cfg.n_kv_heads * mean_blocks
                * self.block_k * self.cfg.hd)
