"""Family-blind continuous-batching scheduler over the CacheEngine protocol.

The control loop here is the serving scheduler extracted from the original
``launch/serve.py`` monolith, with every family-specific operation routed
through a :class:`repro.launch.engines.base.CacheEngine`:

  * :func:`run_schedule` — the plain (non-speculative) loop: admission via
    per-slot prefill, demand-paged growth, preemption under pool pressure,
    wall-clock and step deadlines, NaN retirement, fault injection, health
    recording.  One loop serves dense/MoE (`PagedKVEngine`), SSM
    (`SSMStateEngine`) and encoder-decoder (`EncDecEngine`) — the loop
    never mentions a family; engines with no allocator simply never see
    the paging branches.
  * :func:`run_speculative` — the draft/verify loop (greedy, paged
    dense/MoE only): structurally a two-pool lockstep specialization, kept
    as its own loop rather than forced through the single-engine protocol.

Preempt/resume is bitwise for greedy decoding on every engine (per-row
numerics are independent of slot index and co-residents; re-admission uses
the same prefill executable), and — via :class:`RequestKeys` — for sampled
decoding too: each request's sampling keys are derived from
``(sample_seed, rid, tokens_drawn)``, not from a shared key stream, so a
resumed request continues with exactly the keys it would have used.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import paged_kv
from repro.dist import straggler as strag
from repro.launch import faults as faults_mod
from repro.launch.engines import base as engines_base
from repro.launch.health import ServeHealth
from repro.models import transformer as T
from repro.launch import steps as st


def percentile(xs: List[float], p: float) -> float:
    return float(np.percentile(np.asarray(xs), p)) if xs else 0.0


def make_sampler(temperature: float, top_p: float, vocab_size: int):
    """Jitted token selector: logits (B, V_padded) + key(s) -> (tokens (B,),
    finite (B,)).

    ``temperature == 0`` is greedy argmax — the default, the only mode the
    speculative path supports (its acceptance rule compares against the
    target argmax), and bit-identical to the pre-sampling scheduler; the
    key argument is ignored.  Otherwise: temperature-scaled nucleus
    sampling with **per-row keys** ``(B, 2)`` (one PRNG key per slot, built
    by the scheduler from request id + tokens drawn); padding lanes are
    masked before the softmax so they can never be drawn.

    The second output is the NaN/Inf guard, computed on the *raw* logits in
    the same launch: a row that is not entirely finite produced a garbage
    token, and the scheduler retires that slot instead of serving it.
    """
    if temperature == 0.0:
        @jax.jit
        def greedy(logits, key):
            del key
            ok = jnp.isfinite(logits).all(axis=-1)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), ok
        return greedy

    @jax.jit
    def sample(logits, keys):
        ok = jnp.isfinite(logits).all(axis=-1)
        lg = logits.astype(jnp.float32) / temperature
        lane = jnp.arange(lg.shape[-1])
        lg = jnp.where(lane >= vocab_size, -jnp.inf, lg)
        if top_p < 1.0:
            srt = jnp.sort(lg, axis=-1)[:, ::-1]
            csum = jnp.cumsum(jax.nn.softmax(srt, axis=-1), axis=-1)
            # smallest prefix with mass >= top_p; the top token always stays
            keep = csum - jax.nn.softmax(srt, axis=-1) < top_p
            cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                             keepdims=True)
            lg = jnp.where(lg < cutoff, -jnp.inf, lg)
        toks = jax.vmap(jax.random.categorical)(keys, lg)
        return toks.astype(jnp.int32), ok

    return sample


class RequestKeys:
    """Per-request, count-addressed sampling keys.

    ``key(rid, drawn) = fold_in(fold_in(PRNGKey(seed), rid), drawn)`` —
    the key for a request's n-th sampled token is a pure function of the
    seed, the request id, and how many tokens the request has already
    drawn.  Nothing depends on scheduler history (admission order, slot
    index, co-residents, preemptions), which is what upgrades preempt/
    resume from a greedy-only bitwise contract to sampled runs too: a
    resumed request replays its recorded prefix and then continues with
    exactly the keys the uninterrupted run would have used.
    """

    def __init__(self, seed: int):
        self.base = jax.random.PRNGKey(seed)
        self._rid: Dict[int, jax.Array] = {}

    def key(self, rid: int, drawn: int) -> jax.Array:
        k = self._rid.get(rid)
        if k is None:
            k = self._rid[rid] = jax.random.fold_in(self.base, rid)
        return jax.random.fold_in(k, drawn)


def pick_victim(active: Dict[int, int], exclude: int, policy: str,
                admit_seq: Dict[int, int], remaining) -> Optional[int]:
    """Choose a slot to preempt under pool pressure.

    ``newest`` evicts the most recently admitted slot (FIFO fairness: the
    oldest requests finish first); ``longest`` evicts the slot with the most
    generation left (frees its blocks for the longest time).  ``exclude``
    is the grower itself — self-preemption is the caller's last resort when
    no other slot exists.
    """
    cands = [s for s in active if s != exclude]
    if not cands:
        return None
    if policy == "newest":
        return max(cands, key=lambda s: admit_seq[s])
    assert policy == "longest", policy
    return max(cands, key=lambda s: (remaining(s), admit_seq[s]))


def finalize_stats(stats: Dict, finished: Dict, t0: float) -> Dict:
    dt = time.time() - t0
    total = sum(len(v) for v in finished.values())
    step_s = stats.pop("step_s")
    stats.update(
        served=len(finished),
        total_tokens=total,
        wall_s=dt,
        tok_s=total / max(dt, 1e-9),
        p50_step_ms=percentile(step_s, 50) * 1e3,
        p99_step_ms=percentile(step_s, 99) * 1e3,
    )
    return stats


@functools.partial(jax.jit, donate_argnums=(0,))
def _splice_token(tokens, slot, token):
    return tokens.at[slot].set(token)


def run_schedule(engine: engines_base.CacheEngine,
                 prompts: List[np.ndarray], *, gens: Sequence[int],
                 temperature: float = 0.0, top_p: float = 1.0,
                 sample_seed: int = 0, preempt_policy: str = "newest",
                 deadline_steps: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 fault_plan: Optional["faults_mod.FaultPlan"] = None,
                 warmup: bool = False, repeats: int = 1,
                 verbose: bool = False) -> Dict:
    """Drive the family-blind continuous-batching loop over ``engine``.

    Engine-agnostic responsibilities live here: the request queue, slot
    occupancy, token selection (greedy or per-request-key sampling),
    preempt/resume snapshots and replay, step (``deadline_steps``) and
    wall-clock (``deadline_ms``) deadlines, fault hooks, health/straggler
    recording and the stats contract.  Everything cache-shaped goes
    through the engine.  When ``deadline_ms`` is set, admission picks the
    queued request with the least remaining budget first (earliest-
    deadline-first) instead of FIFO; victims still resume first.

    While the JAX profiler runs, the loop writes host spans into its trace,
    on the device planes' clock: ``sched.step`` per iteration (``step_num``;
    ``active``, the slots it decodes), ``sched.admit`` per admission
    (``rid``, ``slot``, ``prompt_len``) and ``sched.wait`` wherever the host
    blocks on a device value (``tokens``, the tokens the read brings to the
    host: the first token inside ``sched.admit``, one per decoded slot in a
    step).  A request's tokens reach the host as its waits end.
    """
    requests = len(prompts)
    slots = engine.slots
    gens = list(gens)
    assert len(gens) == requests
    sampler = make_sampler(temperature, top_p, engine.cfg.vocab_size)
    assert preempt_policy in ("newest", "longest"), preempt_policy

    if warmup:
        warm = engine.warmup()
        if warm is not None:
            w_l1, w_out = warm
            keys = RequestKeys(sample_seed)
            if temperature == 0.0:
                sampler(w_l1, keys.base)
                sampler(w_out, keys.base)
            else:
                sampler(w_l1, jnp.stack([keys.base]))
                sampler(w_out, jnp.stack([keys.base] * slots))
            w_tok = _splice_token(jnp.zeros((slots,), jnp.int32),
                                  jnp.int32(0), jnp.int32(0))
            jax.block_until_ready(w_tok)

    def _run() -> Dict:
        # fresh scheduler state per run; the engine's jitted steps are
        # shared, so repeats measure serving on warm executables
        cache = engine.start_run()
        alloc = engine.alloc
        paged = alloc is not None
        health = ServeHealth()
        inj = faults_mod.FaultInjector(fault_plan, health)
        watchdog = strag.StragglerWatchdog(window=50, threshold=3.0,
                                           min_history=4,
                                           on_straggler=health.straggler)
        keys = RequestKeys(sample_seed)

        def select(logits, rows):
            """rows: per-logit-row (rid, tokens_drawn), or None for a slot
            with no live request (its token is discarded)."""
            if temperature == 0.0:
                return sampler(logits, keys.base)    # key unused
            ks = jnp.stack([keys.base if r is None else keys.key(*r)
                            for r in rows])
            return sampler(logits, ks)

        stats: Dict = {"batch_prefills": 0, "slot_prefills": 0,
                       "decode_steps": 0, "step_s": []}
        queue = deque(range(requests))
        generated: Dict[int, List[int]] = {}
        finished: Dict[int, List[int]] = {}
        expired: Dict[int, List[int]] = {}
        failed: Dict[int, List[int]] = {}
        resume_prefix: Dict[int, List[int]] = {}
        replay: Dict[int, List[int]] = {}
        admit_step0: Dict[int, int] = {}    # first admission, for deadlines
        admit_t0: Dict[int, float] = {}     # wall clock of first admission
        admit_seq: Dict[int, int] = {}      # per-slot admission order
        active: Dict[int, int] = {}
        seq_counter = [0]
        tokens = jnp.zeros((slots,), jnp.int32)
        step = 0

        def free_slot(slot):
            nonlocal cache
            cache = engine.release(cache, slot)

        def preempt(vslot, *, reason):
            rid = active.pop(vslot)
            pre = generated.pop(rid) + replay.pop(rid, [])
            resume_prefix[rid] = pre
            free_slot(vslot)
            queue.appendleft(rid)           # victims resume first
            health.count("preemptions")
            health.event("preempt", step, rid=rid, slot=vslot,
                         policy=preempt_policy, reason=reason,
                         prefix_tokens=len(pre))
            if verbose:
                print(f"[serve] step {step}: preempted request {rid} "
                      f"(slot {vslot}, {reason})", flush=True)

        def budget_ms(rid, now):
            """Remaining wall-clock budget; full budget if never admitted."""
            if rid in admit_t0:
                return deadline_ms - (now - admit_t0[rid]) * 1e3
            return deadline_ms

        t0 = time.time()
        while active or queue:
            with jax.profiler.StepTraceAnnotation("sched.step",
                                                  step_num=step) as span:
                ts_iter = time.perf_counter()
                prefills0 = stats["slot_prefills"]
                preempts0 = health.counters["preemptions"]
                inj.on_step(step)
                if paged:
                    inj.squeeze_pool(step, alloc)
                fslot = inj.force_preempt(step)
                if fslot is not None and fslot in active:
                    preempt(fslot, reason="fault")

                # ---- growth: cover this step's write position for every
                # slot; on exhaustion, preempt a victim and retry ----------
                if paged:
                    for slot in list(sorted(active)):
                        if slot not in active:
                            continue        # preempted by an earlier grower
                        rid = active[slot]
                        upto = len(prompts[rid]) + len(generated[rid])
                        while engine.short(slot, upto) > 0:
                            try:
                                start, ids = engine.grow_blocks(
                                    slot, engine.short(slot, upto))
                            except paged_kv.BlockAllocationError as e:
                                health.event("pool_pressure", step,
                                             slot=slot, requested=e.requested,
                                             free=e.free, live=e.live,
                                             high_water=e.high_water)
                                victim = pick_victim(
                                    active, slot, preempt_policy, admit_seq,
                                    lambda s: gens[active[s]]
                                    - len(generated[active[s]]))
                                if victim is None:
                                    # sole active slot: park it in the
                                    # queue and wait for the pool (fault
                                    # hold) to drain
                                    preempt(slot, reason="self")
                                    break
                                preempt(victim, reason="growth")
                                continue
                            for j, b in enumerate(ids):
                                cache = engine.grow_write(cache, slot,
                                                          start + j, b)

                # ---- admission: fill idle slots from the queue -----------
                idle = [s for s in range(slots) if s not in active]
                while queue and idle:
                    if deadline_ms is None or len(queue) == 1:
                        qi = 0
                    else:
                        # earliest-deadline-first admission under --deadline-ms
                        now = time.perf_counter()
                        qi = min(range(len(queue)),
                                 key=lambda i: (budget_ms(queue[i], now), i))
                    rid = queue[qi]
                    # cover the prompt plus this step's decode write
                    need = engine.admission_need(rid)
                    if paged and alloc.free_count < need:
                        health.count("admission_stalls")
                        health.event("admission_stall", step, rid=rid,
                                     need=need, free=alloc.free_count)
                        break
                    del queue[qi]
                    slot = idle.pop(0)
                    with jax.profiler.TraceAnnotation(
                            "sched.admit", rid=rid, slot=slot,
                            prompt_len=len(prompts[rid])):
                        last1, cache = engine.admit(cache, slot, rid)
                        stats["slot_prefills"] += 1
                        health.count("admissions")
                        active[slot] = rid
                        admit_seq[slot] = seq_counter[0]
                        seq_counter[0] += 1
                        if rid in resume_prefix:
                            pre = resume_prefix.pop(rid)
                            generated[rid] = [pre[0]]
                            replay[rid] = pre[1:]
                            first = pre[0]
                            health.count("resumes")
                            health.count("resumed_tokens_replayed",
                                         len(pre) - 1)
                            health.event("resume", step, rid=rid, slot=slot,
                                         prefix_tokens=len(pre))
                        else:
                            admit_step0[rid] = step
                            admit_t0[rid] = time.perf_counter()
                            t1, ok1 = select(last1, [(rid, 0)])
                            with jax.profiler.TraceAnnotation("sched.wait",
                                                              tokens=1):
                                t1, ok1 = jax.device_get((t1, ok1))
                            if not ok1[0]:
                                failed[rid] = []
                                del active[slot]
                                free_slot(slot)
                                idle.insert(0, slot)
                                health.count("nan_retired")
                                health.event("nan_retired", step, rid=rid,
                                             slot=slot, where="prefill")
                                continue
                            first = int(t1[0])
                            generated[rid] = [first]
                        tokens = _splice_token(tokens, jnp.int32(slot),
                                               jnp.int32(first))

                if not active:
                    step += 1
                    if queue:
                        continue                # stalled; pool will drain
                    break

                # ---- decode one token per slot ---------------------------
                span.set_metadata(active=len(active))
                ts = time.perf_counter()
                logits, cache = engine.decode(tokens, cache)
                logits = inj.corrupt_logits(step, logits)
                rows: List = [None] * slots
                for slot, rid in active.items():
                    rows[slot] = (rid, len(generated[rid]))
                toks, okv = select(logits, rows)
                with jax.profiler.TraceAnnotation("sched.wait",
                                                  tokens=len(active)):
                    tok_host, ok_host = jax.device_get((toks, okv))
                stats["step_s"].append(time.perf_counter() - ts)
                stats["decode_steps"] += 1
                tokens = toks

                for slot in sorted(active):
                    rid = active[slot]
                    if not ok_host[slot]:
                        # NaN/Inf logits: retire the request, keep the batch up
                        failed[rid] = generated.pop(rid)
                        del active[slot]
                        replay.pop(rid, None)
                        free_slot(slot)
                        health.count("nan_retired")
                        health.event("nan_retired", step, rid=rid, slot=slot,
                                     where="decode")
                        continue
                    if replay.get(rid):
                        nxt = replay[rid].pop(0)
                        if not replay[rid]:
                            del replay[rid]
                        if nxt != int(tok_host[slot]):
                            # replay re-derives the recorded token (greedy by
                            # determinism, sampled by count-addressed keys);
                            # the splice is the safety net
                            tokens = _splice_token(tokens, jnp.int32(slot),
                                                   jnp.int32(nxt))
                    else:
                        nxt = int(tok_host[slot])
                    generated[rid].append(nxt)
                    if len(generated[rid]) >= gens[rid]:
                        finished[rid] = generated.pop(rid)
                        del active[slot]
                        replay.pop(rid, None)
                        free_slot(slot)
                    elif ((deadline_steps is not None
                           and step - admit_step0[rid] + 1 >= deadline_steps)
                          or (deadline_ms is not None
                              and (time.perf_counter() - admit_t0[rid]) * 1e3
                              >= deadline_ms)):
                        expired[rid] = generated.pop(rid)
                        del active[slot]
                        replay.pop(rid, None)
                        free_slot(slot)
                        health.count("deadline_cancelled")
                        health.event("deadline", step, rid=rid, slot=slot,
                                     tokens=len(expired[rid]))
                watchdog.observe(
                    step, time.perf_counter() - ts_iter,
                    expect_slow=(stats["slot_prefills"] != prefills0
                                 or health.counters["preemptions"]
                                 != preempts0))
                step += 1

        engine.finalize(health, inj)
        stats["leaked_blocks"] = engine.leaked()
        stats["finished"] = finished
        stats["expired"] = expired
        stats["failed"] = failed
        stats["preemptions"] = health.counters["preemptions"]
        stats["resumes"] = health.counters["resumes"]
        stats["health"] = health.to_dict()
        stats["health"]["straggler_summary"] = watchdog.summary()
        stats["kv_bytes_per_step"] = engine.kv_bytes_per_step(gens)
        return finalize_stats(stats, finished, t0)

    best = _run()
    for _ in range(repeats - 1):
        run = _run()
        if run["tok_s"] > best["tok_s"]:
            best = run
    return best


def run_speculative(params, cfg, prompts: List[np.ndarray], *, slots: int,
                    gen: int, gamma: int = 4,
                    draft=None, block_k: int = 32,
                    max_len: Optional[int] = None,
                    gens: Optional[Sequence[int]] = None,
                    pool_blocks: Optional[int] = None,
                    preempt_policy: str = "newest",
                    deadline_steps: Optional[int] = None,
                    fault_plan: Optional["faults_mod.FaultPlan"] = None,
                    warmup: bool = False, repeats: int = 1,
                    verbose: bool = False) -> Dict:
    """Greedy speculative scheduler (see ``serve.serve_speculative`` for the
    user-facing contract docs).  Dense/MoE paged caches only; kept as its
    own two-pool lockstep loop rather than forced through the single-engine
    protocol — the target and drafter block tables are grown, rolled back
    and released together, which no per-engine hook decomposition expresses
    without leaking the pairing into the protocol.
    """
    self_draft = draft is None
    draft_params, dcfg = draft if draft is not None else (params, cfg)
    assert cfg.family in ("dense", "moe"), cfg.family
    assert dcfg.family in ("dense", "moe"), dcfg.family
    assert dcfg.vocab_size == cfg.vocab_size, "drafter must share the vocab"
    requests = len(prompts)
    prompt_len = len(prompts[0])
    slots = min(slots, requests)
    gens = list(gens) if gens is not None else [gen] * requests
    assert len(gens) == requests
    if max_len is None:
        # +gamma: the cache briefly holds the unaccepted draft tail before
        # the post-verify truncation
        max_len = prompt_len + max(gens) + gamma + 8
    bps = paged_kv.blocks_per_seq(max_len, block_k)
    if pool_blocks is not None and pool_blocks < 1 + bps:
        raise ValueError(
            f"pool_blocks={pool_blocks} cannot hold one sequence: need "
            f">= 1 + {bps} (trash + blocks_per_seq(max_len={max_len}))")
    pool_size = pool_blocks if pool_blocks is not None else 1 + slots * bps
    assert preempt_policy in ("newest", "longest"), preempt_policy

    t_calib = jax.jit(st.make_paged_prefill_step(cfg, calibrate=True),
                      donate_argnums=(2,))
    t_slot = jax.jit(st.make_paged_prefill_step(cfg, calibrate=False),
                     donate_argnums=(2,))
    d_calib = d_slot = None
    if not self_draft:
        d_calib = jax.jit(st.make_paged_prefill_step(dcfg, calibrate=True),
                          donate_argnums=(2,))
        d_slot = jax.jit(st.make_paged_prefill_step(dcfg, calibrate=False),
                         donate_argnums=(2,))
    draft_loop = jax.jit(st.make_draft_loop(dcfg, gamma),
                         donate_argnums=(2,))
    verify_step = jax.jit(st.make_verify_step(cfg), donate_argnums=(2,),
                          compiler_options=st.EXACT_ROUNDING)

    @jax.jit
    def select_targets(vlogits):
        # argmax + finite-guard in one launch: a NaN anywhere in a slot's
        # verify logits retires that slot instead of emitting garbage
        return (jnp.argmax(vlogits, axis=-1).astype(jnp.int32),
                jnp.isfinite(vlogits).all(axis=(-1, -2)))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def truncate_step(cache, new_lens):
        cache = dict(cache, length=new_lens)
        cache["kv"] = paged_kv.truncate_lengths(cache["kv"], new_lens)
        return cache

    @functools.partial(jax.jit, donate_argnums=(0,))
    def release_step(cache, slot):
        cache = dict(cache, length=cache["length"].at[slot].set(0))
        cache["kv"] = paged_kv.release_slot(cache["kv"], slot)
        return cache

    @functools.partial(jax.jit, donate_argnums=(0,))
    def grow_step(cache, slot, idx, block):
        kv = cache["kv"]
        return dict(cache, kv=dict(
            kv, block_table=kv["block_table"].at[slot, idx].set(block)))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def rollback_step(cache, slot, new_len):
        # block-level rollback: trash the tail table entries past new_len
        # (the host frees the ids via paged_kv.tail_blocks)
        cache = dict(cache, length=cache["length"].at[slot].set(new_len))
        cache["kv"] = paged_kv.rollback_slot(cache["kv"], slot, new_len)
        return cache

    if warmup:
        w_cache = T.make_paged_cache(cfg, slots, max_len, block_k=block_k,
                                     num_blocks=pool_size)
        w_row = np.full((bps,), paged_kv.TRASH_BLOCK, np.int32)
        w_row[:1] = 1
        w_sid = jnp.asarray([0], jnp.int32)
        w_rowj = jnp.asarray(w_row[None], jnp.int32)
        w_prompt = jnp.asarray(prompts[0])[None]
        w_last, w_cache = t_calib(params, w_prompt, w_cache, w_sid, w_rowj)
        _, w_cache = t_slot(params, w_prompt, w_cache, w_sid, w_rowj)
        w_cache = grow_step(w_cache, jnp.int32(0), jnp.int32(1), jnp.int32(2))
        w_pend = jnp.argmax(w_last, -1).astype(jnp.int32)
        w_pend = jnp.broadcast_to(w_pend[0], (slots,))
        w_lens = jnp.zeros((slots,), jnp.int32).at[0].set(prompt_len)
        w_dcache = None
        if self_draft:
            w_drafts, w_cache = draft_loop(params, w_pend, w_cache)
            w_cache = truncate_step(w_cache, w_lens)
        else:
            w_dcache = T.make_paged_cache(dcfg, slots, max_len,
                                          block_k=block_k,
                                          num_blocks=pool_size)
            _, w_dcache = d_calib(draft_params, w_prompt, w_dcache, w_sid,
                                  w_rowj)
            _, w_dcache = d_slot(draft_params, w_prompt, w_dcache, w_sid,
                                 w_rowj)
            w_dcache = grow_step(w_dcache, jnp.int32(0), jnp.int32(1),
                                 jnp.int32(2))
            w_drafts, w_dcache = draft_loop(draft_params, w_pend, w_dcache)
            w_dcache = truncate_step(w_dcache, w_lens)
            w_dcache = rollback_step(w_dcache, jnp.int32(0),
                                     jnp.int32(prompt_len))
            w_dcache = release_step(w_dcache, jnp.int32(0))
        w_in = jnp.concatenate([w_pend[:, None], w_drafts[:, :-1]], axis=1)
        w_vlog, w_cache = verify_step(params, w_in, w_cache)
        select_targets(w_vlog)
        w_cache = truncate_step(w_cache, w_lens)
        w_cache = rollback_step(w_cache, jnp.int32(0), jnp.int32(prompt_len))
        w_cache = release_step(w_cache, jnp.int32(0))
        jax.block_until_ready(w_vlog)

    def _run() -> Dict:
        cache = T.make_paged_cache(cfg, slots, max_len, block_k=block_k,
                                   num_blocks=pool_size)
        alloc = paged_kv.BlockAllocator(pool_size)
        pager = engines_base.PoolManager(alloc, bps, block_k)
        dcache = dalloc = d_pager = None
        if not self_draft:
            dcache = T.make_paged_cache(dcfg, slots, max_len,
                                        block_k=block_k,
                                        num_blocks=pool_size)
            dalloc = paged_kv.BlockAllocator(pool_size)
            d_pager = engines_base.PoolManager(dalloc, bps, block_k)
        health = ServeHealth()
        inj = faults_mod.FaultInjector(fault_plan, health)
        watchdog = strag.StragglerWatchdog(window=50, threshold=3.0,
                                           min_history=4,
                                           on_straggler=health.straggler)
        stats: Dict = {"batch_prefills": 0, "slot_prefills": 0,
                       "decode_steps": 0, "draft_steps": 0,
                       "verify_steps": 0, "drafts_proposed": 0,
                       "drafts_accepted": 0, "gamma": gamma,
                       "slot_accept": {s: [0, 0] for s in range(slots)},
                       "step_s": []}
        queue = deque(range(requests))
        generated: Dict[int, List[int]] = {}
        finished: Dict[int, List[int]] = {}
        expired: Dict[int, List[int]] = {}
        failed: Dict[int, List[int]] = {}
        resume_prefix: Dict[int, List[int]] = {}
        expect: Dict[int, List[int]] = {}   # recorded prefix, re-asserted
        admit_step0: Dict[int, int] = {}
        admit_seq: Dict[int, int] = {}
        active: Dict[int, int] = {}
        seq_counter = [0]
        calib_rid = [None]
        cur_lens = np.zeros((slots,), np.int32)
        pend_h = np.zeros((slots,), np.int32)
        step = 0

        def free_slot(slot):
            nonlocal cache, dcache
            pager.release(slot)
            cache = release_step(cache, jnp.int32(slot))
            if not self_draft:
                d_pager.release(slot)
                dcache = release_step(dcache, jnp.int32(slot))
            # shared-cache drafters must never hold their own blocks; a
            # distinct drafter's table stays in lockstep with the target's
            assert (d_pager is None or
                    set(d_pager.owned) == set(pager.owned))
            cur_lens[slot] = 0

        def preempt(vslot, *, reason):
            rid = active.pop(vslot)
            pre = generated.pop(rid)
            resume_prefix[rid] = pre
            expect.pop(rid, None)
            free_slot(vslot)
            queue.appendleft(rid)
            health.count("preemptions")
            health.event("preempt", step, rid=rid, slot=vslot,
                         policy=preempt_policy, reason=reason,
                         prefix_tokens=len(pre))
            if verbose:
                print(f"[serve-spec] step {step}: preempted request {rid} "
                      f"(slot {vslot}, {reason})", flush=True)

        parked: set = set()             # slots skipping this round's draft

        def park(slot):
            """Gentle pressure tier: skip this slot's speculation for the
            round and give back its own over-coverage tail (blocks past the
            accepted prefix) on every pool.  Its own tail only — another
            slot's gamma coverage is what that slot's in-flight draft writes
            into this round, so reclaiming it would corrupt that stream."""
            nonlocal cache, dcache
            keep = int(cur_lens[slot])
            freed = pager.reclaim_tail(slot, keep)
            if not self_draft:
                freed += d_pager.reclaim_tail(slot, keep)
            cache = rollback_step(cache, jnp.int32(slot), jnp.int32(keep))
            if not self_draft:
                dcache = rollback_step(dcache, jnp.int32(slot),
                                       jnp.int32(keep))
            parked.add(slot)
            health.count("spec_parks")
            health.event("park", step, slot=slot, rid=active[slot],
                         freed=freed)

        def grow_all(slot, upto, pg, cache_name):
            """Cover ``upto`` positions for one slot on one pool; park,
            then preempt, under pressure.  Returns False once the slot is
            out of the round (parked or preempted)."""
            nonlocal cache, dcache
            while slot in active and pg.short(slot, upto) > 0:
                try:
                    start, ids = pg.grow(slot, pg.short(slot, upto))
                except paged_kv.BlockAllocationError as e:
                    health.event("pool_pressure", step, slot=slot,
                                 pool=cache_name, requested=e.requested,
                                 free=e.free, live=e.live,
                                 high_water=e.high_water)
                    others = [s for s in active
                              if s != slot and s not in parked]
                    if others:
                        # someone else is still speculating this round, so
                        # sitting it out cannot stall the whole batch
                        park(slot)
                        return False
                    victim = pick_victim(
                        active, slot, preempt_policy, admit_seq,
                        lambda s: gens[active[s]]
                        - len(generated[active[s]]))
                    if victim is None:
                        preempt(slot, reason="self")
                        return False
                    preempt(victim, reason="growth")
                    parked.discard(victim)
                    continue
                for j, b in enumerate(ids):
                    if cache_name == "kv":
                        cache = grow_step(cache, jnp.int32(slot),
                                          jnp.int32(start + j),
                                          jnp.int32(b))
                    else:
                        dcache = grow_step(dcache, jnp.int32(slot),
                                           jnp.int32(start + j),
                                           jnp.int32(b))
            return slot in active and slot not in parked

        t0 = time.time()
        while active or queue:
            ts_iter = time.perf_counter()
            prefills0 = stats["slot_prefills"]
            preempts0 = health.counters["preemptions"]
            inj.on_step(step)
            inj.squeeze_pool(step, alloc)
            fslot = inj.force_preempt(step)
            if fslot is not None and fslot in active:
                preempt(fslot, reason="fault")

            # ---- growth: every slot needs len + gamma coverage this round
            parked.clear()
            for slot in list(sorted(active)):
                if slot not in active:
                    continue
                upto = int(cur_lens[slot]) + gamma
                if not grow_all(slot, upto, pager, "kv"):
                    continue
                if not self_draft:
                    grow_all(slot, upto, d_pager, "draft_kv")

            # ---- admission -----------------------------------------------
            idle = [s for s in range(slots) if s not in active]
            while queue and idle:
                rid = queue[0]
                s_len = len(prompts[rid])
                need = paged_kv.blocks_per_seq(s_len + gamma, block_k)
                pools_ok = alloc.free_count >= need and (
                    self_draft or dalloc.free_count >= need)
                if not pools_ok:
                    health.count("admission_stalls")
                    health.event("admission_stall", step, rid=rid,
                                 need=need, free=alloc.free_count)
                    break
                queue.popleft()
                slot = idle.pop(0)
                row = pager.admit_row(slot, s_len + gamma)
                if calib_rid[0] is None:
                    calib_rid[0] = rid
                fn = t_calib if rid == calib_rid[0] else t_slot
                sid = jnp.asarray([slot], jnp.int32)
                prompt = jnp.asarray(prompts[rid])[None]
                last1, cache = fn(params, prompt, cache, sid,
                                  jnp.asarray(row[None], jnp.int32))
                stats["slot_prefills"] += 1
                if not self_draft:
                    drow = d_pager.admit_row(slot, s_len + gamma)
                    dfn = d_calib if rid == calib_rid[0] else d_slot
                    _, dcache = dfn(draft_params, prompt, dcache, sid,
                                    jnp.asarray(drow[None], jnp.int32))
                    stats["slot_prefills"] += 1
                health.count("admissions")
                active[slot] = rid
                admit_seq[slot] = seq_counter[0]
                seq_counter[0] += 1
                first_logits = np.asarray(last1[0])
                if not np.isfinite(first_logits).all():
                    failed[rid] = []
                    del active[slot]
                    free_slot(slot)
                    idle.insert(0, slot)
                    health.count("nan_retired")
                    health.event("nan_retired", step, rid=rid, slot=slot,
                                 where="prefill")
                    continue
                first = int(first_logits.argmax())
                if rid in resume_prefix:
                    pre = resume_prefix.pop(rid)
                    assert first == pre[0], (
                        f"resume divergence for request {rid}: re-prefill "
                        f"token {first} != recorded {pre[0]}")
                    expect[rid] = pre
                    health.count("resumes")
                    health.count("resumed_tokens_replayed", len(pre) - 1)
                    health.event("resume", step, rid=rid, slot=slot,
                                 prefix_tokens=len(pre))
                else:
                    admit_step0[rid] = step
                generated[rid] = [first]
                pend_h[slot] = first
                cur_lens[slot] = s_len

            if not active:
                step += 1
                if queue:
                    continue
                break

            # ---- one draft -> verify -> accept round ---------------------
            pending = jnp.asarray(pend_h)
            ts = time.perf_counter()
            if self_draft:
                drafts, cache = draft_loop(params, pending, cache)
                # length-only rewind: verify overwrites the draft K/V rows
                cache = truncate_step(cache, jnp.asarray(cur_lens))
            else:
                drafts, dcache = draft_loop(draft_params, pending, dcache)
            verify_in = jnp.concatenate([pending[:, None], drafts[:, :-1]],
                                        axis=1)
            vlogits, cache = verify_step(params, verify_in, cache)
            vlogits = inj.corrupt_logits(step, vlogits)
            targets, okv = select_targets(vlogits)
            drafts_h, targets_h, ok_h = jax.device_get(
                (drafts, targets, okv))
            stats["step_s"].append(time.perf_counter() - ts)
            stats["draft_steps"] += 1
            stats["verify_steps"] += 1

            new_lens = np.zeros((slots,), np.int32)
            retiring: List[int] = []
            for slot in sorted(active):
                rid = active[slot]
                if slot in parked:
                    # sat the round out under pool pressure: nothing
                    # emitted, prefix stays resident, retries next round.
                    # Its draft row read through trashed tail entries, so
                    # its (discarded) logits are exempt from the NaN guard.
                    new_lens[slot] = cur_lens[slot]
                    continue
                if not ok_h[slot]:
                    failed[rid] = generated.pop(rid)
                    del active[slot]
                    expect.pop(rid, None)
                    health.count("nan_retired")
                    health.event("nan_retired", step, rid=rid, slot=slot,
                                 where="verify")
                    # free after the batch-wide truncate below would also
                    # work; do it here so the blocks recycle immediately
                    free_slot(slot)
                    continue
                k = 0
                while (k < gamma
                       and drafts_h[slot, k] == targets_h[slot, k]):
                    k += 1
                if k < gamma:
                    emit = [int(x) for x in drafts_h[slot, :k]]
                    emit.append(int(targets_h[slot, k]))
                else:
                    emit = [int(x) for x in drafts_h[slot, :gamma]]
                remaining = gens[rid] - len(generated[rid])
                emit = emit[:remaining]
                used_drafts = min(k, len(emit))
                stats["drafts_proposed"] += gamma
                stats["drafts_accepted"] += used_drafts
                stats["slot_accept"][slot][0] += used_drafts
                stats["slot_accept"][slot][1] += gamma
                generated[rid].extend(emit)
                pend_h[slot] = generated[rid][-1]
                if rid in expect:
                    # the bitwise resume contract, asserted live: the
                    # re-emitted greedy continuation must reproduce the
                    # prefix recorded before preemption
                    exp = expect[rid]
                    got = generated[rid]
                    n = min(len(exp), len(got))
                    assert got[:n] == exp[:n], (
                        f"resume divergence for request {rid} at token "
                        f"{next(i for i in range(n) if got[i] != exp[i])}")
                    if len(got) >= len(exp):
                        del expect[rid]
                if len(generated[rid]) >= gens[rid]:
                    retiring.append(slot)
                else:
                    new_lens[slot] = prompt_len + len(generated[rid]) - 1

            # rollback to the accepted prefix in one shot; retiring /
            # inactive slots truncate to zero
            lens_dev = jnp.asarray(new_lens)
            cache = truncate_step(cache, lens_dev)
            if not self_draft:
                dcache = truncate_step(dcache, lens_dev)
            cur_lens = new_lens

            for slot in retiring:
                rid = active.pop(slot)
                finished[rid] = generated.pop(rid)
                expect.pop(rid, None)
                free_slot(slot)

            if deadline_steps is not None:
                for slot in list(sorted(active)):
                    rid = active[slot]
                    if step - admit_step0[rid] + 1 >= deadline_steps:
                        expired[rid] = generated.pop(rid)
                        del active[slot]
                        expect.pop(rid, None)
                        free_slot(slot)
                        health.count("deadline_cancelled")
                        health.event("deadline", step, rid=rid, slot=slot,
                                     tokens=len(expired[rid]))
            watchdog.observe(
                step, time.perf_counter() - ts_iter,
                expect_slow=(stats["slot_prefills"] != prefills0
                             or health.counters["preemptions"] != preempts0))
            step += 1

        inj.drain(alloc)
        health.pool("kv", alloc)
        if dalloc is not None:
            health.pool("draft_kv", dalloc)
        stats["leaked_blocks"] = alloc.live_count + (
            dalloc.live_count if dalloc is not None else 0)
        stats["finished"] = finished
        stats["expired"] = expired
        stats["failed"] = failed
        stats["preemptions"] = health.counters["preemptions"]
        stats["resumes"] = health.counters["resumes"]
        stats["health"] = health.to_dict()
        stats["health"]["straggler_summary"] = watchdog.summary()
        stats["accept_rate"] = (stats["drafts_accepted"]
                                / max(stats["drafts_proposed"], 1))
        total_emitted = sum(len(v) for v in finished.values()) - len(finished)
        stats["tokens_per_verify"] = (total_emitted
                                      / max(stats["verify_steps"], 1))
        stats["slot_accept"] = {
            s: (a / max(p, 1)) for s, (a, p) in stats["slot_accept"].items()}
        nl = cfg.n_layers
        mean_gen = sum(gens) // (2 * len(gens))
        mean_blocks = paged_kv.blocks_per_seq(prompt_len + mean_gen, block_k)
        stats["kv_bytes_per_step"] = (2 * nl * slots * cfg.n_kv_heads
                                      * mean_blocks * block_k * cfg.hd)
        return finalize_stats(stats, finished, t0)

    best = _run()
    for _ in range(repeats - 1):
        run = _run()
        if run["tok_s"] > best["tok_s"]:
            best = run
    return best
