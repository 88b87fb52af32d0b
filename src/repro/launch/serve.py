"""Family-agnostic continuous-batching serving driver over the CIM cache
engines.

The paper's decoder mapping end-to-end, at serving granularity: the model's
recurrent state lives int8 in a device pool exactly as it lives in the CIM
array, and batched decode steps stream one token per sequence per step
through the split-softmax datapath.  One scheduler
(`repro.launch.scheduler.run_schedule`) drives every model family through a
family-specific `repro.launch.engines` cache engine:

  * **dense / MoE** (`PagedKVEngine`) — the int8 paged KV block pool
    (`repro.core.paged_kv`): every admission is a per-slot prefill that
    allocates only the blocks the prompt needs, a slot *grows* one block at
    a time as it crosses block boundaries, and retirement returns blocks to
    the free list.  The very first admission also calibrates the pool's
    static per-layer scales.
  * **SSM** (`SSMStateEngine`) — fixed-size per-slot slabs (conv tail +
    recurrent state) held int8 between steps with per-(layer, slot) scales;
    no paging, no over-commit (the footprint is O(1) per sequence — the
    SSM serving win).
  * **encoder-decoder** (`EncDecEngine`) — paged int8 self-KV plus a
    write-once quantized cross-KV bank carved out of the *same* block pool
    (`BlockAllocator.carve`): computed at admission from the request's
    encoder frames, read-only for the request's lifetime.

Because dense/MoE/encdec blocks are allocated on demand, the pool can be
sized **below** ``slots * blocks_per_seq`` (``--pool-blocks``) to
over-commit memory.  When a growth or admission then exhausts the pool, the
scheduler **preempts** a victim (``--preempt-policy newest`` | ``longest``):
the victim's blocks are freed, its table row is trashed, and the request is
re-queued with its generated prefix.  On re-admission the prompt is
re-prefilled (same per-slot executable as the original admission) and the
recorded prefix is replayed through the ordinary decode path, so the final
outputs are **bitwise identical** to a run that was never preempted —
per-row decode numerics do not depend on slot index or co-resident
sequences, which ``tests/test_overcommit.py`` and ``tests/test_engines.py``
pin.  This holds for sampling too: sampling keys are derived per request
from ``(seed, request id, tokens drawn)`` (`scheduler.RequestKeys`), not
from a shared key stream, so a resumed request continues with exactly the
keys the uninterrupted run would have used.

Operational hardening on the same loop:

  * ``--deadline-steps N`` cancels any request still unfinished N scheduler
    steps after its first admission (preemption/queue time counts — that is
    what a deadline is for) and reports it under ``stats["expired"]``;
  * ``--deadline-ms MS`` is the wall-clock variant, and additionally turns
    admission into earliest-deadline-first: the queued request with the
    least remaining budget is admitted ahead of FIFO order;
  * a finite-guard folded into the token selector retires a slot whose
    logits go NaN/Inf (``stats["failed"]``) instead of emitting garbage;
  * every step is timed through a `repro.dist.straggler.StragglerWatchdog`
    and every degradation (preemption, resume, stall, deadline, NaN retire,
    injected fault) lands in a `repro.launch.health.ServeHealth` record,
    emitted as one JSON artifact via ``--metrics-json``.

Chaos knobs (see `repro.launch.faults`; all deterministic, step-addressed):

    --pool-blocks N             over-commit the pool (min 1 + blocks/seq)
    --deadline-steps N          per-request scheduler-step deadline
    --deadline-ms MS            per-request wall-clock deadline (EDF admit)
    REPRO_FAULT_EXHAUST=S[:H]   steal all free blocks at step S, hold H steps
    REPRO_FAULT_DELAY=S:SEC     sleep SEC before step S (trips the watchdog)
    REPRO_FAULT_NAN=S[:SLOT]    NaN one slot's logits at step S
    REPRO_FAULT_PREEMPT=S[:SLOT] force-preempt one slot at step S
    REPRO_FAULT_SEED=N          recorded into the fault events

``--cache dense`` keeps the pre-paged scheduler (admission = re-prefill the
whole batch) as the measured baseline; ``benchmarks/run.py --json`` records
both plus over-committed churn cells for all three families so the paged
speedup and the cost of preemption under pressure are tracked artifacts
(``BENCH_serve.json``).

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama_1p1b \
        --smoke --requests 8 --slots 4 --prompt-len 32 --gen 24 \
        --pool-blocks 12 --deadline-steps 200 --metrics-json health.json
    PYTHONPATH=src python -m repro.launch.serve --arch falcon_mamba_7b \
        --smoke --requests 6 --slots 3 --prompt-len 16 --gen 12
    PYTHONPATH=src python -m repro.launch.serve --arch seamless_m4t_medium \
        --smoke --requests 6 --slots 3 --prompt-len 12 --gen 10
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch import compile_cache
from repro.launch import faults as faults_mod
from repro.launch import scheduler as sched
from repro.launch import steps as st
from repro.launch.engines import (EncDecEngine, PagedKVEngine, PoolManager,
                                  SSMStateEngine)
from repro.models import transformer as T

# long-standing import sites (tests, benches, examples) keep working; the
# implementations live in scheduler.py / engines/ now
make_sampler = sched.make_sampler
make_sampler  # re-exported
_percentile = sched.percentile
_PoolManager = PoolManager
_pick_victim = sched.pick_victim
_finalize_stats = sched.finalize_stats


def make_engine(params, cfg, prompts: List[np.ndarray], *, slots: int,
                max_len: int, block_k: int = 32,
                pool_blocks: Optional[int] = None,
                frames: Optional[List[np.ndarray]] = None):
    """Family -> CacheEngine dispatch; the only family switch in serving."""
    if cfg.family in ("dense", "moe"):
        return PagedKVEngine(params, cfg, prompts, slots=slots,
                             max_len=max_len, block_k=block_k,
                             pool_blocks=pool_blocks)
    if cfg.family == "ssm":
        return SSMStateEngine(params, cfg, prompts, slots=slots,
                              max_len=max_len, block_k=block_k,
                              pool_blocks=pool_blocks)
    if cfg.family == "encdec":
        if frames is None:
            raise ValueError("encdec serving needs per-request encoder "
                             "frames (frames=[(S_enc, d_model) arrays])")
        return EncDecEngine(params, cfg, prompts, frames=frames,
                            slots=slots, max_len=max_len, block_k=block_k,
                            pool_blocks=pool_blocks)
    raise ValueError(f"no cache engine for family {cfg.family!r}")


def serve_paged(params, cfg, prompts: List[np.ndarray], *, slots: int,
                gen: int, block_k: int = 32, max_len: Optional[int] = None,
                gens: Optional[Sequence[int]] = None,
                temperature: float = 0.0, top_p: float = 1.0,
                sample_seed: int = 0,
                pool_blocks: Optional[int] = None,
                preempt_policy: str = "newest",
                deadline_steps: Optional[int] = None,
                deadline_ms: Optional[float] = None,
                fault_plan: Optional["faults_mod.FaultPlan"] = None,
                frames: Optional[List[np.ndarray]] = None,
                warmup: bool = False, repeats: int = 1,
                verbose: bool = False) -> Dict:
    """Demand-paged scheduler; returns a stats dict (tok/s, latency, prefill
    counts, the generated sequences, allocator accounting, and the run's
    ``health`` record).

    ``gens`` optionally staggers per-request generation lengths (churn: slots
    retire at different steps).  ``temperature``/``top_p`` select tokens via
    :func:`scheduler.make_sampler` (0.0 = greedy, the default).
    ``pool_blocks`` sizes the block pool below the full
    ``1 + slots * blocks_per_seq`` reservation to over-commit; exhaustion
    preempts a ``preempt_policy`` victim and resumes it later with a
    bitwise-identical continuation.  ``frames`` carries the per-request
    encoder inputs for the encdec family.  ``warmup=True`` compiles each
    jitted step on throwaway inputs before the clock starts; ``repeats > 1``
    (benchmarking) reruns the whole schedule on the same compiled steps and
    keeps the fastest run.
    """
    requests = len(prompts)
    slots = min(slots, requests)
    gens = list(gens) if gens is not None else [gen] * requests
    if max_len is None:
        max_len = max(len(p) for p in prompts) + max(gens) + 8
    engine = make_engine(params, cfg, prompts, slots=slots, max_len=max_len,
                         block_k=block_k, pool_blocks=pool_blocks,
                         frames=frames)
    return sched.run_schedule(
        engine, prompts, gens=gens, temperature=temperature, top_p=top_p,
        sample_seed=sample_seed, preempt_policy=preempt_policy,
        deadline_steps=deadline_steps, deadline_ms=deadline_ms,
        fault_plan=fault_plan, warmup=warmup, repeats=repeats,
        verbose=verbose)


def serve_dense(params, cfg, prompts: List[np.ndarray], *, slots: int,
                gen: int, max_len: Optional[int] = None,
                gens: Optional[Sequence[int]] = None,
                temperature: float = 0.0, top_p: float = 1.0,
                sample_seed: int = 0,
                warmup: bool = False, repeats: int = 1,
                verbose: bool = False) -> Dict:
    """Pre-paged baseline scheduler: admission re-prefills the *entire*
    batch (prompt + generated-so-far for in-flight slots).  Kept as the A/B
    reference the paged path is measured against."""
    requests = len(prompts)
    prompt_len = len(prompts[0])
    slots = min(slots, requests)
    gens = list(gens) if gens is not None else [gen] * requests
    assert len(gens) == requests
    if max_len is None:
        max_len = prompt_len + max(gens) + 8
    seq_pad = prompt_len + max(gens)    # fixed re-prefill width (one trace)
    sampler = sched.make_sampler(temperature, top_p, cfg.vocab_size)

    prefill_step = jax.jit(st.make_prefill_step(cfg, max_len))
    decode_step = jax.jit(st.make_decode_step(cfg), donate_argnums=(2,))

    @jax.jit
    def reprefill_step(params, seqs, lens):
        return T.prefill(params, seqs, cfg, T.make_cache(cfg, slots, max_len),
                         valid_len=lens)

    if warmup:
        w_tok = jnp.asarray(np.stack([prompts[0]] * slots))
        w_last, _ = prefill_step(params, {"tokens": w_tok})
        w_seqs = jnp.zeros((slots, seq_pad), jnp.int32)
        w_lens = jnp.full((slots,), prompt_len, jnp.int32)
        _, w_cache = reprefill_step(params, w_seqs, w_lens)
        w_key = (jax.random.PRNGKey(0) if temperature == 0.0
                 else jnp.stack([jax.random.PRNGKey(0)] * slots))
        w_sel, _ = sampler(w_last, w_key)
        w_out, _ = decode_step(params, w_sel.astype(jnp.int32), w_cache)
        jax.block_until_ready(w_out)

    def _run() -> Dict:
        stats: Dict = {"batch_prefills": 0, "slot_prefills": 0,
                       "decode_steps": 0, "step_s": []}
        queue = list(range(requests))
        generated: Dict[int, List[int]] = {}
        finished: Dict[int, List[int]] = {}
        active: Dict[int, int] = {}
        keys = sched.RequestKeys(sample_seed)

        def select(logits):
            if temperature == 0.0:
                toks, _ = sampler(logits, keys.base)   # key unused
                return toks
            ks = jnp.stack([
                keys.key(active[s], len(generated.get(active[s], [])))
                if s in active else keys.base for s in range(slots)])
            toks, _ = sampler(logits, ks)
            return toks

        t0 = time.time()
        for slot in range(slots):
            active[slot] = queue.pop(0)
        prompts_arr = jnp.asarray(np.stack([prompts[active[s]]
                                            for s in range(slots)]))
        last, cache = prefill_step(params, {"tokens": prompts_arr})
        stats["batch_prefills"] += 1
        tokens = select(last)
        for slot in range(slots):
            generated[active[slot]] = [int(tokens[slot])]

        while active:
            ts = time.perf_counter()
            logits, cache = decode_step(params, tokens, cache)
            tokens = select(logits)
            tok_host = np.asarray(tokens)
            stats["step_s"].append(time.perf_counter() - ts)
            stats["decode_steps"] += 1
            retired = False
            for slot in sorted(active):
                rid = active[slot]
                generated[rid].append(int(tok_host[slot]))
                if len(generated[rid]) >= gens[rid]:
                    finished[rid] = generated.pop(rid)
                    del active[slot]
                    retired = True
                    if queue:
                        active[slot] = queue.pop(0)
                        generated[active[slot]] = []
            if retired and active:
                # admission (or plain retirement) = full-batch re-prefill,
                # the throughput collapse the paged scheduler removes
                seqs = np.zeros((slots, seq_pad), np.int32)
                lens = np.ones((slots,), np.int32)
                for slot, rid in active.items():
                    seq = np.concatenate([prompts[rid],
                                          np.asarray(generated[rid],
                                                     np.int32)])
                    seqs[slot, :len(seq)] = seq
                    lens[slot] = len(seq)
                last, cache = reprefill_step(params, jnp.asarray(seqs),
                                             jnp.asarray(lens))
                stats["batch_prefills"] += 1
                tokens = select(last)
                tok_host = np.asarray(tokens)
                for slot, rid in active.items():
                    generated[rid].append(int(tok_host[slot]))

        stats["leaked_blocks"] = 0
        stats["finished"] = finished
        nl = cfg.n_layers
        stats["kv_bytes_per_step"] = (2 * nl * slots * cfg.n_kv_heads
                                      * max_len * cfg.hd)
        return sched.finalize_stats(stats, finished, t0)

    best = _run()
    for _ in range(repeats - 1):
        run = _run()
        if run["tok_s"] > best["tok_s"]:
            best = run
    return best


def make_self_draft(params, cfg, n_layers: Optional[int] = None):
    """Derive a drafter (params, cfg) from the target without new weights.

    ``n_layers=None`` shares the full target — self-speculation, where
    acceptance is 1.0 by construction and the measured speedup is pure
    launch fusion (gamma scanned draft steps + one verify instead of gamma
    dispatched decode steps).  An integer keeps only the first ``n_layers``
    decoder blocks (a layer-prefix drafter sharing embed / final norm /
    head — EdgeCIM's SLM-style cheap drafter, dense family only).
    """
    if n_layers is None:
        return params, cfg
    assert cfg.family == "dense", "layer-prefix drafter needs dense family"
    assert 0 < n_layers <= cfg.n_layers, (n_layers, cfg.n_layers)
    seg = jax.tree.map(lambda a: a[:n_layers], params["segments"][0])
    return dict(params, segments=[seg]), cfg.replace(n_layers=n_layers)


def serve_speculative(params, cfg, prompts: List[np.ndarray], *, slots: int,
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
    """Greedy speculative scheduler, drafter-aware about cache sharing,
    with the same demand-paged over-commit machinery as :func:`serve_paged`
    (dense/MoE paged caches only; implemented in
    `scheduler.run_speculative`).

    Per round, for every slot at once: the drafter runs ``gamma`` greedy
    steps fused into one ``lax.scan`` launch (`steps.make_draft_loop`), the
    target verifies ``[pending, drafts[:-1]]`` in one fused multi-token
    launch (`steps.make_verify_step`), and the host accepts the longest
    prefix where draft token == target argmax, then takes the target's
    correction token.  Caches are truncated to the accepted prefix
    (`paged_kv.truncate_lengths`) — the K/V for accepted tokens is already
    bit-correct because the target itself wrote it during verify.

    Cache layout depends on the drafter.  A *distinct* drafter gets its own
    paged cache and block pool (its K/V comes from different weights), which
    doubles every prefill / grow / truncate / release — the scheduler keeps
    the two block tables in lockstep (grown, rolled back, and released
    together), and asserts a self-drafter (shared cache) never owns drafter
    blocks at all.  Self-drafting (``draft=None``) shares the target's
    cache: the draft loop appends its K/V at positions ``len..len+gamma``,
    a length-only truncation rewinds to ``len``, and the verify launch
    *overwrites* those same positions with target-computed K/V before
    anything past ``len`` is ever read again.

    Demand paging note: each round needs coverage for ``len + gamma``
    positions (the unaccepted draft tail briefly occupies blocks before the
    rollback).  Pool pressure has a gentler first tier than eviction: a slot
    that cannot grow its speculation window **parks** for the round — it
    skips draft/verify acceptance, keeps its accepted prefix resident, and
    gives back its own over-coverage tail (`paged_kv.tail_blocks` on host,
    `paged_kv.rollback_slot` on device, applied to *both* block tables in
    lockstep) — and retries next round.  Never another slot's tail: a
    co-resident slot's gamma coverage is exactly what its in-flight draft
    writes into, so reclaiming it would corrupt that stream.  Only when
    every other active slot is already parked does the scheduler escalate
    to preempting a victim.

    Correctness contract: emitted tokens are **bitwise identical** to the
    non-speculative greedy path for *any* drafter, because every accepted
    token is checked against (and every correction token is) the target's
    own argmax at exactly the sequential cache state.  The same argument
    makes preemption recovery exact: a resumed request re-emits its greedy
    continuation from the re-prefilled prompt, which the scheduler asserts
    against the recorded prefix token-for-token.  ``draft`` is a
    ``(draft_params, draft_cfg)`` pair; ``None`` self-drafts with the full
    target (see :func:`make_self_draft`).
    """
    return sched.run_speculative(
        params, cfg, prompts, slots=slots, gen=gen, gamma=gamma,
        draft=draft, block_k=block_k, max_len=max_len, gens=gens,
        pool_blocks=pool_blocks, preempt_policy=preempt_policy,
        deadline_steps=deadline_steps, fault_plan=fault_plan,
        warmup=warmup, repeats=repeats, verbose=verbose)


def serve(params, cfg, prompts: List[np.ndarray], *, slots: int, gen: int,
          cache_kind: str = "paged", block_k: int = 32,
          max_len: Optional[int] = None,
          gens: Optional[Sequence[int]] = None,
          gamma: int = 4, draft=None,
          temperature: float = 0.0, top_p: float = 1.0,
          pool_blocks: Optional[int] = None,
          preempt_policy: str = "newest",
          deadline_steps: Optional[int] = None,
          deadline_ms: Optional[float] = None,
          fault_plan: Optional["faults_mod.FaultPlan"] = None,
          frames: Optional[List[np.ndarray]] = None,
          metrics_json: Optional[str] = None,
          warmup: bool = False, repeats: int = 1,
          verbose: bool = False) -> Dict:
    """Dispatch on the cache layout / speculative mode; see
    :func:`serve_paged` and :func:`serve_speculative`.  ``draft`` switches
    to the speculative scheduler (greedy only; paged dense/MoE only).  The
    over-commit / chaos knobs (``pool_blocks``, ``preempt_policy``,
    ``deadline_steps``, ``deadline_ms``, ``fault_plan``) are paged-path
    features; ``frames`` carries encdec encoder inputs; ``metrics_json``
    writes the run's health record as one JSON artifact."""
    if draft is not None:
        assert cache_kind == "paged", "speculative serving is paged-only"
        assert temperature == 0.0, "speculative serving is greedy-only"
        assert deadline_ms is None, \
            "--deadline-ms is not wired into the speculative loop"
        draft_pair = None if draft == "self" else draft
        stats = serve_speculative(
            params, cfg, prompts, slots=slots, gen=gen, gamma=gamma,
            draft=draft_pair, block_k=block_k, max_len=max_len, gens=gens,
            pool_blocks=pool_blocks, preempt_policy=preempt_policy,
            deadline_steps=deadline_steps, fault_plan=fault_plan,
            warmup=warmup, repeats=repeats, verbose=verbose)
    elif cache_kind == "paged":
        stats = serve_paged(
            params, cfg, prompts, slots=slots, gen=gen, block_k=block_k,
            max_len=max_len, gens=gens, temperature=temperature,
            top_p=top_p, pool_blocks=pool_blocks,
            preempt_policy=preempt_policy, deadline_steps=deadline_steps,
            deadline_ms=deadline_ms, fault_plan=fault_plan, frames=frames,
            warmup=warmup, repeats=repeats, verbose=verbose)
    else:
        assert cache_kind == "dense", cache_kind
        if pool_blocks is not None or deadline_steps is not None or (
                deadline_ms is not None) or (
                fault_plan is not None and fault_plan.armed):
            raise ValueError("pool_blocks / deadlines / faults are "
                             "paged-path features; --cache dense has no "
                             "block pool to squeeze")
        stats = serve_dense(params, cfg, prompts, slots=slots, gen=gen,
                            max_len=max_len, gens=gens,
                            temperature=temperature, top_p=top_p,
                            warmup=warmup, repeats=repeats, verbose=verbose)
    if metrics_json:
        doc = dict(stats.get("health", {}))
        doc["run"] = {k: stats[k] for k in
                      ("served", "total_tokens", "tok_s", "wall_s",
                       "decode_steps", "leaked_blocks", "p50_step_ms",
                       "p99_step_ms") if k in stats}
        doc["run"]["expired"] = sorted(stats.get("expired", {}))
        doc["run"]["failed"] = sorted(stats.get("failed", {}))
        import pathlib
        p = pathlib.Path(metrics_json)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(doc, indent=2, sort_keys=True))
        if verbose:
            print(f"[serve] health metrics -> {p}", flush=True)
    return stats


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1p1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--block-k", type=int, default=32)
    ap.add_argument("--cache", choices=("paged", "dense"), default="paged")
    ap.add_argument("--fused", choices=("auto", "on", "off"), default="auto",
                    help="fused decode datapath: quantize->QK^T->LUT->PV in "
                         "one kernel (auto/on) vs the composed quantize + "
                         "decode-kernel pipeline (off, A/B baseline)")
    ap.add_argument("--draft", default=None,
                    help="speculative decoding drafter: an arch name "
                         "(independent weights), 'self' (share the target "
                         "weights; acceptance 1.0, measures launch fusion), "
                         "or 'self:N' (first N target layers). Greedy + "
                         "paged only; output tokens are bitwise identical "
                         "to the plain greedy path")
    ap.add_argument("--gamma", type=int, default=4,
                    help="draft tokens per speculative round")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy (default; "
                         "required under --draft)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (only with --temperature)")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="over-commit: size the KV block pool below the "
                         "full slots*blocks_per_seq reservation; pool "
                         "pressure preempts and resumes requests "
                         "(bitwise-identical outputs)")
    ap.add_argument("--preempt-policy", choices=("newest", "longest"),
                    default="newest",
                    help="victim choice under pool pressure: most recently "
                         "admitted slot, or most generation remaining")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="cancel a request still unfinished this many "
                         "scheduler steps after first admission")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="cancel a request still unfinished this many "
                         "wall-clock ms after first admission; admission "
                         "becomes earliest-deadline-first")
    ap.add_argument("--metrics-json", default=None,
                    help="write the run's serving-health record "
                         "(preemptions, stragglers, faults, pool "
                         "occupancy) to this path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    arch = get_arch(args.arch)
    cfg = arch.smoke if args.smoke else arch.config
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    # "auto" = fused on: the dispatch layer itself picks compiled Pallas on
    # TPU and the bit-matching XLA twin elsewhere, so fused is always safe.
    cfg = cfg.replace(attn_fused=(args.fused != "off"))

    key = jax.random.PRNGKey(args.seed)
    params = st.init_params_fn(cfg)(key)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, args.prompt_len,
                            dtype=np.int32) for _ in range(args.requests)]
    frames = None
    if cfg.family == "encdec":
        # synthetic frontend embeddings standing in for the audio encoder
        # frontend, one shared encoder length per run
        frames = [np.asarray(rng.normal(size=(args.prompt_len, cfg.d_model)),
                             np.float32) * 0.02
                  for _ in range(args.requests)]

    draft = args.draft
    if draft and draft != "self":
        if draft.startswith("self:"):
            draft = make_self_draft(params, cfg, int(draft.split(":", 1)[1]))
        else:
            darch = get_arch(draft)
            dcfg = darch.smoke if args.smoke else darch.config
            if args.smoke:
                dcfg = dcfg.replace(dtype="float32")
            dcfg = dcfg.replace(attn_fused=(args.fused != "off"))
            dparams = st.init_params_fn(dcfg)(jax.random.PRNGKey(
                args.seed + 1))
            draft = (dparams, dcfg)

    fault_plan = faults_mod.FaultPlan.from_env()
    stats = serve(params, cfg, prompts, slots=args.slots, gen=args.gen,
                  cache_kind=args.cache, block_k=args.block_k,
                  gamma=args.gamma, draft=draft,
                  temperature=args.temperature, top_p=args.top_p,
                  pool_blocks=args.pool_blocks,
                  preempt_policy=args.preempt_policy,
                  deadline_steps=args.deadline_steps,
                  deadline_ms=args.deadline_ms,
                  fault_plan=fault_plan if fault_plan.armed else None,
                  frames=frames,
                  metrics_json=args.metrics_json,
                  verbose=True)
    mode = f"{args.cache}+spec" if args.draft else args.cache
    print(f"[{mode}:{cfg.family}] served {stats['served']} requests, "
          f"{stats['total_tokens']} tokens in {stats['wall_s']:.2f}s "
          f"({stats['tok_s']:.1f} tok/s, {stats['decode_steps']} decode "
          f"steps, {stats['batch_prefills']} batch + "
          f"{stats['slot_prefills']} slot prefills, "
          f"p50/p99 step {stats['p50_step_ms']:.1f}/"
          f"{stats['p99_step_ms']:.1f} ms)", flush=True)
    if "health" in stats:
        c = stats["health"]["counters"]
        print(f"  health: {c['preemptions']} preemptions, "
              f"{c['resumes']} resumes "
              f"({c['resumed_tokens_replayed']} tokens replayed), "
              f"{c['admission_stalls']} stalls, "
              f"{c['deadline_cancelled']} expired, "
              f"{c['nan_retired']} NaN-retired, "
              f"{c['faults_injected']} faults, "
              f"{len(stats['health']['stragglers'])} straggler steps",
              flush=True)
    if args.draft:
        print(f"  speculative: gamma={stats['gamma']} "
              f"accept_rate={stats['accept_rate']:.2f} "
              f"tokens_per_verify={stats['tokens_per_verify']:.2f} "
              f"({stats['verify_steps']} verify rounds)", flush=True)
    for rid in sorted(stats["finished"]):
        print(f"  req {rid}: {stats['finished'][rid][:8]}...")


if __name__ == "__main__":
    main()
