"""The serving loop's record in a profiler trace, and the names it is read by.

`run_schedule` writes host spans into the JAX profiler's trace: one
``sched.step`` per iteration, one ``sched.admit`` per admission and one
``sched.wait`` wherever the host blocks on a device value.  Every served
token reaches the host at the end of a wait, so the trace alone places each
request's tokens in time.  The benchmark classes device time by the jitted
programs' module names (``jit_decode_step``, ``jit_prefill_step``), which
the last test pins.

Smoke dense config on the CPU.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch
from repro.core import paged_kv
from repro.launch import scheduler as sched
from repro.launch import serve as srv
from repro.launch import steps as st

SLOTS, BLOCK_K = 3, 8


@pytest.fixture(scope="module")
def rig():
    cfg = get_arch("tinyllama_1p1b").smoke.replace(dtype="float32")
    params = st.init_params_fn(cfg)(jax.random.PRNGKey(5))
    rng = np.random.default_rng(3)
    lens = [16, 9, 16, 12, 9, 16, 12]
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lens]
    gens = [6, 3, 9, 4, 7, 2, 5]
    return cfg, params, prompts, gens


def _engine(rig):
    cfg, params, prompts, gens = rig
    return srv.make_engine(params, cfg, prompts, slots=SLOTS,
                           max_len=max(map(len, prompts)) + max(gens) + 8,
                           block_k=BLOCK_K)


def _spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the ``sched.*`` host spans."""
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sched."):
                    out.append((e.name, int(e.start_ns), int(e.end_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_place_every_served_token(rig, tmp_path):
    _, _, prompts, gens = rig
    plain = sched.run_schedule(_engine(rig), prompts, gens=gens)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        traced = sched.run_schedule(_engine(rig), prompts, gens=gens)
    finally:
        jax.profiler.stop_trace()
    # tracing changes nothing the program serves
    assert traced["finished"] == plain["finished"]
    assert sorted(traced["finished"]) == list(range(len(prompts)))

    spans = _spans(tmp_path)
    steps = [s for s in spans if s[0] == "sched.step"]
    admits = [s for s in spans if s[0] == "sched.admit"]
    waits = [s for s in spans if s[0] == "sched.wait"]
    assert [s[3]["step_num"] for s in steps] == list(range(len(steps)))
    assert len(admits) == traced["health"]["counters"]["admissions"]
    assert all(any(_inside(w, o) for o in steps + admits) for w in waits)
    assert sum(w[3]["tokens"] for w in waits) == sum(gens)

    def step_of(span):
        (j,) = [s[3]["step_num"] for s in steps if _inside(span, s)]
        return j

    # per step: the wait outside every admission reads the decoded tokens
    step_wait = {}
    for w in waits:
        if not any(_inside(w, a) for a in admits):
            assert step_of(w) not in step_wait
            step_wait[step_of(w)] = w
    holders = {j: 0 for j in step_wait}
    by_slot = {}
    for a in admits:
        rid, slot, k = a[3]["rid"], a[3]["slot"], step_of(a)
        assert a[3]["prompt_len"] == len(prompts[rid])
        (first,) = [w for w in waits if _inside(w, a)]
        assert first[3]["tokens"] == 1
        # the request holds its slot from its admission's step until the
        # step that decodes its last token; one wait brings each token
        n = len(traced["finished"][rid])
        held = list(range(k, k + n - 1))
        emitted = [first[2]] + [step_wait[j][2] for j in held]
        assert len(emitted) == gens[rid]
        assert all(b > a for a, b in zip(emitted, emitted[1:]))
        for j in held:
            holders[j] += 1
        by_slot.setdefault(slot, []).append((k, k + n - 2))
    for j, w in step_wait.items():
        (s,) = [s for s in steps if s[3]["step_num"] == j]
        assert holders[j] == w[3]["tokens"] == s[3]["active"]
    for held in by_slot.values():
        assert all(nxt[0] > prev[1] for prev, nxt in zip(held, held[1:]))


def _module_name(jitted, *args):
    return re.search(r"module @(\w+)", jitted.lower(*args).as_text()).group(1)


def test_serving_programs_keep_their_module_names(rig):
    """The profiler names a program's device time after its module, and the
    benchmark's trace reduction classes it by ``decode_step`` and
    ``prefill_step``: a renamed step would empty those classes silently."""
    cfg, params, prompts, _ = rig
    eng = _engine(rig)
    cache = eng.make_cache()
    row = np.full((1, eng.bps), paged_kv.TRASH_BLOCK, np.int32)
    prefill_args = (params, jnp.asarray(prompts[0])[None], cache,
                    jnp.zeros((1,), jnp.int32), jnp.asarray(row))
    i32 = jnp.int32(0)
    tokens = jnp.zeros((SLOTS,), jnp.int32)
    logits = jnp.zeros((SLOTS, cfg.vocab_size), jnp.float32)
    names = {
        "calib_prefill": _module_name(eng.calib_prefill, *prefill_args),
        "slot_prefill": _module_name(eng.slot_prefill, *prefill_args),
        "decode": _module_name(eng.decode_step, params, tokens, cache),
        "grow": _module_name(eng.grow_step, cache, i32, i32, i32),
        "release": _module_name(eng.release_step, cache, i32),
        "select": _module_name(sched.make_sampler(0.0, 1.0, cfg.vocab_size),
                               logits, jax.random.PRNGKey(0)),
        "splice": _module_name(sched._splice_token, tokens, i32, i32),
    }
    assert names == {
        "calib_prefill": "jit_prefill_step", "slot_prefill": "jit_prefill_step",
        "decode": "jit_decode_step", "grow": "jit_grow_step",
        "release": "jit_release_step", "select": "jit_greedy",
        "splice": "jit__splice_token"}
