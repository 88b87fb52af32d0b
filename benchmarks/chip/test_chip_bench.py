"""CPU tests of the chip benchmark's harness, at sizes a test run holds.

Nothing here describes a TPU topology or needs a chip: the harness's own
look for a chip is skipped and the program runs its XLA twin on the CPU.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import bench  # noqa: E402
import counts  # noqa: E402
import metric_readers  # noqa: E402
import model_weights as mw  # noqa: E402
import reference  # noqa: E402
import runner  # noqa: E402
import trace_reduce  # noqa: E402
import workload  # noqa: E402

TINY_CONF = {
    "name": "tiny", "program": "mistral_nemo_12b", "family": "dense",
    "norm_eps": 1e-6,
    "model": {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 256, "vocab_size": 512,
              "norm": "rmsnorm", "act": "silu", "rope_theta": 1e4,
              "max_seq": 64, "tie_embeddings": False, "dtype": "bfloat16"},
}
TINY_TRAFFIC = {
    "config": "tiny", "slots": 4, "max_len": 64, "block_k": 32,
    "prompt_block": [[24, 1]], "output": [4, 12], "requests": 20000,
    "sample_tokens": 40, "min_compared": 10, "trace_seconds": 1,
    # at this size, six requests compared, sound runs read a widest gap of
    # 0.031-0.063 and the control 0.81-1.44 over five seeds (CPU, bf16)
    "gap_limit": 0.25,
}
PEAKS = counts.peaks("TPU v5 lite")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_run(seed=3, wrap_engine=None, control=False, seconds=1.0):
    out = runner.run_cell("tiny", TINY_TRAFFIC, TINY_CONF, seed=seed,
                          seconds=seconds, trace=False,
                          t_start=time.perf_counter(), peaks=PEAKS,
                          wrap_engine=wrap_engine)
    res = runner.check(out, TINY_TRAFFIC, TINY_CONF, seed, control=control)
    return out, res


# ---- traffic, counts, peaks --------------------------------------------

@pytest.mark.parametrize("cell", sorted(p.stem for p in
                                        (HERE / "traffic").glob("*.json")))
def test_traffic_is_seeded(cell):
    traffic = workload.load_json("traffic", cell)
    conf = workload.load_json("configs", traffic["config"])
    vocab = conf["model"]["vocab_size"]
    small = dict(traffic, requests=traffic["slots"] + 60)
    a = workload.make_queue(small, vocab, 2**31 + 11)
    b = workload.make_queue(small, vocab, 2**31 + 11)
    c = workload.make_queue(small, vocab, 2**31 + 12)
    assert a.gens == b.gens
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert a.gens != c.gens or any(
        not np.array_equal(x, y) for x, y in zip(a.prompts, c.prompts))
    # every seed gets the same requests' sizes; it deals the filling ones
    # to the slots in an order of its own
    sizes = lambda q: [(len(p), g) for p, g in zip(q.prompts, q.gens)]
    slots = traffic["slots"]
    assert sizes(a)[slots:] == sizes(c)[slots:]
    assert sorted(sizes(a)[:slots]) == sorted(sizes(c)[:slots])
    lens = {n for n, _ in traffic["prompt_block"]}
    assert set(a.prompt_lens) == lens
    block = sum(k for _, k in traffic["prompt_block"])
    body = [len(p) for p in a.prompts[slots:slots + block]]
    assert sorted(body) == sorted(n for n, k in traffic["prompt_block"]
                                  for _ in range(k))
    # the filling requests hold the block's shares, to one request
    fill = [len(p) for p in a.prompts[:slots]]
    for n, k in traffic["prompt_block"]:
        assert abs(fill.count(n) - slots * k / block) < 1
    lo, hi = traffic["output"]
    assert all(lo <= g <= hi for g in a.gens[slots:])
    assert all(1 <= g <= hi for g in a.gens[:slots])
    assert max(a.prompt_lens) + hi + 1 <= traffic["max_len"]
    assert all(int(p.max()) < vocab for p in a.prompts)


SKEW = {"topics": 4, "ids_per_topic": 64, "zipf": 1.2}


def test_token_skew_draws_each_prompt_from_one_topic():
    traffic = workload.load_json("traffic", "olmo-1b.decode")
    vocab = workload.load_json("configs", "olmo-1b")["model"]["vocab_size"]
    small = dict(traffic, requests=traffic["slots"] + 60)
    skewed = dict(small, token_skew=SKEW)
    seed = 2**31 + 13
    a = workload.make_queue(skewed, vocab, seed)
    b = workload.make_queue(skewed, vocab, seed)
    c = workload.make_queue(skewed, vocab, seed + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert any(not np.array_equal(x, y) for x, y in zip(a.prompts, c.prompts))
    # the cell's sizes, request by request, as without the key
    plain = workload.make_queue(small, vocab, seed)
    sizes = lambda q: [(len(p), g) for p, g in zip(q.prompts, q.gens)]
    assert sizes(a) == sizes(plain)
    assert all(p.dtype == np.int32 and 0 <= p.min() and p.max() < vocab
               for p in a.prompts)
    # a prompt's most frequent id is its topic's first rank: prompts of
    # one topic draw from that topic's ids alone, and there are at most
    # as many topics as the key says
    by_topic = {}
    for p in a.prompts:
        by_topic.setdefault(int(np.bincount(p).argmax()), []).append(p)
    assert 1 < len(by_topic) <= SKEW["topics"]
    share = workload.zipf_shares(SKEW["ids_per_topic"], SKEW["zipf"])[0]
    for top, prompts in by_topic.items():
        ids = np.concatenate(prompts)
        assert len(np.unique(ids)) <= SKEW["ids_per_topic"]
        # the first rank holds its Zipf share, to within sampling error
        sigma = (share * (1 - share) / ids.size) ** 0.5
        assert abs(np.mean(ids == top) - share) < 5 * sigma


@pytest.mark.parametrize("skew,match", [
    (dict(SKEW, topics=0), "topics must be a whole number >= 1"),
    (dict(SKEW, ids_per_topic=0), r"ids_per_topic must be .* in \[1, 512\]"),
    (dict(SKEW, ids_per_topic=513), r"ids_per_topic must be .* in \[1, 512\]"),
    (dict(SKEW, zipf=0.0), "zipf must be > 0"),
    ({"topics": 4, "zipf": 1.2}, "takes exactly the keys"),
    (dict(SKEW, hot_share=0.5), "takes exactly the keys"),
], ids=["no_topic", "no_ids", "ids_over_vocab", "flat", "missing_key",
        "unknown_key"])
def test_token_skew_refuses_what_it_cannot_draw(skew, match):
    with pytest.raises(ValueError, match=match):
        workload.make_queue(dict(TINY_TRAFFIC, token_skew=skew), 512, 1)


def test_counts_by_hand():
    m = mw.Model(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1,
                 head_dim=4, d_ff=16, vocab_size=10, norm="rmsnorm",
                 norm_eps=1e-5, rope_theta=1e4, tie_embeddings=False)
    # per layer: q 8*8, k 8*4, v 8*4, o 8*8, mlp 3*8*16 = 576; head 80
    assert counts.matmul_params(m) == 2 * 576 + 80
    assert counts.attn_pairs_causal(3) == 6
    assert counts.layer_params(m) == 2 * 576
    # 3-token prefill: layers 2*1152*3, head at the last row only 2*80,
    # attention 4 * 2 layers * 2 heads * 4 * 6 pairs
    assert counts.prefill_flops(m, 3) == 6912 + 160 + 384
    # one decode call, 2 rows, attention lengths 5 + 7
    assert counts.decode_flops(m, 12, 2) == 2 * 1232 * 2 + 4 * 2 * 2 * 4 * 12
    # int8 K+V: 2 * 1 head * 4 * 12 = 96; f32 q and out: 2*4*2*4*2 = 128
    assert counts.decode_attn_bytes(m, 12, 2) == 2 * (96 + 128)
    pk = {"int8_ops": 4.0, "bf16_flops": 2.0, "hbm_bytes_per_s": 1e9}
    # QK^T and PV: 2*2*4*6 = 96 ops each -> 96/4 + 96/2 = 72 s per layer
    assert counts.prefill_attn_seconds(m, 3, pk) == pytest.approx(144.0)


def test_peaks_refuse_unknown_device():
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v4")


# ---- weights and reference ----------------------------------------------

def _program_cfg(conf):
    return runner.program_config(conf)


def test_program_params_are_the_reference_weights():
    m = mw.Model.from_config(TINY_CONF)
    params = runner.make_params(2**33 + 5, m, _program_cfg(TINY_CONF))
    key = mw.base_key(2**33 + 5)
    w1 = mw.layer_weights(key, 1, m)
    seg = params["segments"][0]
    # the same draws; jit may round the scaling differently by an ulp
    same = dict(rtol=3e-7, atol=0)
    np.testing.assert_allclose(seg["attn"]["wk"]["w"][1], w1["wk"], **same)
    np.testing.assert_allclose(seg["mlp"]["w_out"]["w"][1], w1["w_out"],
                               **same)
    np.testing.assert_allclose(seg["norm2"]["scale"][1], w1["norm2"], **same)
    np.testing.assert_allclose(params["lm_head"]["w"],
                               mw.head_weights(key, m)["lm_head"], **same)
    other = mw.base_key(5)      # the high bits of the seed count
    assert not np.array_equal(mw.layer_weights(other, 1, m)["wk"], w1["wk"])
    # a vocabulary the program pads: the padding rows are zero
    conf = json.loads(json.dumps(TINY_CONF))
    conf["model"].update(vocab_size=500, tie_embeddings=True)
    m = mw.Model.from_config(conf)
    params = runner.make_params(5, m, _program_cfg(conf))
    table = np.asarray(params["embed"]["table"])
    assert table.shape == (512, 128)
    assert not table[500:].any() and table[:500].all(axis=1).any()


@pytest.mark.parametrize("norm,tied", [("rmsnorm", False),
                                       ("nonparam_ln", True)])
def test_reference_matches_program_logits(norm, tied):
    """In float32 with float attention the program's forward and the
    reference agree to rounding."""
    from repro.models import transformer as T
    conf = json.loads(json.dumps(TINY_CONF))
    conf["model"].update(norm=norm, tie_embeddings=tied, dtype="float32")
    conf["norm_eps"] = 1e-6 if norm == "rmsnorm" else 1e-5   # the program's
    m = mw.Model.from_config(conf)
    pcfg = _program_cfg(conf).replace(attn_mode="float")
    params = runner.make_params(7, m, pcfg)
    rng = np.random.default_rng(0)
    seq = np.zeros((41,), np.int32)
    seq[:30] = rng.integers(0, m.vocab_size, 30)
    forward = jax.jit(lambda p, t: T.forward(p, t[None], pcfg)[0][0])
    with jax.default_matmul_precision("highest"):
        for n in range(30, 41):          # greedy, one token at a time
            seq[n] = int(jnp.argmax(forward(params, jnp.asarray(seq))[n - 1]))
        want = np.asarray(forward(params, jnp.asarray(seq)))[:40]
    got = reference.compare(7, m, seq[:30], seq[30:])
    # every served token is the program's first: the reference ranks it
    # first too, or within rounding of its best
    assert got["gap"].shape == (11,)
    assert float(got["gap"].max()) < 1e-4
    key = mw.base_key(7)
    h = reference._hidden(key, seq[:40], m, False)[:40]
    logits = np.asarray(reference._logits(key, h, m, False))
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)


# ---- the whole run, sound, controlled and broken -------------------------

def test_sound_run_passes_and_control_fails():
    out, res = tiny_run(seed=2**31 + 3, control=True)
    ok, checks = runner.verdict(out, res["gap"], TINY_TRAFFIC)
    assert ok, checks
    assert out.compiles == 0, out.compile_events
    assert out.failed == 0 and out.attempted > 0
    # the control, put in the program's place, is not correct
    ctrl_ok, ctrl_checks = runner.verdict(out, res["ctrl_gap"], TINY_TRAFFIC)
    assert not ctrl_ok, ctrl_checks
    m = runner.metrics(out, {"name": "olmo-1b.decode"}, BENCH, trace=False)
    assert set(m) == {"setup_s", "tok_s", "itl_p95_ms"}
    assert all(v["value"] > 0 for v in m.values())
    layer = runner.metrics(out, {"name": "olmo-1b.decode"}, BENCH,
                           trace=True)
    # without a trace only the proxy's own metrics can be read
    assert set(layer) == {"sched.admit_share", "kv.live_share", "dev.mfu"}
    # a cell whose token-gap tail is too unsteady to bound end to end
    # reports it per layer instead
    cell = {"name": "mistral-nemo-12b-4l.longctx"}
    assert set(runner.metrics(out, cell, BENCH, trace=False)) == {
        "setup_s", "tok_s"}
    assert set(runner.metrics(out, cell, BENCH, trace=True)) == {
        "sched.itl_p95_ms", "kv.live_share", "dev.mfu"}


def test_warmup_compiles_every_prompt_length():
    traffic = dict(TINY_TRAFFIC, prompt_block=[[24, 1], [16, 2], [8, 1]])
    out = runner.run_cell("tiny", traffic, TINY_CONF, seed=11, seconds=1.0,
                          trace=False, t_start=time.perf_counter(),
                          peaks=PEAKS)
    assert {n for _, n in out.run.admissions} == {8, 16, 24}
    assert out.compiles == 0, out.compile_events


def test_traced_run_checks_the_whole_window(monkeypatch):
    """A traced run profiles the window's first ``trace_seconds`` and
    serves on: the per-layer record ends where the profiler stopped, the
    check compares every request the whole window finished."""
    marks = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k:
                        marks.append(("start", time.perf_counter())))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda:
                        marks.append(("stop", time.perf_counter())))
    monkeypatch.setattr(trace_reduce, "xplane_file", lambda d: d)
    monkeypatch.setattr(trace_reduce, "read_planes", lambda path: {})
    monkeypatch.setattr(trace_reduce, "summarize", lambda planes: "summary")
    traffic = dict(TINY_TRAFFIC, trace_seconds=0.4)
    seed = 2**31 + 21
    out = runner.run_cell("tiny", traffic, TINY_CONF, seed=seed,
                          seconds=1.5, trace=True,
                          t_start=time.perf_counter(), peaks=PEAKS)
    assert [k for k, _ in marks] == ["start", "stop"]
    assert out.run.trace == "summary"
    assert 0.4 <= out.run.window_s < 1.0 and out.window_s >= 1.5
    t_stop = marks[1][1]
    assert out.run.decode_calls and all(c["t"] < t_stop
                                        for c in out.run.decode_calls)
    assert out.tokens > sum(c["slots"] for c in out.run.decode_calls)
    res = runner.check(out, traffic, TINY_CONF, seed)
    ok, checks = runner.verdict(out, res["gap"], traffic)
    assert ok, checks
    assert checks["tokens_compared"]["value"] >= traffic["sample_tokens"]


class _Counting:
    """The paged engine with a program counter of its decode calls, kept
    cumulative in its own state, one on the device and one on the host."""

    def __init__(self, engine):
        self.__dict__["_e"] = engine
        self.__dict__["n"] = 0

    def __getattr__(self, name):
        return getattr(self._e, name)

    def decode(self, tokens, cache):
        self.__dict__["n"] += 1
        return self._e.decode(tokens, cache)

    def counters(self, cache):
        return {"decode_calls": jnp.asarray(self.n, jnp.int32),
                "decode_calls_on_host": self.n}


@pytest.mark.parametrize("counting", [True, False], ids=["counters",
                                                         "none"])
def test_program_counters_cover_the_window(counting):
    out = runner.run_cell("tiny", TINY_TRAFFIC, TINY_CONF, seed=2**31 + 23,
                          seconds=1.0, trace=False,
                          t_start=time.perf_counter(), peaks=PEAKS,
                          wrap_engine=_Counting if counting else None)
    calls = len(out.run.decode_calls)
    assert calls > 0
    want = ({"decode_calls": calls, "decode_calls_on_host": calls}
            if counting else {})
    assert out.run.counters == want


class _Broken:
    """The paged engine with its decode step broken underneath."""

    def __init__(self, engine, fault):
        self.__dict__["_e"] = engine
        self.__dict__["_fault"] = fault

    def __getattr__(self, name):
        return getattr(self._e, name)

    def decode(self, tokens, cache):
        if self._fault == "stale_state":
            keep = jax.tree.map(jnp.copy, cache)
            logits, _ = self._e.decode(tokens, cache)
            return logits, keep
        logits, cache = self._e.decode(tokens, cache)
        half = logits.shape[0] // 2
        if self._fault == "half_batch":
            logits = jnp.concatenate([logits[:half], logits[:half]])
        elif self._fault == "altered_token":
            logits = -logits
        return logits, cache


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "altered_token"])
def test_broken_timed_path_is_not_correct(fault):
    out, res = tiny_run(seed=2**31 + 4,
                        wrap_engine=lambda e: _Broken(e, fault))
    ok, checks = runner.verdict(out, res["gap"], TINY_TRAFFIC)
    assert not ok, checks


def test_bench_exits_without_a_chip(capsys):
    rc = bench.main(["--workload", "olmo-1b.decode", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_compile_watch_counts_only_when_armed():
    watch = runner.compile_watch()
    watch.events.clear()
    watch.armed = True
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    watch.armed = False
    assert watch.count >= 1
    n = watch.count
    jax.jit(lambda x: x * 5 - 2)(jnp.arange(9.0)).block_until_ready()
    assert watch.count == n


# ---- trace reduction -----------------------------------------------------

def _hlo_kernel(name, n):
    """A Pallas kernel's operation as the trace names it: its whole HLO
    instruction, named after the kernel's ``name=``."""
    return (f"%{name}.{n} = f32[8,128]{{1,0}} custom-call(f32[8,128]{{1,0}} "
            f'%param.{n}), custom_call_target="tpu_custom_call"')


DECODE_KERNEL = _hlo_kernel("splitmax_decode_fused_paged_pallas", 7)
PREFILL_KERNEL = _hlo_kernel("splitmax_attention_pallas", 2)


def _synthetic_planes():
    ms = 1_000_000
    return {
        "/host:CPU": {"python": [
            ("bench.window", 0, 100 * ms),
            ("bench.decode", 5 * ms, 6 * ms),
            ("bench.admit", 50 * ms, 52 * ms),
        ]},
        "/device:TPU:0": {
            "XLA Modules": [("jit_decode_step(1)", 10 * ms, 30 * ms),
                            ("jit_prefill_step(2)", 55 * ms, 85 * ms),
                            ("jit_greedy(3)", 90 * ms, 91 * ms),
                            ("jit_decode_step(1)", 95 * ms, 120 * ms)],
            "XLA Ops": [("fusion.1", 10 * ms, 20 * ms),
                        (DECODE_KERNEL, 20 * ms, 30 * ms),
                        ("while.4", 55 * ms, 85 * ms),
                        (PREFILL_KERNEL, 55 * ms, 65 * ms),
                        ("fusion.9", 60 * ms, 85 * ms),
                        ("fusion.3", 90 * ms, 91 * ms),
                        (DECODE_KERNEL, 95 * ms, 120 * ms)],
        },
    }


def test_trace_reduction_on_a_synthetic_trace():
    t = trace_reduce.summarize(_synthetic_planes())
    assert t.window_s == pytest.approx(0.1)
    # busy: 10-30, 55-85, 90-91, 95-100 (clipped) = 56 ms
    assert t.busy_s == pytest.approx(0.056)
    assert t.module_s == pytest.approx({"decode": 0.025, "prefill": 0.030,
                                        "other": 0.001})
    assert t.module_n == {"decode": 2, "prefill": 1, "other": 1}
    assert t.kernel_s == pytest.approx({
        "decode/splitmax_decode_fused_paged_pallas": 0.015,
        "prefill/splitmax_attention_pallas": 0.010})
    # ops are named with the program that ran them; the loop that holds
    # the prefill's two operations is not named
    assert [n for n, _ in t.top_ops[:2]] == ["prefill/fusion.9",
                                             "decode/" + DECODE_KERNEL]
    assert not any("while" in n for n, _ in t.top_ops)
    assert t.top_ops[1][1] == pytest.approx(0.015)
    # idle 0-10, 30-55, 85-90, 91-95 ms; the host's spans cover 5-6 of
    # the first and 50-52 of the second, the scheduler the rest
    assert dict(t.idle_by_span) == pytest.approx(
        {"scheduler": 0.041, "admit": 0.002, "decode": 0.001})


def test_trace_reduction_on_a_recorded_trace():
    """The first quarter second of a traced window of
    ``mistral-nemo-12b-4l.longctx`` on one TPU v5e (``--keep-trace``): two
    decode steps, each a ``while`` over the layers that holds the fused
    paged decode kernel and the pool's copies."""
    raw = json.loads((HERE / "testdata" /
                      "longctx_trace_excerpt.json").read_text())
    planes = {p: {ln: [tuple(ev) for ev in evs] for ln, evs in lines.items()}
              for p, lines in raw.items()}
    t = trace_reduce.summarize(planes)
    assert t.window_s == pytest.approx(0.25)
    idle = sum(s for _, s in t.idle_by_span)
    assert t.busy_s + idle == pytest.approx(t.window_s, abs=1e-6)
    assert t.module_n["decode"] == 2
    kernel = t.kernel_s["decode/splitmax_decode_fused_paged_pallas"]
    assert 0 < kernel < t.module_s["decode"] <= t.busy_s
    # the loops are left out of the top operations, so no time is named
    # twice: what the top operations hold fits in the busy time
    assert not any("%while" in n for n, _ in t.top_ops)
    assert sum(s for _, s in t.top_ops) <= t.busy_s
    assert "splitmax_decode_fused_paged" in t.top_ops[0][0]
    assert t.top_ops[0][1] == pytest.approx(kernel, rel=1e-5)


def _layer_readings(planes):
    """Every per-layer metric of ``olmo-1b.decode`` over a trace, with a
    fixed record of the window's calls."""
    run = runner.Run(
        cell="olmo-1b.decode", family=runner.family(TINY_CONF),
        m=mw.Model.from_config(TINY_CONF), peaks=PEAKS, window_s=0.1,
        decode_calls=[{"t": 0.0, "slots": 4, "live_tokens": 4000,
                       "live_blocks": 128, "pool_blocks": 256}] * 2,
        admissions=[(0.05, 24)], admit_span_s=0.002, itl_ms=[20.0],
        counters={}, trace=trace_reduce.summarize(planes))
    return metric_readers.read_all(BENCH["per_layer"], run.cell, run)


def test_a_second_kernel_leaves_the_attention_roofline():
    """A second named kernel in the decode program, an operation that
    takes the attention kernel's output and a buffer's allocation are
    timed apart from the attention kernel, which its roofline reads."""
    ms = 1_000_000
    planes = _synthetic_planes()
    ops = planes["/device:TPU:0"]["XLA Ops"]
    assert ops[0] == ("fusion.1", 10 * ms, 20 * ms)
    ops[0:1] = [
        (_hlo_kernel("grouped_expert_matmul_pallas", 3), 10 * ms, 15 * ms),
        ('%custom-call.4 = s8[8,128]{1,0} custom-call(), '
         'custom_call_target="AllocateBuffer"', 15 * ms, 16 * ms),
        ("%convert.5 = bf16[8,128]{1,0} convert(f32[8,128]{1,0} "
         "%splitmax_decode_fused_paged_pallas.7)", 16 * ms, 20 * ms)]
    t = trace_reduce.summarize(planes)
    assert t.kernel_s == pytest.approx({
        "decode/splitmax_decode_fused_paged_pallas": 0.015,
        "decode/grouped_expert_matmul_pallas": 0.005,
        "prefill/splitmax_attention_pallas": 0.010})
    before = _layer_readings(_synthetic_planes())
    assert {"kern.decode_attn_roofline",
            "kern.prefill_attn_roofline"} <= set(before)
    assert _layer_readings(planes) == before


def _recorded(name):
    raw = json.loads((HERE / "testdata" / name).read_text())
    return {p: {ln: [tuple(ev) for ev in evs] for ln, evs in lines.items()}
            for p, lines in raw.items()}


@pytest.mark.parametrize("name,kernels", [
    ("chat_trace_excerpt.json", {"decode/splitmax_decode_fused_paged_pallas",
                                 "prefill/splitmax_attention_pallas"}),
    ("longctx_trace_excerpt.json",
     {"decode/splitmax_decode_fused_paged_pallas"}),
])
def test_recorded_kernels_are_timed_by_name(name, kernels):
    """On one TPU v5e's trace each kernel's time is that of the operations
    whose own instruction it is, clipped to the window; the operations that
    take its output and the buffers' allocations are no kernel's."""
    planes = _recorded(name)
    t = trace_reduce.summarize(planes)
    assert set(t.kernel_s) == kernels
    a, b = planes["/host:CPU"]["bench"][0][1:]
    assert planes["/host:CPU"]["bench"][0][0] == "bench.window"
    ops = planes["/device:TPU:0"]["XLA Ops"]
    for key, seconds in t.kernel_s.items():
        kernel = key.split("/")[1]
        own = sum(min(e, b) - max(s, a) for n, s, e in ops
                  if n.startswith(f"%{kernel}.") and e > a and s < b)
        assert seconds == pytest.approx(own / 1e9, rel=1e-9)
    takers = [n for n, _, _ in ops if trace_reduce.kernel_name(n) is None
              and any(f"%{k.split('/')[1]}." in n for k in t.kernel_s)]
    assert any(n.startswith("%convert") for n in takers)
    allocs = [n for n, _, _ in ops
              if 'custom_call_target="AllocateBuffer"' in n]
    assert allocs and not any(map(trace_reduce.kernel_name, allocs))


def test_readers_exist_for_every_per_layer_metric():
    for spec in BENCH["per_layer"]:
        assert hasattr(metric_readers.load(spec["name"]), "read")
