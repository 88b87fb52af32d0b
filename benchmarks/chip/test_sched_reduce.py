"""CPU tests of the reduction of the scheduler's spans (`sched_reduce`)."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE)]

import sched_reduce  # noqa: E402
import trace_reduce  # noqa: E402

MS = 1_000_000


def _planes(sched=True):
    """100 ms of window; the device busy 10-30, 55-85, 90-91, 95-100 ms."""
    host = [("bench.window", 0, 100 * MS), ("bench.decode", 5 * MS, 6 * MS),
            ("bench.admit", 50 * MS, 52 * MS)]
    spans = [
        # the read-back of the step that was open when the profiler started
        ("sched.wait", 2 * MS, 8 * MS),
        ("sched.step", 9 * MS, 50 * MS),
        ("sched.admit", 12 * MS, 20 * MS), ("sched.wait", 15 * MS, 18 * MS),
        ("sched.wait", 35 * MS, 45 * MS),
        ("sched.step", 50 * MS, 96 * MS),
        ("sched.admit", 52 * MS, 60 * MS), ("sched.wait", 58 * MS, 60 * MS),
        ("sched.wait", 86 * MS, 89 * MS),
        ("sched.step", 96 * MS, 120 * MS),
    ]
    return {
        "/host:CPU": {"python": host + (spans if sched else [])},
        "/device:TPU:0": {
            "XLA Modules": [("jit_decode_step(1)", 10 * MS, 30 * MS),
                            ("jit_prefill_step(2)", 55 * MS, 85 * MS),
                            ("jit_greedy(3)", 90 * MS, 91 * MS),
                            ("jit_decode_step(1)", 95 * MS, 120 * MS)],
            "XLA Ops": [("fusion.1", 10 * MS, 20 * MS),
                        ("custom-call.7", 20 * MS, 30 * MS),
                        ("while.4", 55 * MS, 85 * MS),
                        ("custom-call.2", 55 * MS, 65 * MS),
                        ("fusion.9", 60 * MS, 85 * MS),
                        ("fusion.3", 90 * MS, 91 * MS),
                        ("custom-call.7", 95 * MS, 120 * MS)],
        },
    }


def test_sched_spans_on_a_synthetic_trace():
    s = sched_reduce.summarize(_planes())
    # idle 0-10, 30-55, 85-90, 91-95 ms; steps cover 9-100 (the last cut
    # at the window's end), waits 2-8, 15-18, 35-45, 58-60, 86-89
    assert s.window_s == pytest.approx(0.1)
    assert s.idle_s == pytest.approx(0.044)
    assert s.step_n == 3
    assert s.step_host_s == pytest.approx(0.091 - 0.018)
    assert s.admit_s == pytest.approx(0.016)
    # idle in a step: 9-10, 30-55, 85-90, 91-95 (35 ms), of which 35-45
    # and 86-89 inside waits; idle inside waits: 2-8, 35-45, 86-89
    assert s.idle_host_s == pytest.approx(0.022)
    assert s.idle_wait_s == pytest.approx(0.019)
    assert sched_reduce.metrics(s) == pytest.approx({
        "sched.host_ms_per_step": 73 / 3, "sched.admit_span_share": 16.0,
        "dev.idle_host_share": 22.0, "dev.idle_wait_share": 19.0})
    # the device reduction reads the same trace as it did without the spans
    assert trace_reduce.summarize(_planes()) == trace_reduce.summarize(
        _planes(sched=False))


def test_a_trace_without_sched_spans_reads_nothing():
    s = sched_reduce.summarize(_planes(sched=False))
    assert s.step_n == 0 and s.idle_s == pytest.approx(0.044)
    assert set(sched_reduce.metrics(s).values()) == {None}


def test_excerpt_keeps_the_sched_spans():
    ex = sched_reduce.excerpt(_planes(), 0.05)
    assert ex["/host:CPU"]["sched"] == [
        ["sched.wait", 2 * MS, 8 * MS], ["sched.step", 9 * MS, 50 * MS],
        ["sched.admit", 12 * MS, 20 * MS], ["sched.wait", 15 * MS, 18 * MS],
        ["sched.wait", 35 * MS, 45 * MS]]
    assert ex["/host:CPU"]["bench"] == trace_reduce.excerpt(
        _planes(), 0.05)["/host:CPU"]["bench"]


def test_sched_spans_on_a_recorded_trace():
    """The first quarter second of a traced window of ``olmo-1b.chat`` on
    one TPU v5e (``sched_reduce.excerpt``): the read-back of the step that
    was open when the profiler started, two releases, then one step that
    admits two requests and decodes."""
    raw = json.loads((HERE / "testdata" /
                      "chat_trace_excerpt.json").read_text())
    planes = {p: {ln: [tuple(ev) for ev in evs] for ln, evs in lines.items()}
              for p, lines in raw.items()}
    s = sched_reduce.summarize(planes)
    t = trace_reduce.summarize(planes)
    assert s.idle_s == pytest.approx(t.window_s - t.busy_s, abs=1e-9)
    assert 0 < s.idle_host_s and 0 < s.idle_wait_s
    assert s.idle_host_s + s.idle_wait_s <= s.idle_s
    assert s.step_n == 1
    # spans nest: after the first recorded step begins, every admission
    # lies in a step and every wait in a step or an admission; before it,
    # only the read-back of the step the profiler's start cut off
    spans = sorted((st, e, n) for n, st, e in raw["/host:CPU"]["sched"])
    steps = [sp for sp in spans if sp[2] == "sched.step"]
    admits = [sp for sp in spans if sp[2] == "sched.admit"]
    waits = [sp for sp in spans if sp[2] == "sched.wait"]

    def inside(a, outer):
        return any(o[0] <= a[0] and a[1] <= o[1] for o in outer)

    assert [w for w in waits if w[0] < steps[0][0]] == waits[:1]
    assert len(admits) == 2 and all(inside(a, steps) for a in admits)
    assert all(inside(w, steps + admits) for w in waits[1:])
    # one read-back in each admission: its first token
    assert [sum(inside(w, [a]) for w in waits) for a in admits] == [1, 1]
