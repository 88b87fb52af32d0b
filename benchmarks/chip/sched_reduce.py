"""The scheduler's own spans in a profiler trace, on the device's clock.

`repro.launch.scheduler.run_schedule` writes three host spans into the
profiler's trace: ``sched.step`` (one iteration of its loop), ``sched.admit``
(one admission: prefill dispatch, first-token selection and read-back) and
``sched.wait`` (the host blocked on a device value).  Over the traced window
that `trace_reduce.summarize` reads (``bench.window``), this reduces them to
the scheduler's host time per step and its admissions' share, and splits the
device's idle time by what the host was doing in it:

  * ``idle_host_s``: no device op, the host inside a ``sched.step`` and
    outside every ``sched.wait``: host work between device calls;
  * ``idle_wait_s``: no device op, the host inside a ``sched.wait``: a slow
    read-back, or device work the trace did not record.

The rest of the idle time lies outside any recorded step.  The profiler
drops a span that was open when it started or stopped, so the steps that
hold the window's two ends are not in the trace; a traced run's window
opens and closes inside an engine call, so those two steps are its rest.

`runner.run_cell` does not call this yet, so the four metrics of
`metrics` are not in a run's result line (PERF.md, section 7).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import trace_reduce
from trace_reduce import Interval, _clip, _union


@dataclasses.dataclass
class SchedSummary:
    window_s: float
    idle_s: float            # no device op
    step_n: int              # sched.step spans in the window
    step_host_s: float       # their time outside every sched.wait
    admit_s: float           # sched.admit spans, summed
    idle_host_s: float       # idle, in a sched.step, outside every wait
    idle_wait_s: float       # idle, inside a sched.wait


def _seconds(ivs: List[Interval]) -> float:
    return sum(b - a for a, b in ivs) / 1e9


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _host(planes) -> list:
    return [ev for name, lines in planes.items()
            if not name.startswith("/device:")
            for evs in lines.values() for ev in evs]


def summarize(planes: Dict[str, Dict[str, list]],
              window_name: str = "bench.window") -> SchedSummary:
    """Reduce the ``sched.*`` spans of one traced window (first TPU plane
    for the device's busy time, as `trace_reduce.summarize` takes it)."""
    host = _host(planes)
    wins = [(s, e) for n, s, e in host if n == window_name]
    if not wins:
        raise ValueError(f"no {window_name!r} span in the trace")
    win = wins[0]

    def spans(name):
        return [iv for iv in (_clip((s, e), win) for n, s, e in host
                              if n == name) if iv]

    steps, admits, waits = (spans(f"sched.{k}")
                            for k in ("step", "admit", "wait"))
    dev = sorted(n for n in planes if n.startswith("/device:TPU:"))
    if not dev:
        raise ValueError("no TPU device plane in the trace")
    busy = _union([iv for iv in (_clip((s, e), win) for _, s, e in
                                 planes[dev[0]].get("XLA Ops", [])) if iv])
    gaps, prev = [], win[0]
    for a, b in busy + [(win[1], win[1])]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)

    in_step, in_wait = _union(steps), _union(waits)
    idle_in_step = _intersect(gaps, in_step)
    return SchedSummary(
        window_s=(win[1] - win[0]) / 1e9, idle_s=_seconds(gaps),
        step_n=len(steps),
        step_host_s=_seconds(in_step) - _seconds(_intersect(in_step,
                                                             in_wait)),
        admit_s=_seconds(admits),
        idle_host_s=(_seconds(idle_in_step)
                     - _seconds(_intersect(idle_in_step, in_wait))),
        idle_wait_s=_seconds(_intersect(gaps, in_wait)))


def metrics(s: SchedSummary) -> Dict[str, Optional[float]]:
    """The four per-layer numbers the spans give, by their metric names.
    None where the trace holds no ``sched.step``: a program that writes no
    spans."""
    names = ("sched.host_ms_per_step", "sched.admit_span_share",
             "dev.idle_host_share", "dev.idle_wait_share")
    if not s.step_n:
        return dict.fromkeys(names)
    return dict(zip(names, (
        1e3 * s.step_host_s / s.step_n,
        100.0 * s.admit_s / s.window_s,
        100.0 * s.idle_host_s / s.window_s,
        100.0 * s.idle_wait_s / s.window_s)))


def excerpt(planes: Dict[str, Dict[str, list]], seconds: float,
            window_name: str = "bench.window") -> Dict:
    """`trace_reduce.excerpt` with the ``sched.*`` spans that reach into it,
    cut to it, on a host line ``sched`` of their own."""
    out = trace_reduce.excerpt(planes, seconds, window_name)
    host = _host(planes)
    a = min(s for n, s, _ in host if n == window_name)
    b = a + int(seconds * 1e9)
    out["/host:CPU"]["sched"] = [[n, max(s, a), min(e, b)]
                                 for n, s, e in host
                                 if n.startswith("sched.") and s < b and e > a]
    return out
