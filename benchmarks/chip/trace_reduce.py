"""From a profiler trace to device times, on the chip's own clock.

The traced run wraps its window in a host span ``bench.window`` and each
engine call in ``bench.<kind>``.  The device plane's ``XLA Modules`` line
says which jitted program ran when, and its ``XLA Ops`` line every
operation; an operation is attributed to the module whose interval holds
it.  A module is classed by its jitted function's name (``decode_step``,
``prefill_step``).  A Pallas kernel is an operation whose own instruction
is a ``tpu_custom_call``, and is known by that instruction's name, which is
the ``name=`` its ``pallas_call`` was given: its time is summed per module
class and kernel, ``kernel_s["decode/splitmax_decode_fused_paged_pallas"]``,
so that two kernels in one program are timed apart, and an operation that
only takes a kernel's output, or allocates its buffer, is no kernel's
time.  An operation that holds others, as a ``while`` loop over the layers
holds their operations, counts toward the device's busy time but is not
itself named among the top operations: its time is theirs.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Tuple

MODULE_CLASSES = (("decode", "decode_step"), ("prefill", "prefill_step"))
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_HLO_SUFFIX = re.compile(r"\.\d+$")

Interval = Tuple[int, int]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    module_s: Dict[str, float]          # class -> device seconds
    module_n: Dict[str, int]            # class -> executions
    kernel_s: Dict[str, float]          # "<class>/<kernel>" -> seconds
    top_ops: List[Tuple[str, float]]    # ("<class>/<op>", device seconds)
    idle_by_span: List[Tuple[str, float]]


def xplane_file(trace_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line):
    return [(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]


def read_planes(path) -> Dict[str, Dict[str, list]]:
    """plane name -> line name -> [(event name, start_ns, end_ns)]."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(_events(line))
    return out


def module_class(name: str) -> str:
    for cls, mark in MODULE_CLASSES:
        if mark in name:
            return cls
    return "other"


def kernel_name(op_name: str) -> Optional[str]:
    """The Pallas kernel an operation runs, or None.

    An operation's name in the trace is its whole HLO instruction,
    ``%<name>.<N> = <shape> custom-call(<operands>), custom_call_target=
    "tpu_custom_call", ...``.  Its own instruction is the text before
    `` = ``; without ``%`` and XLA's ``.N`` suffix that is the kernel's
    ``name=``.  An operation that names a kernel only among its operands,
    or a custom call of another target, runs no kernel."""
    own, sep, rest = op_name.partition(" = ")
    if not sep or KERNEL_TARGET not in rest:
        return None
    return _HLO_SUFFIX.sub("", own.lstrip("%"))


def _clip(iv: Interval, win: Interval) -> Optional[Interval]:
    a, b = max(iv[0], win[0]), min(iv[1], win[1])
    return (a, b) if b > a else None


def _union(ivs: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def summarize(planes: Dict[str, Dict[str, list]],
              window_name: str = "bench.window") -> TraceSummary:
    """Reduce one traced window to device times (first TPU plane)."""
    host = [ev for name, lines in planes.items() if not name.startswith(
        "/device:") for evs in lines.values() for ev in evs]
    wins = [(s, e) for n, s, e in host if n == window_name]
    if not wins:
        raise ValueError(f"no {window_name!r} span in the trace")
    win = wins[0]
    spans = sorted((s, e, n[len("bench."):]) for n, s, e in host
                   if n.startswith("bench.") and n != window_name)
    dev = sorted(n for n in planes if n.startswith("/device:TPU:"))
    if not dev:
        raise ValueError("no TPU device plane in the trace")
    lines = planes[dev[0]]
    modules = sorted((s, e, n) for n, s, e in lines.get("XLA Modules", []))
    # a holder sorts before the first operation it holds
    ops = sorted(((s, e, n) for n, s, e in lines.get("XLA Ops", [])),
                 key=lambda op: (op[0], -op[1]))

    module_s: Dict[str, float] = collections.defaultdict(float)
    module_n: Dict[str, int] = collections.defaultdict(int)
    for s, e, n in modules:
        iv = _clip((s, e), win)
        if iv:
            cls = module_class(n)
            module_s[cls] += (iv[1] - iv[0]) / 1e9
            module_n[cls] += 1

    kernel_s: Dict[str, float] = collections.defaultdict(float)
    per_op: Dict[str, float] = collections.defaultdict(float)
    busy: List[Interval] = []
    mi = 0
    for i, (s, e, n) in enumerate(ops):
        iv = _clip((s, e), win)
        if not iv:
            continue
        busy.append(iv)
        if i + 1 < len(ops) and ops[i + 1][0] < e and ops[i + 1][1] <= e:
            continue                    # it holds the operations after it
        dt = (iv[1] - iv[0]) / 1e9
        while mi < len(modules) and modules[mi][1] <= s:
            mi += 1
        cls = (module_class(modules[mi][2])
               if mi < len(modules) and modules[mi][0] <= s else "other")
        per_op[f"{cls}/{n}"] += dt
        kernel = kernel_name(n)
        if kernel is not None:
            kernel_s[f"{cls}/{kernel}"] += dt
    busy = _union(busy)
    busy_s = sum(b - a for a, b in busy) / 1e9

    idle: Dict[str, float] = collections.defaultdict(float)
    gaps, prev = [], win[0]
    for a, b in busy + [(win[1], win[1])]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    si = 0
    for a, b in gaps:
        # each idle stretch goes to the engine call the host was in, and
        # to the scheduler between calls
        while si < len(spans) and spans[si][1] <= a:
            si += 1
        covered = 0
        for s, e, kind in spans[si:]:
            if s >= b:
                break
            part = min(e, b) - max(s, a)
            if part > 0:
                idle[kind] += part / 1e9
                covered += part
        idle["scheduler"] += (b - a - covered) / 1e9

    return TraceSummary(
        window_s=(win[1] - win[0]) / 1e9, busy_s=busy_s,
        module_s=dict(module_s), module_n=dict(module_n),
        kernel_s=dict(kernel_s),
        top_ops=sorted(per_op.items(), key=lambda kv: -kv[1])[:10],
        idle_by_span=sorted(idle.items(), key=lambda kv: -kv[1])[:10])


def describe(planes: Dict[str, Dict[str, list]], per_line: int = 40) -> Dict:
    """A short account of a trace's planes, lines and event names, to read
    by hand before trusting the reduction."""
    out = {}
    for pname, lines in planes.items():
        out[pname] = {}
        for lname, evs in lines.items():
            names = collections.Counter(n for n, _, _ in evs)
            out[pname][lname] = {
                "events": len(evs),
                "span_ns": ([min(s for _, s, _ in evs),
                             max(e for _, _, e in evs)] if evs else None),
                "names": names.most_common(per_line)}
    return out


def excerpt(planes: Dict[str, Dict[str, list]], seconds: float,
            window_name: str = "bench.window") -> Dict:
    """The first ``seconds`` of the traced window: the window span cut to
    that length, the ``bench.*`` host spans and the TPU lines' events in
    it.  Small enough to keep as a test's recorded trace."""
    host = [ev for name, lines in planes.items() if not name.startswith(
        "/device:") for evs in lines.values() for ev in evs]
    a = min(s for n, s, _ in host if n == window_name)
    b = a + int(seconds * 1e9)
    out = {"/host:CPU": {"bench": [
        [n, max(s, a), min(e, b)] for n, s, e in host
        if n.startswith("bench.") and s < b and e > a]}}
    for pname, lines in planes.items():
        if pname.startswith("/device:TPU:0"):
            out[pname] = {ln: [[n, s, e] for n, s, e in evs if s < b and e > a]
                          for ln, evs in lines.items()
                          if ln in ("XLA Modules", "XLA Ops")}
    return out
