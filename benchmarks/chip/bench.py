"""Serving benchmark of the paged int8 split-softmax path on one TPU chip.

    python benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one chip.  The cell (``BENCHMARK.json``'s ``workloads``) names
a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<cell>.json``); the configuration names its model family
(``families/<family>.py``: weights, reference and counts).  Set-up makes
the weights on the device from the seed, builds the queue, compiles every
shape the window uses and fills the slots; then ``--seconds`` of serving
are measured; then the served tokens are checked against the family's
float32 reference.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer ones from a profiler trace of the window.  Earlier lines on
standard error give the counts behind them, the compilations inside the
window and each number compared beside its limit; the last line of standard
output is one JSON object.  Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=pathlib.Path, default=None,
                    help="write an account of the trace's event names and "
                         "an excerpt of its first quarter second here "
                         "(with --trace 1)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = find_cell(bench, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell["chips"]}

    import counts
    import runner
    import workload
    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    # cache every program, however small or quick, so that a second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    peaks = counts.peaks(device["kind"])
    traffic = workload.load_json("traffic", args.workload)
    conf = workload.load_json("configs", cell["config"])

    out = runner.run_cell(args.workload, traffic, conf, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_start=T_START, peaks=peaks,
                          keep_trace=args.keep_trace)
    log(f"window: {out.window_s:.3f} s, {len(out.run.decode_calls)} decode "
        f"calls, {len(out.run.admissions)} admissions, {out.tokens} "
        f"tokens, {len(out.itl_ms)} token gaps, {out.failed} failed")
    if out.itl_ms:
        q = np.percentile(out.itl_ms, [50, 90, 95, 99, 100])
        log("token gaps (ms): p50 {:.1f}, p90 {:.1f}, p95 {:.1f}, p99 {:.1f}, "
            "max {:.1f}".format(*q))
    log(f"compiles_in_window: {out.compiles} {out.compile_events}")
    log(f"memory_peak_bytes: {out.memory_peak}")

    t_ref = time.perf_counter()
    res = runner.check(out, traffic, conf, args.seed)
    gap = res["gap"]
    correct, checks = runner.verdict(out, gap, traffic)
    log(f"reference: {gap.size} served tokens of "
        f"{len(runner.sample(out, traffic, args.seed))} requests compared "
        f"in {time.perf_counter() - t_ref:.1f} s; mean gap "
        f"{float(gap.mean()) if gap.size else 0.0!r}, tokens off the "
        f"reference's first {int((gap > 0).sum())}")

    result = {
        "correct": correct,
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": runner.metrics(out, cell, bench, bool(args.trace)),
        "device": dict(device, memory_peak_bytes=out.memory_peak),
    }
    if args.trace:
        t = out.run.trace
        result["device"].update(busy_s=t.busy_s, window_s=t.window_s)
        result["breakdown"] = {"device_ops": [list(x) for x in t.top_ops],
                               "idle_gaps": [list(x) for x in
                                             t.idle_by_span]}
        log(f"trace: module seconds {t.module_s}, executions "
            f"{t.module_n}, kernel seconds by name {t.kernel_s}")
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
