"""Readings for a cell's correctness limit: the program and its control.

    python benchmarks/chip/limits.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--control-seeds 1,2,3]

In one process, for each seed: one run of the cell as ``bench.py`` makes it
(set-up, a window at the cell's own load), then the reference of the
configuration's family over the sampled served tokens, and for the control
seeds the control over the same prompts and tokens (the family's
``compare``, ``control=True``).  Prints one JSON line per seed with the
widest and mean gaps of both, and the verdict of the cell's own limits on
each: ``correct`` for the program, ``ctrl_correct`` for the control put in
its place (it should read false).  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import bench  # noqa: E402  (puts this directory and src/ on sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    import jax
    if jax.devices()[0].platform != "tpu":
        bench.log("no TPU found")
        return 2
    import counts
    import runner
    import workload
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    b = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    cell = bench.find_cell(b, args.workload)
    traffic = workload.load_json("traffic", args.workload)
    conf = workload.load_json("configs", cell["config"])
    peaks = counts.peaks(jax.devices()[0].device_kind)
    for seed in seeds:
        t0 = time.perf_counter()
        out = runner.run_cell(args.workload, traffic, conf, seed=seed,
                              seconds=args.seconds, trace=False,
                              t_start=t0, peaks=peaks)
        res = runner.check(out, traffic, conf, seed,
                           control=seed in control)
        row = {"seed": seed, "setup_s": out.setup_s,
               "tok_s": out.tokens / out.window_s,
               "compared": int(res["gap"].size),
               "widest_gap": float(res["gap"].max()),
               "mean_gap": float(res["gap"].mean()),
               "off_first": int((res["gap"] > 0).sum()),
               "correct": runner.verdict(out, res["gap"], traffic)[0],
               "failed": out.failed, "compiles": out.compiles,
               "seconds": time.perf_counter() - t0}
        if "ctrl_gap" in res:
            c = res["ctrl_gap"]
            row.update(ctrl_widest_gap=float(c.max()),
                       ctrl_mean_gap=float(c.mean()),
                       ctrl_off_first=int((c > 0).sum()),
                       ctrl_correct=runner.verdict(out, c, traffic)[0])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
