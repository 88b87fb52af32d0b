"""Benchmark harness: one section per paper table/figure.

Default mode prints ``name,value,derived`` CSV rows (value is us_per_call
for timing benches, the metric itself for model-based benches).

``--json`` emits the tracked perf artifacts on the 8-CPU-device grid
(set up before jax imports):

  * ``benchmarks/BENCH_serve.json``     — paged vs dense under churn,
    the SSM / encdec family cells through the same scheduler, plus
    speculative vs plain paged on the latency cell (tok/s, p50/p99
    decode-step latency, prefill counts, bytes moved, accept rate)
  * ``benchmarks/BENCH_attention.json`` — kernel microbenchmarks
  * ``benchmarks/BENCH_roofline.json``  — compile-only HLO roofline of the
    decode / draft-loop / fused-verify launches (why speculation pays)

``make perf-check`` diffs a fresh run against the committed baselines.

  * energy_model      — Fig 8 / Fig 9 / Table I (TOPS/W, TOPS/mm2)
  * softmax_latency   — §V-B 33% split-softmax latency reduction
  * softmax_accuracy  — Fig 11 (float vs int8-LUT accuracy delta)
  * attention_bench   — kernel microbenchmarks (host wall-clock)
  * serve_bench       — continuous-batching scheduler (json mode only)
"""
import argparse
import json
import os
import pathlib


def _force_cpu_grid() -> None:
    """8 host-platform devices, before any jax import."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def run_json(out_dir: pathlib.Path) -> None:
    from benchmarks import attention_bench, roofline_bench, serve_bench

    serve_json = serve_bench.run_grid()
    (out_dir / "BENCH_serve.json").write_text(
        json.dumps(serve_json, indent=2) + "\n")
    spec = serve_json["speculative"]
    print(f"wrote {out_dir / 'BENCH_serve.json'}: "
          f"churn dense {serve_json['dense']['tok_s']:.1f} tok/s, "
          f"paged {serve_json['paged']['tok_s']:.1f} tok/s "
          f"({serve_json['paged_over_dense_tok_s']:.2f}x); "
          f"latency paged {serve_json['spec_paged']['tok_s']:.1f} tok/s, "
          f"speculative {spec['tok_s']:.1f} tok/s "
          f"({serve_json['spec_over_paged_tok_s']:.2f}x paged, "
          f"accept {spec['accept_rate']:.2f}, "
          f"{spec['tokens_per_verify']:.1f} tok/verify, "
          f"parity={serve_json['bitwise_parity']}); "
          f"families ssm {serve_json['ssm_churn']['tok_s']:.1f} tok/s "
          f"(preempt parity={serve_json['ssm_preempt_parity']}), "
          f"encdec {serve_json['encdec_churn']['tok_s']:.1f} tok/s "
          f"(pressure parity={serve_json['encdec_pressure_parity']})")

    roof_json = roofline_bench.run()
    (out_dir / "BENCH_roofline.json").write_text(
        json.dumps(roof_json, indent=2) + "\n")
    print(f"wrote {out_dir / 'BENCH_roofline.json'}: "
          f"verify/gamma-decodes bytes "
          f"{roof_json['verify_bytes_over_gamma_decodes']:.2f}x, "
          f"flops {roof_json['verify_flops_over_gamma_decodes']:.2f}x, "
          f"decode bottleneck "
          f"{roof_json['decode']['bottleneck']}")

    rows = attention_bench.run()
    attn_json = {"rows": {name: {"us_per_call": val, "derived": derived}
                          for name, val, derived in rows}}
    # fused-vs-composed decode ratios, one per grid point (gated by
    # perf_check.py: fused must keep beating the staged pipeline)
    ratios = {}
    for name, info in attn_json["rows"].items():
        if name.startswith("decode.fused_"):
            shape = name[len("decode.fused_"):]
            composed = attn_json["rows"]["decode.composed_" + shape]
            ratios[shape] = composed["us_per_call"] / info["us_per_call"]
    attn_json["fused_over_composed"] = ratios
    (out_dir / "BENCH_attention.json").write_text(
        json.dumps(attn_json, indent=2) + "\n")
    ratio_str = ", ".join(f"{k} {v:.2f}x" for k, v in ratios.items())
    print(f"wrote {out_dir / 'BENCH_attention.json'} ({len(rows)} rows; "
          f"fused/composed: {ratio_str})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: energy,latency,accuracy,attention")
    ap.add_argument("--accuracy-steps", type=int, default=120)
    ap.add_argument("--json", action="store_true",
                    help="emit benchmarks/BENCH_*.json on the 8-CPU grid")
    ap.add_argument("--out-dir", default=str(pathlib.Path(__file__).parent),
                    help="where --json writes the BENCH_*.json files")
    args = ap.parse_args()
    if args.json:
        _force_cpu_grid()
    from repro.launch import compile_cache
    compile_cache.enable()

    if args.json:
        run_json(pathlib.Path(args.out_dir))
        return

    which = set(args.only.split(",")) if args.only else {
        "energy", "latency", "accuracy", "attention"}

    rows = []
    if "energy" in which:
        from benchmarks import energy_model
        rows += energy_model.run()
    if "latency" in which:
        from benchmarks import softmax_latency
        rows += softmax_latency.run()
    if "accuracy" in which:
        from benchmarks import softmax_accuracy
        rows += softmax_accuracy.run(steps=args.accuracy_steps)
    if "attention" in which:
        from benchmarks import attention_bench
        rows += attention_bench.run()

    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.5f},{derived}")
    if "energy" in which:
        from benchmarks import energy_model
        energy_model.print_table1()


if __name__ == "__main__":
    main()
