"""Model FLOPs of every prompt and output token of the window (counted from
shapes and live lengths) over the window's seconds at the chip's bf16 peak.
Layer: device (whole step)."""


def read(run):
    f = run.family
    flops = sum(f.prefill_flops(run.m, n) for _, n in run.admissions)
    flops += sum(f.decode_flops(run.m, c["live_tokens"], c["slots"])
                 for c in run.decode_calls)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])
