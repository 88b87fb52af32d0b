"""Model FLOPs of the prompts admitted in the window over the prefill
programs' device time at the chip's bf16 peak: the whole prefill step's
share of the peak, which bounds what its attention kernel can give.
Layer: device (whole step)."""


def read(run):
    t = run.trace
    if t is None or not run.admissions or not t.module_s.get("prefill"):
        return None
    flops = sum(run.family.prefill_flops(run.m, n)
                for _, n in run.admissions)
    return 100.0 * flops / (t.module_s["prefill"] * run.peaks["bf16_flops"])
