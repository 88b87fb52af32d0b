"""Prefill attention kernel's share of its roofline: causal QK^T at the
int8 peak plus PV at the bf16 peak for every prompt admitted in the
window, over the Pallas kernel's device time in the prefill programs.
Layer: kernels (splitmax_attn)."""


def read(run):
    t = run.trace
    if t is None or not run.admissions or not t.kernel_s.get("prefill"):
        return None
    least = sum(run.family.prefill_attn_seconds(run.m, n, run.peaks)
                for _, n in run.admissions)
    return 100.0 * least / t.kernel_s["prefill"]
