"""Prefill attention kernel's share of its roofline: causal QK^T at the
int8 peak plus PV at the bf16 peak for every prompt admitted in the
window, over the device time of the split-softmax attention kernel, by its
name, in the prefill programs.  Layer: kernels (splitmax_attn)."""

KERNEL = "prefill/splitmax_attention_pallas"


def read(run):
    t = run.trace
    if t is None or not run.admissions or not t.kernel_s.get(KERNEL):
        return None
    least = sum(run.family.prefill_attn_seconds(run.m, n, run.peaks)
                for _, n in run.admissions)
    return 100.0 * least / t.kernel_s[KERNEL]
