"""Decode attention kernel's share of its roofline: the int8 K/V bytes of
the live lengths the proxy tracked, with the f32 query and output, at the
HBM peak, over the device time of the fused paged decode kernel, by its
name, in the decode program.  Layer: kernels (splitmax_decode)."""

KERNEL = "decode/splitmax_decode_fused_paged_pallas"


def read(run):
    t = run.trace
    if t is None or not t.kernel_s.get(KERNEL):
        return None
    nbytes = sum(run.family.decode_attn_bytes(run.m, c["live_tokens"],
                                              c["slots"])
                 for c in run.decode_calls)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t.kernel_s[KERNEL]
