"""Decode attention kernel's share of its roofline: the int8 K/V bytes of
the live lengths the proxy tracked, with the f32 query and output, at the
HBM peak, over the Pallas kernel's device time in the decode program.
Layer: kernels (splitmax_decode)."""


def read(run):
    t = run.trace
    if t is None or not t.kernel_s.get("decode"):
        return None
    nbytes = sum(run.family.decode_attn_bytes(run.m, c["live_tokens"],
                                              c["slots"])
                 for c in run.decode_calls)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t.kernel_s["decode"]
