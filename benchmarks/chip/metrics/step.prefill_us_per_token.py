"""Device time of the prefill programs per prompt token admitted in the
window, from the trace.  Layer: jitted steps."""


def read(run):
    t = run.trace
    tokens = sum(n for _, n in run.admissions)
    if t is None or not tokens or not t.module_s.get("prefill"):
        return None
    return 1e6 * t.module_s["prefill"] / tokens
