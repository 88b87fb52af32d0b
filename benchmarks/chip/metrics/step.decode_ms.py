"""Device time of the decode program per execution, from the trace.
Layer: jitted steps."""


def read(run):
    t = run.trace
    if t is None or not t.module_n.get("decode"):
        return None
    return 1e3 * t.module_s["decode"] / t.module_n["decode"]
