"""Share of the window the host spends admitting: from each ``admit``
call to the next engine call (the per-slot prefill's dispatch, the first
token's selection and its read-back), summed.  Layer: scheduler."""


def read(run):
    return 100.0 * run.admit_span_s / run.window_s
