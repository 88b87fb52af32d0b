"""Live blocks of the paged K/V pool over its size, at each decode call,
averaged over the window.  Layer: cache engine."""


def read(run):
    calls = [c for c in run.decode_calls if c["pool_blocks"]]
    if not calls:
        return None
    return 100.0 * sum(c["live_blocks"] / c["pool_blocks"]
                       for c in calls) / len(calls)
