"""95th percentile of the gaps between consecutive tokens of one request,
as ``itl_p95_ms`` takes them, over the part of the window a traced run
profiles: where host stalls make that tail too unsteady to bound end to
end.  Layer: scheduler."""
import numpy as np


def read(run):
    if not run.itl_ms:
        return None
    return float(np.percentile(run.itl_ms, 95))
