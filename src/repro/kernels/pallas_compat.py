"""Backend capability gate for the Pallas TPU kernels.

:func:`pallas_supported` is the single check the dispatch layer
(`kernels/ops.py`) and the autotuner (`kernels/autotune.py`) consult before
reaching for a *compiled* Pallas kernel — everywhere else the kernels run in
the interpreter (tests) or are replaced by the XLA twin of the same math.
"""
from __future__ import annotations

import jax


def pallas_supported() -> bool:
    """True when compiled Pallas kernels can actually run here.

    Mosaic lowering of these kernels targets TPU; on CPU/GPU backends the
    kernels are exercised through ``interpret=True`` (tests) or replaced by
    the blocked XLA twins.  ``impl="auto"`` resolves to Pallas exactly when
    this is true, and autotune sweeps use it to decide whether timing the
    compiled kernel is meaningful.
    """
    return jax.default_backend() == "tpu"
