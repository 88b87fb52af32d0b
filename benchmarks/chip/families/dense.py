"""The dense decoder family: SwiGLU MLP, MHA or grouped K/V heads, RMSNorm
or non-parametric LayerNorm, tied or untied head.

A family module gives the harness, by these names, everything that depends
on the model's architecture:

  * ``Model``: the sizes, ``Model.from_config(conf)`` from the
    configuration file;
  * ``program_params(key, m, shapes)``: the weights from the seed in the
    program's parameter layout, one jitted call;
  * ``compare(seed, m, prompt, served, *, control)``: the plain reference
    and its control over served tokens;
  * the counts behind the per-layer metrics: ``matmul_params``,
    ``layer_params``, ``prefill_flops``, ``decode_flops``,
    ``decode_attn_bytes`` and ``prefill_attn_seconds``.

This one binds the dense weights (``model_weights.py``), reference
(``reference.py``) and counts (``counts.py``).
"""
from counts import (decode_attn_bytes, decode_flops,  # noqa: F401
                    layer_params, matmul_params, prefill_attn_seconds,
                    prefill_flops)
from model_weights import Model, program_params  # noqa: F401
from reference import compare  # noqa: F401
