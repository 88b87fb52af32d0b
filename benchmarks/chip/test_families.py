"""CPU tests of how the harness finds a configuration's model family.

A family is a file, ``families/<name>.py``; a configuration names it under
``family``.  These tests show that a new family needs that file alone, and
that a configuration the family cannot serve is refused, not run as
another.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np
import pytest

import test_chip_bench as tcb
from test_chip_bench import BENCH, TINY_CONF, TINY_TRAFFIC

import model_weights as mw  # noqa: E402  (test_chip_bench set sys.path)
import runner  # noqa: E402

STUB = '''"""The dense family's functions under another name, counting calls."""
import model_weights
import reference
from counts import (decode_attn_bytes, decode_flops, layer_params,
                    matmul_params, prefill_attn_seconds, prefill_flops)

Model = model_weights.Model
CALLS = []


def program_params(key, m, shapes):
    CALLS.append("program_params")
    return model_weights.program_params(key, m, shapes)


def compare(*args, **kw):
    CALLS.append("compare")
    return reference.compare(*args, **kw)
'''

# deepseek-moe-16b at the program's smoke size: one dense layer, then two
# of 8 routed experts, top-3, and 2 shared
MOE_SMOKE = {
    "name": "deepseek-moe-smoke", "program": "deepseek_moe_16b",
    "family": "moe", "norm_eps": 1e-6,
    "model": {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
              "head_dim": 16, "d_ff": 128, "vocab_size": 512,
              "norm": "rmsnorm", "act": "silu", "rope_theta": 1e4,
              "max_seq": 64, "tie_embeddings": False, "dtype": "bfloat16",
              "moe": {"n_experts": 8, "top_k": 3, "d_ff_expert": 32,
                      "n_shared": 2, "first_dense_layers": 1}},
}


def _with(conf, family=None, **model):
    conf = json.loads(json.dumps(conf))
    conf["model"].update(model)
    if family is not None:
        conf["family"] = family
    return conf


def test_a_family_is_found_from_its_file_alone(tmp_path, monkeypatch):
    (tmp_path / "stub.py").write_text(STUB)
    monkeypatch.setattr(runner, "FAMILIES", tmp_path)
    # the program's entry of a new family names it, as the stub's does here
    monkeypatch.setattr(runner, "program_family", lambda conf: "stub")
    monkeypatch.setitem(TINY_CONF, "family", "stub")
    seed = 2**31 + 9
    out, res = tcb.tiny_run(seed=seed)
    stub = out.run.family
    assert pathlib.Path(stub.__file__) == tmp_path / "stub.py"
    assert stub.CALLS.count("program_params") == 1
    assert stub.CALLS.count("compare") == len(
        runner.sample(out, TINY_TRAFFIC, seed)) > 0
    ok, checks = runner.verdict(out, res["gap"], TINY_TRAFFIC)
    assert ok, checks
    cell = {"name": "olmo-1b.decode"}
    layer = runner.metrics(out, cell, BENCH, trace=True)
    assert "dev.mfu" in layer
    monkeypatch.undo()
    # the dense family gives the same gaps over the same served tokens, and
    # its counts the same per-layer readings
    assert TINY_CONF["family"] == "dense"
    dense = runner.family(TINY_CONF)
    assert dense is not stub
    np.testing.assert_array_equal(
        runner.check(out, TINY_TRAFFIC, TINY_CONF, seed)["gap"], res["gap"])
    out = dataclasses.replace(out, run=dataclasses.replace(out.run,
                                                           family=dense))
    assert runner.metrics(out, cell, BENCH, trace=True) == layer


REFUSALS = {
    # a name no file can hold, as no module can be named so: the message
    # names the files there are
    "unknown_family": (
        lambda: runner.family(_with(MOE_SMOKE, family="no-such-family")),
        r"no model family 'no-such-family'.*\[.*'dense'.*\]"),
    "family_not_the_programs": (
        lambda: runner.family(_with(MOE_SMOKE, family="dense")),
        r"states family 'dense', the program's 'deepseek_moe_16b' is "
        r"'moe'"),
    "dense_with_moe_section": (
        lambda: mw.Model.from_config(
            _with(TINY_CONF, moe=MOE_SMOKE["model"]["moe"])),
        r"a dense model has no sections: \['moe'\]"),
    "unknown_key_in_section": (
        lambda: runner.program_config(
            _with(MOE_SMOKE, moe={"n_experts": 8, "experts_per_tok": 3})),
        r"unknown keys in section 'moe': \['experts_per_tok'\]"),
    "section_of_a_plain_key": (
        lambda: runner.program_config(_with(TINY_CONF, norm={"eps": 1})),
        r"model key 'norm' takes no section"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_a_configuration_the_family_cannot_serve_is_refused(case):
    fn, match = REFUSALS[case]
    with pytest.raises(ValueError, match=match):
        fn()


def test_a_moe_family_needs_its_file_alone(tmp_path, monkeypatch):
    """The next family's file, beside the dense one, is all the harness
    needs to find it; a configuration that states another family for the
    same program is still refused."""
    (tmp_path / "dense.py").write_text(
        (runner.FAMILIES / "dense.py").read_text())
    (tmp_path / "moe.py").write_text('"""A stub of the moe family."""\n')
    monkeypatch.setattr(runner, "FAMILIES", tmp_path)
    stub = runner.family(MOE_SMOKE)
    assert pathlib.Path(stub.__file__) == tmp_path / "moe.py"
    assert stub.__doc__ == "A stub of the moe family."
    with pytest.raises(ValueError, match=r"states family 'dense', the "
                       r"program's 'deepseek_moe_16b' is 'moe'"):
        runner.family(_with(MOE_SMOKE, family="dense"))


def test_program_config_builds_the_moe_section(tmp_path):
    from repro.configs import get_arch
    from repro.models.config import MoEConfig
    path = tmp_path / "deepseek-moe-smoke.json"
    path.write_text(json.dumps(MOE_SMOKE, indent=1))
    conf = json.loads(path.read_text())
    pcfg = runner.program_config(conf)
    assert pcfg.family == "moe" and pcfg.d_ff == 128
    assert isinstance(pcfg.moe, MoEConfig)
    # the keys the file leaves out keep the program's own values
    assert pcfg.moe == get_arch("deepseek_moe_16b").smoke.moe
    assert pcfg.moe.capacity_factor == 1.25
    # a section of one key, over the program's own section
    part = runner.program_config(_with(MOE_SMOKE, moe={"top_k": 2}))
    assert part.moe == dataclasses.replace(
        get_arch("deepseek_moe_16b").config.moe, top_k=2)
    # where the program has none, the section is built whole
    dense = runner.program_config(_with(TINY_CONF,
                                        moe=MOE_SMOKE["model"]["moe"]))
    assert dense.moe == pcfg.moe
