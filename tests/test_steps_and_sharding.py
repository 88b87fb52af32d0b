"""Train/serve steps, loss correctness, sharding rules, tiny-mesh dry-run."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch import steps as st
from repro.optim import adamw


def test_cross_entropy_matches_naive(rng):
    b, s, v, pad = 2, 8, 50, 14
    logits = jnp.asarray(rng.normal(0, 2, (b, s, v + pad)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, (b, s)), jnp.int32)
    got = st.cross_entropy(logits, labels, v)
    # naive: slice off padding, softmax, pick gold
    lg = np.asarray(logits)[..., :v]
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    gold = np.take_along_axis(p, np.asarray(labels)[..., None], -1)[..., 0]
    want = -np.log(gold).mean()
    assert abs(float(got) - want) < 1e-4


def test_padding_lanes_never_win(rng):
    b, s, v = 1, 4, 10
    logits = jnp.full((b, s, 16), 5.0)
    labels = jnp.zeros((b, s), jnp.int32)
    loss = st.cross_entropy(logits, labels, v)
    # all-equal logical logits -> loss == log(v), padding excluded
    assert abs(float(loss) - np.log(v)) < 1e-4


def test_training_reduces_loss():
    arch = get_arch("tinyllama_1p1b")
    cfg = arch.smoke.replace(dtype="float32")
    opt_cfg = adamw.OptimizerConfig(peak_lr=2e-3, warmup_steps=5,
                                    total_steps=60)
    from repro.data.pipeline import DataConfig, batch_for_step
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8,
                    seed=3)
    params = st.init_params_fn(cfg)(jax.random.PRNGKey(0))
    opt_state = adamw.init_state(params)
    step = jax.jit(st.make_train_step(cfg, opt_cfg))
    losses = []
    for i in range(40):
        params, opt_state, m = step(params, opt_state, batch_for_step(dc, i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses[::8]


def test_compressed_training_reduces_loss():
    """The int8 error-feedback step trains: same smoke model as above,
    gradient passed through the wire-format numerics each step."""
    from repro.dist import compression as comp
    arch = get_arch("tinyllama_1p1b")
    cfg = arch.smoke.replace(dtype="float32")
    opt_cfg = adamw.OptimizerConfig(peak_lr=2e-3, warmup_steps=5,
                                    total_steps=60)
    from repro.data.pipeline import DataConfig, batch_for_step
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8,
                    seed=3)
    params = st.init_params_fn(cfg)(jax.random.PRNGKey(0))
    opt_state = adamw.init_state(params)
    err = comp.init_error(params)
    step = jax.jit(st.make_compressed_train_step(cfg, opt_cfg))
    losses = []
    for i in range(30):
        params, opt_state, err, m = step(params, opt_state, err,
                                         batch_for_step(dc, i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses[::6]


def test_grad_accum_matches_big_batch():
    arch = get_arch("olmo_1b")
    cfg = arch.smoke.replace(dtype="float32")
    from repro.data.pipeline import DataConfig, batch_for_step
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    batch = batch_for_step(dc, 0)
    params = st.init_params_fn(cfg)(jax.random.PRNGKey(1))
    opt_cfg = adamw.OptimizerConfig(peak_lr=1e-3, warmup_steps=1,
                                    total_steps=10, accum_steps=2)
    # accumulated: split batch into 2 microbatches
    micro = jax.tree.map(lambda x: x.reshape((2, 4) + x.shape[1:]), batch)
    p1, _, m1 = st.make_grad_accum_train_step(cfg, opt_cfg)(
        params, adamw.init_state(params), micro)
    p2, _, m2 = st.make_train_step(cfg, opt_cfg)(
        params, adamw.init_state(params), batch)
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    # f32 matmul-order noise only; the bound covers the slightly different
    # XLA CPU codegen of single- vs multi-device builds (conftest forces 8)
    assert d < 1e-4, d


# ------------------------------- sharding -----------------------------------

def test_param_sharding_rules():
    os.environ.setdefault("XLA_FLAGS", "")
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as sh

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    cfg = get_arch("deepseek_67b").config
    spec = sh._trailing_spec("segments/0/attn/wq/w",
                             jax.ShapeDtypeStruct((95, 8192, 8192),
                                                  jnp.float32),
                             cfg, FakeMesh())
    assert spec == (None, "data", "model")
    spec = sh._trailing_spec("embed/table",
                             jax.ShapeDtypeStruct((102400, 8192),
                                                  jnp.float32),
                             cfg, FakeMesh())
    assert spec == ("model", "data")
    # divisibility guard: a dim the mesh does not divide replicates
    spec = sh._trailing_spec("segments/0/attn/wq/w",
                             jax.ShapeDtypeStruct((95, 100, 8192),
                                                  jnp.float32),
                             cfg, FakeMesh())
    assert spec == (None, None, "model")


def test_moe_expert_sharding_rules():
    from repro.dist import sharding as sh

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    # deepseek-moe: 64 experts % 16 == 0 -> EP over model
    cfg = get_arch("deepseek_moe_16b").config
    spec = sh._trailing_spec("segments/1/moe/w_in",
                             jax.ShapeDtypeStruct((27, 64, 2048, 1408),
                                                  jnp.float32),
                             cfg, FakeMesh())
    assert spec == (None, "model", "data", None)
    # mixtral: 8 experts % 16 != 0 -> replicate experts, TP inside
    cfg = get_arch("mixtral_8x22b").config
    spec = sh._trailing_spec("segments/0/moe/w_in",
                             jax.ShapeDtypeStruct((56, 8, 6144, 16384),
                                                  jnp.float32),
                             cfg, FakeMesh())
    assert spec == (None, None, "data", "model")


def _tiny_mesh():
    from jax.sharding import Mesh
    if jax.local_device_count() < 8:
        pytest.skip("needs 8 host-platform devices (conftest default)")
    return Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))


def test_shard_is_identity_without_binding():
    from repro.dist.sharding import current_axis_rules, shard
    assert current_axis_rules() is None
    x = jnp.ones((4, 8))
    assert shard(x, "batch", "embed") is x


def test_shard_applies_logical_rules_in_jit():
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.sharding import axis_rules, shard
    from repro.launch.mesh import logical_rules
    mesh = _tiny_mesh()
    with axis_rules(mesh, logical_rules(mesh)):
        y = jax.jit(lambda x: shard(x, "batch", "heads", None, None))(
            jnp.ones((4, 8, 16, 4)))
    assert y.sharding.is_equivalent_to(
        NamedSharding(mesh, P("data", "model")), y.ndim)


def test_shard_guards_divisibility_and_axis_reuse():
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.sharding import axis_rules, shard
    mesh = _tiny_mesh()
    rules = {"batch": ("data",), "heads": "model", "mlp": "model"}
    with axis_rules(mesh, rules):
        # "mlp" would reuse the model axis -> replicated
        y = jax.jit(lambda x: shard(x, "batch", "heads", "mlp"))(
            jnp.ones((4, 8, 16)))
        # 3 % data(2) != 0 -> batch dim replicated
        z = jax.jit(lambda x: shard(x, "batch", None))(jnp.ones((3, 8)))
    assert y.sharding.is_equivalent_to(
        NamedSharding(mesh, P("data", "model", None)), y.ndim)
    assert z.sharding.is_equivalent_to(NamedSharding(mesh, P()), z.ndim)


def test_axis_rules_binding_restores_previous():
    from repro.dist.sharding import axis_rules, current_axis_rules
    mesh = _tiny_mesh()
    with axis_rules(mesh, {"batch": "data"}):
        with axis_rules(mesh, {"batch": None}):
            assert current_axis_rules()[1] == {"batch": None}
        assert current_axis_rules()[1] == {"batch": "data"}
    assert current_axis_rules() is None


DRYRUN_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from jax.sharding import Mesh
from repro.launch.dryrun import dryrun_cell
from repro.configs import get_arch
arch = get_arch("olmo_1b")
# dryrun's import appends its own 512-device flag; use the first 8
mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ("data", "model"))
cfg = arch.smoke.replace(scan_layers=False)
r = dryrun_cell("olmo_1b", "train_4k", multi_pod=False, mesh=mesh,
                config_override=cfg, verbose=False)
assert r["roofline"]["hlo_flops_per_chip"] > 0
print("TINY-MESH-OK")
"""


@pytest.mark.slow
def test_tiny_mesh_dryrun_subprocess():
    """8 fake devices in a subprocess (keeps this process at 1 device):
    the full lower+compile+analyze path on a (2,4) mesh."""
    # the child stays on the CPU: with a TPU library installed it must not
    # try to load it while another test worker holds it
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", DRYRUN_SNIPPET], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "TINY-MESH-OK" in out.stdout, out.stderr[-2000:]
