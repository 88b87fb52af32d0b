"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs here: each test lowers one kernel at TinyLlama-1.1B widths
(Hq=32, Hkv=4, head_dim=64, a paged pool of 32-token blocks), and the paged
decode also at the benchmark's OLMo-1B and Mistral-NeMo shapes, for a v5e
chip that is described, not attached, and checks that Mosaic emitted the kernel
(``tpu_custom_call``).  Interpret mode cannot see what the chip's compiler
refuses — unsupported shape casts, int32 operands on the MXU, tiles that do
not fit VMEM — so these compiles guard the kernels between chip runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a module that loaded it while being
collected would give parallel test workers different test lists.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import split_softmax as ss
from repro.core.lut import LUTConfig
from repro.kernels import ops

HQ, HKV, D = 32, 4, 64          # configs/tinyllama_1p1b.py
B, BLOCK_K, MAX_BLOCKS = 8, 32, 64
GAMMA = 4
CFG = LUTConfig(scale_z=8.0 / 127)
EXP_LUT, RECIP_LUT = ss.make_luts(CFG)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a compile
    for a described device is written to the cache but cannot be read back
    without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, kernel, sharding, *shapes):
    """Compile ``fn`` and check that Mosaic emitted the Pallas kernel under
    the ``name=`` its ``pallas_call`` gives: the profiler names the kernel's
    device op after it, and the benchmark's trace reduction finds it so."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    assert all(re.search(rf"%{kernel}(\.\d+)? = ", ln)
               and f"/{kernel}/pallas_call" in ln for ln in calls), calls
    return text


SCALAR = ((), jnp.float32)


@pytest.mark.parametrize("seq", [512, 200])
def test_prefill_compiles(one_chip, seq):
    def prefill(q, k, v, s_q, s_k, s_v):
        return ops.splitmax_attention(q, k, v, s_q, s_k, s_v, EXP_LUT,
                                      RECIP_LUT, cfg=CFG, causal=True,
                                      impl="pallas")
    _compile(prefill, "splitmax_attention_pallas", one_chip,
             ((1, HQ, seq, D), jnp.int8), ((1, HKV, seq, D), jnp.int8),
             ((1, HKV, seq, D), jnp.int8), SCALAR, SCALAR, SCALAR)


POOL = ((1 + B * MAX_BLOCKS, HKV, BLOCK_K, D), jnp.int8)
TABLE = ((B, MAX_BLOCKS), jnp.int32)
LENS = ((B,), jnp.int32)
DENSE_CACHE = ((B, HKV, MAX_BLOCKS * BLOCK_K, D), jnp.int8)


# the benchmark's configurations, as served: (B, Hq, Hkv, D, pool blocks,
# table entries per slot), 32-token pages
PAGED_SHAPES = {
    "tinyllama": (B, HQ, HKV, D, 1 + B * MAX_BLOCKS, MAX_BLOCKS),
    "olmo-1b": (32, 16, 16, 128, 2049, 64),
    "mistral-nemo-12b-4l": (24, 32, 8, 128, 10_777, 449),
}


@pytest.mark.parametrize(
    "shape,fused",
    [(name, fused) for name in PAGED_SHAPES for fused in (True, False)],
    ids=[str(fused) if name == "tinyllama" else f"{name}-{fused}"
         for name in PAGED_SHAPES for fused in (True, False)])
def test_paged_decode_compiles(one_chip, shape, fused):
    b, hq, hkv, d, num_blocks, max_blocks = PAGED_SHAPES[shape]
    op = ops.splitmax_decode_fused_paged if fused else ops.splitmax_decode_paged
    q_dtype = jnp.bfloat16 if fused else jnp.int8
    pool = ((num_blocks, hkv, BLOCK_K, d), jnp.int8)

    def decode(q, kp, vp, table, s_q, s_k, s_v, lens):
        return op(q, kp, vp, table, s_q, s_k, s_v, lens, EXP_LUT, RECIP_LUT,
                  cfg=CFG, impl="pallas")
    kernel = ("splitmax_decode_fused_paged_pallas" if fused
              else "splitmax_decode_paged_pallas")
    _compile(decode, kernel, one_chip, ((b, hq, d), q_dtype), pool, pool,
             ((b, max_blocks), jnp.int32), ((b,), jnp.float32), SCALAR,
             SCALAR, ((b,), jnp.int32))


@pytest.mark.parametrize("fused", [True, False])
def test_dense_decode_compiles(one_chip, fused):
    op = ops.splitmax_decode_fused if fused else ops.splitmax_decode
    q_dtype = jnp.bfloat16 if fused else jnp.int8

    def decode(q, k, v, s_q, s_k, s_v, lens):
        return op(q, k, v, s_q, s_k, s_v, lens, EXP_LUT, RECIP_LUT, cfg=CFG,
                  impl="pallas")
    kernel = ("splitmax_decode_fused_pallas" if fused
              else "splitmax_decode_pallas")
    _compile(decode, kernel, one_chip, ((B, HQ, D), q_dtype), DENSE_CACHE,
             DENSE_CACHE, ((B,), jnp.float32), SCALAR, SCALAR, LENS)


@pytest.mark.parametrize("paged", [True, False])
def test_verify_compiles(one_chip, paged):
    q = ((B, HQ, GAMMA, D), jnp.bfloat16)
    s_q = ((B, GAMMA), jnp.float32)
    if paged:
        def verify(q, kp, vp, table, s_q, s_k, s_v, lens):
            return ops.splitmax_decode_fused_verify_paged(
                q, kp, vp, table, s_q, s_k, s_v, lens, EXP_LUT, RECIP_LUT,
                cfg=CFG, impl="pallas")
        _compile(verify, "splitmax_decode_fused_verify_paged_pallas",
                 one_chip, q, POOL, POOL, TABLE, s_q, SCALAR, SCALAR, LENS)
    else:
        def verify(q, k, v, s_q, s_k, s_v, lens):
            return ops.splitmax_decode_fused_verify(
                q, k, v, s_q, s_k, s_v, lens, EXP_LUT, RECIP_LUT, cfg=CFG,
                impl="pallas")
        _compile(verify, "splitmax_decode_fused_verify_pallas", one_chip, q,
                 DENSE_CACHE, DENSE_CACHE, s_q, SCALAR, SCALAR, LENS)


@pytest.mark.parametrize("requant", [False, True])
def test_int8_matmul_compiles(one_chip, requant):
    def gemm(x, w, mult):
        return ops.int8_matmul(x, w, mult if requant else None,
                               impl="pallas")
    _compile(gemm, "int8_matmul_pallas", one_chip, ((512, 2048), jnp.int8),
             ((2048, 2048), jnp.int8), SCALAR)


@pytest.mark.parametrize("shape", list(PAGED_SHAPES))
def test_paged_kv_append_compiles(one_chip, shape):
    b, _, hkv, d, num_blocks, _ = PAGED_SHAPES[shape]
    pool = ((num_blocks, hkv, BLOCK_K, d), jnp.int8)
    rows = ((b, hkv, d), jnp.int8)
    _compile(lambda *a: ops.paged_kv_append(*a, impl="pallas"),
             "paged_kv_append_pallas", one_chip, pool, pool,
             ((b,), jnp.int32), ((b,), jnp.int32), rows, rows)


# ops that only pass a pool through the decode step: the donated
# parameters, views of them, the layer loop that carries them, and the
# Pallas kernels, which take and give them in place
POOL_CARRIERS = {"parameter", "get-tuple-element", "bitcast", "tuple",
                 "while", "custom-call"}


def test_paged_decode_step_updates_pool_in_place(one_chip):
    """The jitted, cache-donating paged decode step at OLMo-1B's K/V widths
    (16 KV heads of 128, 32 slots x 2,048) never materialises a layer's
    pages: no op but those that carry the pools has a pool-sized result,
    the append and decode kernels are emitted under their names, and both
    pools stay aliased from the donated cache to the returned one."""
    from repro.configs import get_arch
    from repro.launch import steps as st
    from repro.models import transformer as T

    n_layers, slots, max_len = 4, 32, 2048
    cfg = get_arch("olmo_1b").config.replace(
        n_layers=n_layers, d_model=256, d_ff=512, vocab_size=512,
        attn_impl="pallas")

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(st.init_params_fn(cfg),
                                      jax.random.PRNGKey(0)))
    cache = described(jax.eval_shape(
        lambda: T.make_paged_cache(cfg, slots, max_len, block_k=BLOCK_K)))
    token = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    assert cache["kv"]["k_pages"].shape[2:] == (16, BLOCK_K, 128)
    step = jax.jit(st.make_decode_step(cfg), donate_argnums=(2,),
                   compiler_options=st.EXACT_ROUNDING)
    text = step.lower(params, token, cache).compile().as_text()

    nb = cache["kv"]["k_pages"].shape[1]
    pool_sized = re.compile(rf"s8\[[^\]]*\b({nb}|{n_layers * nb})\b")
    instr = re.compile(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z\-]*)\(")
    strays, kernels = [], set()
    for line in text.splitlines():
        m = instr.match(line)
        if not m or not pool_sized.search(m.group(2)):
            continue
        name, op = m.group(1), m.group(3)
        if op not in POOL_CARRIERS or (
                op == "custom-call" and "tpu_custom_call" not in line):
            strays.append(f"{op} {name}")
        if op == "custom-call":
            kernels.add(re.sub(r"\.\d+$", "", name))
    assert not strays, strays
    assert kernels == {"paged_kv_append_pallas"}, kernels
    for kernel in ("paged_kv_append_pallas",
                   "splitmax_decode_fused_paged_pallas"):
        assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text), \
            kernel

    params_of = {m.group(1): int(m.group(2)) for m in re.finditer(
        r"%cache__kv____(\w_pages)__\S* = s8\S* parameter\((\d+)\)", text)}
    assert sorted(params_of) == ["k_pages", "v_pages"], params_of
    header = text.splitlines()[0]
    aliased = {int(p) for p in re.findall(r"\{\d*\}: \((\d+), \{\}", header)}
    assert set(params_of.values()) <= aliased, (params_of, header[:400])
