"""Smoke test on one TPU: serve TinyLlama-1.1B at full width, check parity.

    python chip_smoke.py [--seed N]

One process, one chip, no network; weights are random from ``--seed`` and
prompts are synthetic.  Phases, each of which must pass:

  1. device   — JAX must see a TPU; anything else exits non-zero.
  2. kernels  — prefill split-softmax attention (Sq=200, not a tile
                multiple), fused paged decode and fused paged verify
                (gamma 4) at TinyLlama widths (Hq=32, Hkv=4, D=64), the
                compiled Pallas kernels against the XLA twin and the
                ``impl="ref"`` oracle on the chip.
  3. serve    — ``repro.launch.serve.serve`` on the full-width config
                (22 layers, d_model 2048, bf16, vocab 32000): 8 requests,
                4 slots, prompt 200, 32 generated tokens, paged int8 KV
                pool with 32-token blocks, fused decode, greedy.  The
                compiled prefill, decode and verify steps must contain the
                Pallas kernels (``tpu_custom_call``), and one verify of 4
                tokens must give the logits of 4 decodes bit for bit.
  4. xla      — the same schedule with ``attn_impl="xla"``; tokens compared.
  5. spec     — one speculative pass with a 4-layer prefix drafter, gamma 4;
                tokens must equal the plain paged tokens.

Earlier lines report compile seconds, a smoke tok/s (not a benchmark: one
short run, compile excluded, host clock), peak device memory and every
parity result.  The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

HQ, HKV, D = 32, 4, 64
PREFILL_LEN = 200
B, BLOCK_K, MAX_BLOCKS, GAMMA = 8, 32, 64, 4
REQUESTS, SLOTS, PROMPT, GEN = 8, 4, 200, 32
KERNEL_RTOL = 2e-5          # tests/test_kernels.py's kernel-vs-oracle bound


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def compare(name: str, got, want, *, exact: bool = False) -> bool:
    """Bitwise equal, or (unless ``exact``) within the CPU suite's kernel
    tolerance."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        log(f"  {name}: FAIL shape {got.shape} vs {want.shape} or non-finite")
        return False
    diff = np.abs(got - want)
    scale = float(np.max(np.abs(want))) + 1e-30
    rel = float(diff.max()) / scale
    bitwise = bool(np.array_equal(got, want))
    ok = bitwise or (not exact and rel <= KERNEL_RTOL)
    log(f"  {name}: bitwise={bitwise} max_abs_diff={float(diff.max()):.3e} "
        f"max_rel_diff={rel:.3e} mismatched={int(np.sum(got != want))}/"
        f"{got.size} -> {'ok' if ok else 'FAIL'}")
    return ok


# ---------------------------------------------------------------------------
# phase 2: kernels at full width
# ---------------------------------------------------------------------------

def _quantized(rng, shape, qlib):
    x = jnp.asarray(rng.normal(0.0, 1.0, shape), jnp.float32)
    s = qlib.absmax_scale(x)
    return qlib.quantize(x, s), s


def kernel_phase(seed: int) -> bool:
    from repro.core import quantization as qlib
    from repro.core import split_softmax as ss
    from repro.core.lut import LUTConfig
    from repro.kernels import ops

    cfg = LUTConfig(scale_z=8.0 / 127)          # the serving default
    exp_lut, recip_lut = ss.make_luts(cfg)
    rng = np.random.default_rng(seed)
    ok = True

    q, s_q = _quantized(rng, (1, HQ, PREFILL_LEN, D), qlib)
    k, s_k = _quantized(rng, (1, HKV, PREFILL_LEN, D), qlib)
    v, s_v = _quantized(rng, (1, HKV, PREFILL_LEN, D), qlib)
    outs = {impl: jax.block_until_ready(ops.splitmax_attention(
        q, k, v, s_q, s_k, s_v, exp_lut, recip_lut, cfg=cfg, causal=True,
        impl=impl)) for impl in ("pallas", "xla", "ref")}
    ok &= compare("prefill Sq=200 pallas vs ref", outs["pallas"], outs["ref"])
    ok &= compare("prefill Sq=200 xla vs ref", outs["xla"], outs["ref"])

    n_blocks = 1 + B * MAX_BLOCKS
    kp, s_k = _quantized(rng, (n_blocks, HKV, BLOCK_K, D), qlib)
    vp, s_v = _quantized(rng, (n_blocks, HKV, BLOCK_K, D), qlib)
    table = jnp.asarray(1 + rng.permutation(B * MAX_BLOCKS).reshape(
        B, MAX_BLOCKS), jnp.int32)
    lens = jnp.asarray(rng.integers(GAMMA, MAX_BLOCKS * BLOCK_K + 1, (B,)),
                       jnp.int32)
    qd = jnp.asarray(rng.normal(0.0, 1.0, (B, HQ, D)), jnp.bfloat16)
    sd = qlib.absmax_scale(qd, axis=(1, 2))
    outs = {impl: jax.block_until_ready(ops.splitmax_decode_fused_paged(
        qd, kp, vp, table, sd, s_k, s_v, lens, exp_lut, recip_lut, cfg=cfg,
        impl=impl)) for impl in ("pallas", "xla", "ref")}
    ok &= compare("fused paged decode pallas vs ref", outs["pallas"],
                  outs["ref"])
    ok &= compare("fused paged decode xla vs ref", outs["xla"], outs["ref"])

    qv = jnp.asarray(rng.normal(0.0, 1.0, (B, HQ, GAMMA, D)), jnp.bfloat16)
    sv = qlib.absmax_scale(qv, axis=(1, 3))[:, 0, :, 0]
    outs = {impl: jax.block_until_ready(
        ops.splitmax_decode_fused_verify_paged(
            qv, kp, vp, table, sv, s_k, s_v, lens, exp_lut, recip_lut,
            cfg=cfg, impl=impl)) for impl in ("pallas", "xla", "ref")}
    ok &= compare("fused paged verify gamma=4 pallas vs ref", outs["pallas"],
                  outs["ref"])
    ok &= compare("fused paged verify gamma=4 xla vs ref", outs["xla"],
                  outs["ref"])
    return ok


# ---------------------------------------------------------------------------
# phases 3-5: serving
# ---------------------------------------------------------------------------

def _has_kernel(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def step_checks(params, cfg, prompts) -> bool:
    """The serving steps at the served shapes (compiles hit the persistent
    cache): each must hold the Pallas kernels, and row t of a verify must
    give the logits of the t-th of as many decodes bit for bit, which is
    the speculative contract one level below the tokens."""
    from repro.launch import serve, steps as st

    engine = serve.make_engine(params, cfg, prompts, slots=SLOTS,
                               max_len=PROMPT + GEN + 8, block_k=BLOCK_K)
    verify = jax.jit(st.make_verify_step(cfg),
                     compiler_options=st.EXACT_ROUNDING)
    cache = engine.make_cache()
    rows = 1 + np.arange(SLOTS * engine.bps, dtype=np.int32).reshape(
        SLOTS, engine.bps)
    args = (jnp.asarray(prompts[0])[None], cache, jnp.zeros((1,), jnp.int32),
            jnp.asarray(rows[:1]))
    found = {
        "prefill": _has_kernel(engine.slot_prefill, params, *args),
        "decode": _has_kernel(engine.decode_step, params,
                              jnp.zeros((SLOTS,), jnp.int32), cache),
        "verify": _has_kernel(verify, params,
                              jnp.zeros((SLOTS, GAMMA), jnp.int32), cache),
    }
    log(f"  tpu_custom_call in compiled steps: {found}")

    last = []
    for slot in range(SLOTS):
        prefill = engine.calib_prefill if slot == 0 else engine.slot_prefill
        logits, cache = prefill(params, jnp.asarray(prompts[slot])[None],
                                cache, jnp.asarray([slot], jnp.int32),
                                jnp.asarray(rows[slot:slot + 1]))
        last.append(logits[0])
    first = jnp.argmax(jnp.stack(last), -1).astype(jnp.int32)
    drafts = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (SLOTS, GAMMA - 1)), jnp.int32)
    tokens = jnp.concatenate([first[:, None], drafts], 1)
    ver_logits, _ = verify(params, tokens, jax.tree.map(jnp.copy, cache))
    dec_logits = []
    for t in range(GAMMA):
        logits, cache = engine.decode_step(params, tokens[:, t], cache)
        dec_logits.append(logits)
    same = compare(f"{GAMMA} decodes vs one verify, logits", ver_logits,
                   jnp.stack(dec_logits, 1), exact=True)
    return all(found.values()) and same


def token_agreement(name: str, got: dict, want: dict) -> bool:
    same = sum(int(a == b) for r in want
               for a, b in zip(got.get(r, []), want[r]))
    total = sum(len(t) for t in want.values())
    equal = got == want
    first = {r: next((i for i, (a, b) in enumerate(zip(got.get(r, []), t))
                      if a != b), None) for r, t in want.items()}
    diverged = {r: i for r, i in first.items() if i is not None}
    log(f"  {name}: tokens_equal={equal} agreement={same}/{total} "
        f"first_divergence={diverged or 'none'}")
    return equal


def full_width_config():
    """The published TinyLlama-1.1B config, not the smoke one."""
    from repro.configs import get_arch
    cfg = get_arch("tinyllama_1p1b").config
    assert (cfg.n_layers, cfg.d_model, cfg.dtype, cfg.vocab_size) == (
        22, 2048, "bfloat16", 32000), cfg
    return cfg


def serve_phase(cfg, seed: int) -> bool:
    from repro.launch import serve, steps as st

    t0 = time.perf_counter()
    params = st.init_params_fn(cfg)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"  params: {n_params / 1e9:.3f}B, init "
        f"{time.perf_counter() - t0:.1f}s")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, PROMPT, dtype=np.int32)
               for _ in range(REQUESTS)]
    kw = dict(slots=SLOTS, gen=GEN, block_k=BLOCK_K, cache_kind="paged")

    def run(name, run_cfg, **extra):
        t = time.perf_counter()
        stats = serve.serve(params, run_cfg, prompts, warmup=True, **kw,
                            **extra)
        total = time.perf_counter() - t
        log(f"  {name}: served {stats['served']} requests, "
            f"{stats['total_tokens']} tokens; compile+warmup "
            f"{total - stats['wall_s']:.1f}s; smoke tok/s "
            f"{stats['tok_s']:.1f} (one short run, not a benchmark)")
        return stats

    ok = True
    plain = run("paged fused pallas", cfg)
    ok &= plain["served"] == REQUESTS and all(
        len(t) == GEN for t in plain["finished"].values())
    ok &= step_checks(params, cfg, prompts)

    xla = run("paged fused xla twin", cfg.replace(attn_impl="xla"))
    ok &= token_agreement("pallas vs xla twin", xla["finished"],
                          plain["finished"])

    draft = serve.make_self_draft(params, cfg, 4)
    spec = run("speculative self:4 gamma=4", cfg, draft=draft, gamma=GAMMA)
    log(f"  speculative accept_rate={spec['accept_rate']:.3f} "
        f"tokens_per_verify={spec['tokens_per_verify']:.2f}")
    ok &= token_agreement("speculative vs plain", spec["finished"],
                          plain["finished"])
    return bool(ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    log(f"device: {dev}")
    if dev["platform"] != "tpu":
        log("no TPU found: this smoke test runs only on the chip")
        return 1

    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")

    ok = True
    phases = (("kernels", lambda: kernel_phase(args.seed)),
              ("serve", lambda: serve_phase(full_width_config(), args.seed)))
    for name, phase in phases:
        t = time.perf_counter()
        log(f"[{name}]")
        passed = phase()
        log(f"[{name}] {'passed' if passed else 'FAILED'} in "
            f"{time.perf_counter() - t:.1f}s")
        ok &= passed
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    if not ok:
        log("chip smoke FAILED")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
