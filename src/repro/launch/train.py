"""Production training driver.

Wires together: config registry, synthetic data pipeline, sharded train step,
checkpoint manager (atomic + async + SIGTERM preemption save), straggler
watchdog, and optional int8 error-feedback gradient compression for the
inter-pod all-reduce.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1p1b \
        --smoke --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

``--smoke`` selects the arch's reduced config so the driver runs end-to-end
on one CPU; the same code path drives the production mesh when devices exist
(``--mesh single|multi``).  Restart the same command after an interruption
and it resumes from the latest checkpoint — the data pipeline is
stateless-seeded so the token stream continues exactly.
"""
from __future__ import annotations

import argparse
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_arch
from repro.data.pipeline import DataConfig, batch_for_step
from repro.dist import compression
from repro.dist import sharding as sh
from repro.dist.straggler import StragglerWatchdog
from repro.launch import compile_cache
from repro.launch import steps as st
from repro.launch.mesh import logical_rules, make_production_mesh
from repro.optim import adamw


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1p1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default=None, choices=[None, "single", "multi"])
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 error-feedback gradient compression "
                         "(repro.dist.compression); the residual is not "
                         "checkpointed — a resume restarts it at zero")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    compile_cache.enable()

    arch = get_arch(args.arch)
    cfg = arch.smoke if args.smoke else arch.config
    if args.smoke:
        cfg = cfg.replace(dtype="float32")

    opt_cfg = adamw.OptimizerConfig(peak_lr=args.lr,
                                    warmup_steps=args.warmup,
                                    total_steps=args.steps)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed,
                          frames=cfg.family == "encdec",
                          d_model=cfg.d_model)

    # ---- mesh / shardings --------------------------------------------------
    mesh = None
    if args.mesh:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    key = jax.random.PRNGKey(args.seed)
    init_fn = st.init_params_fn(cfg)
    params = init_fn(key)
    opt_state = adamw.init_state(params)
    if args.compress_grads:
        train_step = st.make_compressed_train_step(cfg, opt_cfg)
        grad_err = compression.init_error(params)
    else:
        train_step = st.make_train_step(cfg, opt_cfg)
        grad_err = None

    if mesh is not None:
        p_shard = sh.param_shardings(params, cfg, mesh)
        params = jax.device_put(params, p_shard)
        o_shard = adamw.OptState(step=sh.replicated(mesh),
                                 mu=sh.param_shardings(opt_state.mu, cfg,
                                                       mesh),
                                 nu=sh.param_shardings(opt_state.nu, cfg,
                                                       mesh))
        opt_state = jax.device_put(opt_state, o_shard)
        if grad_err is not None:
            grad_err = jax.device_put(grad_err, p_shard)
            jitted = jax.jit(train_step,
                             in_shardings=(p_shard, o_shard, p_shard, None),
                             donate_argnums=(0, 1, 2))
        else:
            jitted = jax.jit(train_step,
                             in_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
    elif grad_err is not None:
        jitted = jax.jit(train_step, donate_argnums=(0, 1, 2))
    else:
        jitted = jax.jit(train_step, donate_argnums=(0, 1))

    # ---- checkpoint/resume -------------------------------------------------
    start_step = 0
    ckpt: Optional[CheckpointManager] = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir)
        if ckpt.latest_step() is not None:
            start_step, (params, opt_state), extra = ckpt.restore(
                None, (params, opt_state))
            print(f"resumed from step {start_step}", flush=True)
        latest = {"step": 0, "state": (params, opt_state)}
        ckpt.install_sigterm_handler(
            lambda: (latest["step"], latest["state"]))

    watchdog = StragglerWatchdog(
        on_straggler=lambda r: print(
            f"  [straggler] step {r.step}: {r.seconds:.2f}s "
            f"({r.ratio:.1f}x median)", flush=True))

    # ---- loop ---------------------------------------------------------------
    ctx = sh.axis_rules(mesh, logical_rules(mesh)) if mesh else _null_ctx()
    with ctx:
        t_start = time.time()
        for step in range(start_step, args.steps):
            batch = batch_for_step(data_cfg, step)
            watchdog.start_step()
            if grad_err is not None:
                params, opt_state, grad_err, metrics = jitted(
                    params, opt_state, grad_err, batch)
            else:
                params, opt_state, metrics = jitted(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
            watchdog.end_step(step)
            if ckpt:
                latest = {"step": step + 1, "state": (params, opt_state)}
            if (step + 1) % args.log_every == 0 or step == start_step:
                print(f"step {step + 1:5d} loss {float(metrics['loss']):.4f}"
                      f" ce {float(metrics.get('ce', metrics['loss'])):.4f}"
                      f" lr {float(metrics['lr']):.2e}"
                      f" gnorm {float(metrics['grad_norm']):.2f}",
                      flush=True)
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save_async(step + 1, (params, opt_state),
                                extra={"seed": args.seed})
    if ckpt:
        ckpt.wait()
        ckpt.save(args.steps, jax.tree.map(np.asarray, (params, opt_state)),
                  extra={"final": True})
    dt = time.time() - t_start
    n_steps = args.steps - start_step
    print(f"done: {n_steps} steps in {dt:.1f}s "
          f"({dt / max(n_steps, 1):.3f}s/step); "
          f"stragglers flagged: {len(watchdog.reports)}", flush=True)


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
