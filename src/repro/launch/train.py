"""End-to-end training driver.

``python -m repro.launch.train --arch h2o-danube-1.8b --reduced --steps 200``
runs a reduced config on local devices; on a real cluster the same driver
binds the production mesh (--mesh single|multi) and full config.  Features:
deterministic data, jit'd train step with sharded params/optimizer, async
atomic checkpoints every --ckpt-every steps, automatic resume (elastic: the
checkpoint restores onto whatever mesh is present), bf16 gradient-compression
flag, microbatch accumulation.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--fft-backend", choices=["jnp", "pallas"], default=None,
                    help="override the config's fft_backend (fft_conv plans "
                         "+ fourier_mix) for A/B runs")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress", choices=["none", "bf16"], default="none")
    ap.add_argument("--ckpt-dir", default="runs/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "single", "multi"],
                    default="none")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    import jax
    from . import compile_cache
    compile_cache.enable()
    import repro.configs as C
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import model as M, actsharding
    from repro.train import optimizer as opt_lib
    from repro.train.checkpoint import CheckpointManager
    from repro.train.train_step import make_train_step, init_opt_state
    from . import mesh as mesh_lib, sharding as sh

    cfg = C.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.fft_backend is not None:
        cfg = dataclasses.replace(cfg, fft_backend=args.fft_backend)
    dcfg = DataConfig(seq_len=args.seq_len, global_batch=args.global_batch)
    data = SyntheticLM(dcfg, cfg)
    ocfg = opt_lib.AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                               total_steps=args.steps)
    compress = None if args.compress == "none" else args.compress
    step_fn = make_train_step(cfg, ocfg, microbatches=args.microbatches,
                              compress=compress)

    mesh = None
    if args.mesh != "none":
        mesh = mesh_lib.make_production_mesh(multi_pod=args.mesh == "multi")

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = init_opt_state(cfg, ocfg, params, compress=compress)
    print(f"[train] {cfg.name}: {M.param_count(params)/1e6:.1f}M params, "
          f"{args.steps} steps, batch {args.global_batch} x {args.seq_len}")

    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    start = 0
    latest = mgr.latest_step()
    if latest is not None:
        (params, opt_state), extra = mgr.restore(
            latest, (params, opt_state))
        start = int(extra.get("data_step", latest))
        print(f"[train] resumed from step {latest}")

    if mesh is not None:
        pshard = sh.param_shardings(cfg, mesh, jax.eval_shape(lambda: params))
        params = jax.device_put(params, pshard)
        ctx = lambda: actsharding.activation_spec(
            mesh, mesh_lib.data_axes(mesh), "model")
    else:
        ctx = contextlib.nullcontext

    with (mesh if mesh is not None else contextlib.nullcontext()), ctx():
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        t0 = time.time()
        t_steady = None                       # set after step 1 (post-compile)
        for step in range(start, args.steps):
            batch = data.batch_at(step)
            params, opt_state, metrics = jit_step(params, opt_state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"[train] step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)", flush=True)
            if t_steady is None:
                jax.block_until_ready(metrics["loss"])
                t_steady = time.time()        # compile excluded from tok/s
            if args.ckpt_every and step and step % args.ckpt_every == 0:
                mgr.save_async(step, (params, opt_state),
                               extra={"data_step": step + 1})
        jax.block_until_ready(params)
        steady_steps = args.steps - start - 1
        if steady_steps > 0:
            toks = steady_steps * args.global_batch * args.seq_len
            print(f"[train] tokens/sec {toks / (time.time() - t_steady):.0f} "
                  f"(fft_backend={cfg.fft_backend}, steady steps "
                  f"{steady_steps})", flush=True)
        mgr.wait()
        mgr.save(args.steps, (params, opt_state),
                 extra={"data_step": args.steps})
    print("[train] done")


if __name__ == "__main__":
    main()
