"""Training driver: ``python -m repro.launch.train --arch <id> [--smoke] ...``

Production path (TPU): builds the mesh, shards params/optimizer/batches via
GSPMD, checkpoints every --ckpt-every steps (atomic, keep-K), auto-resumes
from the latest checkpoint (including onto a DIFFERENT mesh shape — elastic
restart), and handles SIGTERM preemption by saving before exit.

CPU path (--smoke / this container): same code on a 1×1 mesh with the
reduced config — the end-to-end driver for deliverable (b).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.ckpt.manager import CheckpointManager
from repro.data.pipeline import DataConfig, TokenPipeline, stub_inputs
from repro.launch import mesh as meshlib
from repro.models import layers, params as params_lib, transformer
from repro.train import optimizer as opt, step as step_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config on CPU")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="1x1", help="data×model, e.g. 16x16")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--state-dtype", default="f32")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.get_config(args.arch)
    if args.smoke:
        cfg = configs.reduce_config(cfg)
    dp, tp = (int(x) for x in args.mesh.split("x"))
    mesh = meshlib.make_mesh((dp, tp), ("data", "model"))
    if mesh.size > 1:
        layers.enable_activation_sharding(mesh)

    tcfg = step_lib.TrainConfig(
        adamw=opt.AdamWConfig(
            lr=args.lr, warmup_steps=min(20, args.steps // 10 + 1),
            total_steps=args.steps, state_dtype=args.state_dtype,
        ),
        ce_chunk=min(1024, args.seq_len),
    )
    specs = transformer.model_specs(cfg)
    param_sh = meshlib.param_shardings(specs, mesh)

    key = jax.random.PRNGKey(args.seed)
    with jax.set_mesh(mesh):
        params = params_lib.materialize(specs, key)
        params = jax.tree.map(jax.device_put, params, param_sh)
        opt_state = opt.init_state(params, tcfg.adamw)

    data = TokenPipeline(
        DataConfig(args.seq_len, args.global_batch, cfg.vocab_size, args.seed)
    )
    extra = stub_inputs(cfg, args.global_batch)

    mgr = None
    start_step = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        mgr.install_preemption_handler()
        latest = mgr.latest_step()
        if latest is not None:
            # elastic restore: reshard onto the CURRENT mesh
            state_like = {"params": params, "opt": opt_state}
            sh_like = {
                "params": param_sh,
                "opt": jax.tree.map(lambda _: None, opt_state),
            }
            restored = mgr.restore(latest, state_like)
            params = jax.tree.map(jax.device_put, restored["params"], param_sh)
            opt_state = restored["opt"]
            start_step = latest
            print(f"[train] resumed from step {latest}")

    train_step = jax.jit(
        step_lib.make_train_step(cfg, tcfg), donate_argnums=(0, 1)
    )

    t0 = time.time()
    losses = []
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        batch.update(extra)
        with mesh:
            params, opt_state, metrics = train_step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            tok_s = args.global_batch * args.seq_len * (step - start_step + 1) / max(dt, 1e-9)
            print(
                f"[train] step={step} loss={losses[-1]:.4f} "
                f"lr={float(metrics['lr']):.2e} gnorm={float(metrics['grad_norm']):.2f} "
                f"tok/s={tok_s:,.0f}"
            )
        if mgr and (step % args.ckpt_every == args.ckpt_every - 1 or mgr.preempted):
            mgr.save(step + 1, {"params": params, "opt": opt_state})
            if mgr.preempted:
                print("[train] preemption save complete; exiting")
                return losses
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state})
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
