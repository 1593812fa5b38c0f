"""End-to-end training driver: mesh, sharded init, data, checkpoints,
straggler monitoring, restart/elastic resume.

CLI (runs on CPU with reduced configs; the same code lowers onto the
production mesh):

    PYTHONPATH=src python -m repro.launch.train \
        --arch deepseek-moe-16b --reduced --steps 50 \
        --ckpt-dir /tmp/ckpt --ckpt-every 20 [--resume]

Fault-tolerance drill covered by tests/test_train_loop.py: kill between
checkpoints, resume, verify the loss curve continues bit-identically
(deterministic pipeline + checkpointed step).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_config, get_reduced
from repro.data.pipeline import Prefetcher, TokenPipeline
from repro.ft import checkpoint as ckpt
from repro.ft.elastic import choose_mesh_shape, make_mesh_from_plan
from repro.ft.straggler import StepMonitor
from repro.launch.mesh import batch_axes
from repro.models import model as M
from repro.models.steps import make_train_step
from repro.optim import adamw


def build_shardings(cfg, mesh):
    pspec = M.pspecs(cfg)
    to_shard = lambda spec: NamedSharding(mesh, spec)
    param_sh = jax.tree_util.tree_map(to_shard, pspec)
    dspec = adamw.zero1_pspecs(M.specs(cfg), pspec,
                               data_size=mesh.shape.get("data", 1))
    opt_leaf_sh = jax.tree_util.tree_map(to_shard, dspec)
    return param_sh, opt_leaf_sh


def train(cfg, *, steps: int, global_batch: int, seq_len: int,
          ckpt_dir: str | None = None, ckpt_every: int = 0,
          resume: bool = False, opt_cfg: adamw.AdamWConfig | None = None,
          mesh=None, log=print):
    opt_cfg = opt_cfg or adamw.AdamWConfig(total_steps=steps)
    if mesh is None:
        plan = choose_mesh_shape(len(jax.devices()))
        mesh = make_mesh_from_plan(plan)
    ba = batch_axes(mesh)

    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = ((cfg.n_frontend_tokens, cfg.frontend_dim),
                             np.float32)
    if cfg.family == "encdec":
        extras["frames"] = ((seq_len, cfg.frontend_dim), np.float32)
    pipe = TokenPipeline(cfg.vocab_size, seq_len, global_batch,
                         extras=extras)

    param_sh, opt_sh = build_shardings(cfg, mesh)
    batch_sh = {k: NamedSharding(mesh, P(ba)) for k in
                ["tokens"] + list(extras)}

    with jax.set_mesh(mesh):
        start_step = 0
        if resume and ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
            example = {
                "params": M.specs(cfg),
                "opt": adamw.AdamWState(
                    mu=jax.tree_util.tree_map(
                        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                        M.specs(cfg)),
                    nu=jax.tree_util.tree_map(
                        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                        M.specs(cfg)),
                    step=jax.ShapeDtypeStruct((), jnp.int32)),
            }
            shards = {"params": param_sh,
                      "opt": adamw.AdamWState(mu=opt_sh, nu=opt_sh,
                                              step=NamedSharding(mesh, P()))}
            state, start_step = ckpt.restore(ckpt_dir, example,
                                             shardings=shards)
            params, opt_state = state["params"], state["opt"]
            log(f"[train] resumed from step {start_step}")
        else:
            init_fn = jax.jit(partial(M.init, cfg),
                              out_shardings=param_sh)
            params = init_fn(jax.random.PRNGKey(0))
            opt_state = jax.jit(adamw.init,
                                out_shardings=adamw.AdamWState(
                                    mu=opt_sh, nu=opt_sh,
                                    step=NamedSharding(mesh, P())))(params)

        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg),
            in_shardings=(param_sh,
                          adamw.AdamWState(mu=opt_sh, nu=opt_sh,
                                           step=NamedSharding(mesh, P())),
                          batch_sh),
            donate_argnums=(0, 1))

        checkpointer = ckpt.AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        monitor = StepMonitor()
        prefetch = Prefetcher(pipe.batch_at, start_step=start_step)
        losses = []
        try:
            for step in range(start_step, steps):
                batch = prefetch.next()
                batch = {k: jax.device_put(v, batch_sh[k])
                         for k, v in batch.items()}
                with monitor:
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch)
                    loss = float(metrics["loss"])
                losses.append(loss)
                if step % 10 == 0 or step == steps - 1:
                    log(f"[train] step={step} loss={loss:.4f} "
                        f"gnorm={float(metrics['grad_norm']):.3f} "
                        f"t={monitor.median:.3f}s")
                for a in monitor.actions:
                    log(f"[straggler] {a}")
                monitor.actions.clear()
                if (checkpointer and ckpt_every
                        and (step + 1) % ckpt_every == 0):
                    checkpointer.save_async(
                        {"params": params, "opt": opt_state}, step + 1)
        finally:
            prefetch.close()
            if checkpointer:
                checkpointer.wait()
        return params, opt_state, losses


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--global-batch", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--resume", action="store_true")
    args = p.parse_args()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    train(cfg, steps=args.steps, global_batch=args.global_batch,
          seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
          ckpt_every=args.ckpt_every, resume=args.resume)


if __name__ == "__main__":
    main()
