"""LM training driver: config → mesh → sharded train loop with
checkpoint/restart. Runs reduced configs end-to-end on CPU (examples/)
and full configs on a real pod with the same code path.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs import get_config, get_reduced
from repro.data.loader import lm_batches
from repro.launch import sharding as SH
from repro.launch.mesh import batch_axes, make_host_mesh
from repro.models import model as Md
from repro.models.transformer import ShardingPolicy
from repro.optim.adamw import for_config
from repro.runtime.fault import StepMonitor


def build(cfg, mesh, seed: int = 0):
    dp = 1
    for a in batch_axes(mesh):
        dp *= mesh.shape[a]
    policy = ShardingPolicy(batch=batch_axes(mesh), model="model",
                            tp_size=mesh.shape["model"], dp_size=dp)
    cfg = cfg.with_policy(policy)
    opt = for_config(cfg)

    def init_state(key):
        params = Md.init_params(cfg, key)
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state_shapes = jax.eval_shape(init_state, jax.random.PRNGKey(seed))
    specs = SH.train_state_specs(cfg, state_shapes, mesh)
    with jax.set_mesh(mesh):
        state = jax.jit(
            init_state,
            out_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s), specs),
        )(jax.random.PRNGKey(seed))
    step = jax.jit(Md.make_train_step(cfg, opt, param_specs=specs["params"]),
                   donate_argnums=(0,))
    return cfg, state, step, specs


def train(cfg, *, steps: int, batch: int, seq: int, ckpt_dir: str | None = None,
          ckpt_every: int = 50, mesh=None, log=print, seed: int = 0):
    mesh = mesh or make_host_mesh(data=max(1, len(jax.devices())), model=1)
    cfg, state, step, specs = build(cfg, mesh, seed)
    manager = CheckpointManager(ckpt_dir, every=ckpt_every) if ckpt_dir else None
    if manager is not None:
        restored, s0 = manager.restore_latest(like=jax.device_get(state))
        if restored is not None:
            from repro.ckpt.elastic import reshard_tree
            state = reshard_tree(restored, specs, mesh)
            log(f"resumed from step {s0}")
    monitor = StepMonitor()
    stream = lm_batches(cfg.vocab, batch, seq)
    history = []
    with jax.set_mesh(mesh):
        start = int(state["step"])
        for i, b in zip(range(start, steps), stream):
            with monitor:
                state, metrics = step(state, b)
            loss = float(metrics["loss"])
            history.append(loss)
            if manager:
                manager.maybe_save(state, i + 1)
            if i % 10 == 0 or i == steps - 1:
                log(f"step {i} loss {loss:.4f} ema_s {monitor.ema and round(monitor.ema, 3)}")
    if manager:
        manager.maybe_save(state, steps, force=True)
        manager.wait()
    return state, history, monitor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced and cfg.accum_steps > 1 and args.batch % cfg.accum_steps:
        import dataclasses
        cfg = dataclasses.replace(cfg, accum_steps=1)
    t0 = time.time()
    _, history, monitor = train(cfg, steps=args.steps, batch=args.batch,
                                seq=args.seq, ckpt_dir=args.ckpt_dir,
                                ckpt_every=args.ckpt_every)
    print(f"final loss {history[-1]:.4f} (from {history[0]:.4f}) "
          f"in {time.time()-t0:.1f}s; stragglers: {len(monitor.stragglers)}")


if __name__ == "__main__":
    main()
