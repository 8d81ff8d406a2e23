"""End-to-end training driver: ~100M-parameter LM for a few hundred steps.

    PYTHONPATH=src python examples/train_lm.py --steps 300 --arch granite-3-8b
    PYTHONPATH=src python examples/train_lm.py --resume   # restart from ckpt

Demonstrates the full production loop on whatever devices this host has:
sharded params (policy), deterministic seekable data, checkpoint/restart
(preemption-safe), straggler monitoring, heartbeat, grad accumulation.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.distributed.fault_tolerance import (Heartbeat, PreemptionGuard,
                                               StragglerMonitor)
from repro.distributed.sharding import ShardingPolicy
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig
from repro.training.train_step import TrainConfig, make_train_state, train_step


def scale_config(cfg, d_model=512, n_layers=8):
    """~100M-parameter variant of an assigned arch (same family)."""
    heads = max(d_model // 128, 4)
    return dataclasses.replace(
        cfg, n_layers=n_layers, d_model=d_model, n_heads=heads,
        n_kv_heads=max(heads // 4, 1), d_ff=d_model * 3,
        head_dim=d_model // heads, vocab_size=32768,
        global_layers=tuple(g for g in cfg.global_layers if g < n_layers))


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    args = ap.parse_args()

    cfg = scale_config(get_config(args.arch), args.d_model, args.layers)
    mesh = make_host_mesh()
    policy = ShardingPolicy(mesh, cfg)
    tcfg = TrainConfig(
        microbatches=2, remat=True,
        opt=AdamWConfig(lr_peak=3e-4, warmup_steps=20,
                        decay_steps=args.steps))

    params = T.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M devices={mesh.size}")

    state = make_train_state(params, tcfg)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    start_step = 0
    if args.resume and mgr.latest_step() is not None:
        state, manifest = mgr.restore(state)
        start_step = manifest["step"]
        print(f"resumed from step {start_step}")
    with mesh:
        state = jax.device_put(state, policy.tree_shardings(state))

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    data = SyntheticLM(data_cfg)
    data.seek(start_step)                 # replay-free restart
    pipe = Prefetcher(data, depth=2)

    guard = PreemptionGuard().install()
    hb = Heartbeat("/tmp/repro_heartbeat", interval_s=10.0)
    straggler = StragglerMonitor()
    step_fn = jax.jit(lambda s, b: train_step(s, b, cfg=cfg, tcfg=tcfg,
                                              hints=policy.hints()),
                      donate_argnums=0)

    step = start_step
    with mesh:
        for batch_np in pipe:
            if step >= args.steps or guard.should_stop:
                break
            t0 = time.time()
            batch = jax.tree.map(jnp.asarray, batch_np)
            state, metrics = step_fn(state, batch)
            dt = time.time() - t0
            if straggler.observe(step, dt):
                print(f"[straggler] step {step} took {dt:.2f}s "
                      f"(ema {straggler.ema:.2f}s)")
            hb.beat(step)
            step += 1
            if step % 10 == 0:
                print(f"step {step:4d} loss {float(metrics['loss']):7.4f} "
                      f"acc {float(metrics['accuracy']):.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt*1e3:6.1f}ms")
            if step % args.ckpt_every == 0 or guard.should_stop:
                mgr.save(step, jax.device_get(state),
                         metadata={"arch": cfg.name}, blocking=False)
    pipe.close()
    mgr.wait()
    mgr.save(step, jax.device_get(state), metadata={"arch": cfg.name})
    print(f"finished at step {step}; checkpoint in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
