"""Distributed LM training driver.

Wires every substrate layer together: config -> sharded init -> data
pipeline -> pjit train step -> async checkpointing -> straggler monitor ->
failure recovery.  On this CPU container it runs reduced configs end-to-end
(examples/train_lm.py uses it for the ~100M-param run); on a real pod the
same driver scales by pointing --mesh at the production topology.

Usage:
  python -m repro.launch.train --arch gemma_2b --smoke --steps 100
  python -m repro.launch.train --arch kimi_k2_1t_a32b --smoke --data 2 --model 4
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.data.pipeline import Prefetcher, ShardedLoader
from repro.data.synthetic import synthetic_tokens
from repro.lm import model as M
from repro.parallel import sharding as SH
from repro.runtime.straggler import StragglerMonitor
from repro.train import checkpoint as ckpt_lib
from repro.train import optim as optim_lib

__all__ = ["train_loop", "main"]


def train_loop(cfg, mesh, *, steps: int, batch_size: int, seq_len: int,
               lr: float = 3e-3, ckpt_dir=None, ckpt_every: int = 50,
               resume: bool = True, log=print, seed: int = 0,
               optimizer: str = "adafactor"):
    opt = (optim_lib.adafactor(lr) if optimizer == "adafactor"
           else optim_lib.adam(lr))

    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    opt_state = opt.init(params)
    state = {"params": params, "opt": opt_state,
             "step": jnp.zeros((), jnp.int32)}

    pspecs = SH.param_specs(jax.eval_shape(lambda: params), cfg, mesh)
    sspecs = {"params": pspecs,
              "opt": SH.opt_state_specs(pspecs, jax.eval_shape(lambda: opt_state), mesh),
              "step": P()}
    sshard = SH.shardings(sspecs, mesh)
    state = jax.device_put(state, sshard)

    batch_fn = lambda step: (synthetic_tokens(
        step, batch_size, seq_len, cfg.vocab, seed=seed),)
    loader = ShardedLoader(
        batch_fn, mesh,
        [P(tuple(n for n in mesh.axis_names if n in ("pod", "data")), None)])

    step_fn = M.make_train_step(cfg, mesh, opt)
    bshard = SH.shardings(SH.batch_specs(
        jax.eval_shape(lambda: {"tokens": np.zeros((batch_size, seq_len + 1), np.int32)}),
        cfg, mesh), mesh)
    with jax.set_mesh(mesh):
        jstep = jax.jit(step_fn, in_shardings=(sshard, bshard),
                        out_shardings=(sshard, None), donate_argnums=(0,))

        start = 0
        manager = ckpt_lib.CheckpointManager(ckpt_dir) if ckpt_dir else None
        if manager and resume:
            last = ckpt_lib.latest_step(ckpt_dir)
            if last is not None:
                state, extra = ckpt_lib.restore(ckpt_dir, last, state)
                start = last
                log(f"[train] resumed from step {last}")

        monitor = StragglerMonitor()
        history = []
        pf = Prefetcher(lambda s: loader(s), start, steps - start, depth=2)
        for s, (tokens,) in pf:
            t0 = time.time()
            state, metrics = jstep(state, {"tokens": tokens})
            jax.block_until_ready(metrics["loss"])
            dt = time.time() - t0
            monitor.record(s, dt)
            history.append(float(metrics["loss"]))
            if s % 10 == 0 or s == steps - 1:
                log(f"[train] step {s:5d} loss {float(metrics['loss']):.4f} "
                    f"acc {float(metrics['acc']):.3f} "
                    f"gnorm {float(metrics['grad_norm']):.2f} {dt*1e3:.0f}ms"
                    + (" STRAGGLER" if monitor.is_outlier(dt) else ""))
            if manager and (s + 1) % ckpt_every == 0:
                manager.save(s + 1, state)
        if manager:
            manager.save(steps, state)
            manager.wait()
    return state, history


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh(data=args.data, model=args.model)
    train_loop(cfg, mesh, steps=args.steps, batch_size=args.batch,
               seq_len=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir)


if __name__ == "__main__":
    main()
