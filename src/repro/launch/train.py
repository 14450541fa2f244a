"""Training driver: data pipeline + sharded train step + fault tolerance.

CPU-runnable end to end with ``--smoke`` configs (the examples train a
~100M model for a few hundred steps); the same driver lowers unchanged on
the production mesh (see dryrun.py for the no-hardware path).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
      --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs as cfgs
from repro.checkpoint import CheckpointManager
from repro.data import MarkovTask
from repro.distributed.fault import FaultTolerantRunner
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LM, init_params
from repro.optim import adamw, warmup_cosine
from repro.train import make_train_step

__all__ = ["train_loop", "main"]


def train_loop(arch: str, *, smoke: bool = True, steps: int = 100,
               batch: int = 8, seq: int = 64, ckpt_dir: str | None = None,
               peak_lr: float = 3e-3, accum: int = 1, log_every: int = 10,
               seed: int = 0, fault_hook=None):
    cfg = cfgs.get_smoke_config(arch) if smoke else cfgs.get_config(arch)
    model = LM(cfg)
    task = MarkovTask(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch, seed=seed)
    lr = lambda s: warmup_cosine(s, peak_lr=peak_lr, warmup_steps=steps // 10 + 1,
                                 total_steps=steps)
    opt = adamw(lr)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(model, opt, accum_steps=accum),
                      donate_argnums=(0, 1))

    losses: list[float] = []

    def one_step(state, step):
        params, opt_state = state
        batch_t = task.batch(step)
        params, opt_state, metrics = step_fn(params, opt_state, batch_t,
                                             jnp.asarray(step, jnp.int32))
        if step % log_every == 0 or step == steps - 1:
            loss = float(metrics["loss"])
            losses.append(loss)
            print(f"[train {arch}] step {step:5d} loss {loss:.4f} "
                  f"lr {float(metrics.get('lr', 0)):.2e}")
        return (params, opt_state)

    state = (params, opt_state)
    if ckpt_dir is not None:
        manager = CheckpointManager(ckpt_dir, keep=3)
        runner = FaultTolerantRunner(one_step, manager,
                                     checkpoint_every=max(steps // 4, 10))
        start = manager.latest_step() or 0
        if start:
            start, state = manager.restore_latest(state)
            print(f"[train {arch}] resumed from step {start}")
        state, report = runner.run(state, start, steps - start,
                                   fault_hook=fault_hook)
        print(f"[train {arch}] done: {report.steps_run} steps, "
              f"{report.failures_recovered} recoveries, "
              f"{report.checkpoints_written} checkpoints")
    else:
        for step in range(steps):
            state = one_step(state, step)
    return state, losses, task


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m", choices=sorted(cfgs.ARCHS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    t0 = time.time()
    _, losses, task = train_loop(args.arch, smoke=args.smoke, steps=args.steps,
                                 batch=args.batch, seq=args.seq,
                                 ckpt_dir=args.ckpt_dir, accum=args.accum,
                                 peak_lr=args.lr)
    print(f"[train] first loss {losses[0]:.3f} -> last {losses[-1]:.3f} "
          f"(markov entropy floor {task.entropy_floor_nats:.3f} nats) "
          f"in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
