"""Training driver with fault tolerance.

Features:
  * resumes from the latest checkpoint (step-atomic; data stream is
    seekable by step so the token sequence is bit-identical across
    restarts);
  * per-step watchdog — a step exceeding ``--watchdog`` seconds logs a
    straggler warning (on a real cluster this triggers requeue/replace;
    here it is surfaced and counted);
  * elastic: restoring onto a different mesh shape reshards automatically
    (checkpoint stores logical arrays; device_put applies new shardings);
  * crash-injection hook (--crash-at) used by the integration test to
    prove restart-exactness.

CPU demo (reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --reduced --steps 30 --batch 8 --seq 128 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import ckpt
from repro.configs import get_arch, reduced
from repro.data import lm_batch
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.optim import adamw
from repro.runtime import sharding as shardlib
from repro.runtime import train as train_rt
from repro.runtime.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--watchdog", type=float, default=120.0,
                    help="straggler threshold (s/step)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--crash-at", type=int, default=None,
                    help="raise after N steps (fault-tolerance test)")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = make_local_mesh()
    tcfg = train_rt.TrainConfig(
        microbatches=args.microbatches, remat=True, lr=args.lr,
        total_steps=args.steps)

    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    opt_state = adamw.init_state(params)
    start_step = 0

    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        tree = {"params": params, "opt": opt_state}
        shardings = {
            "params": shardlib.param_shardings(cfg, mesh, params),
            "opt": {"mu": shardlib.param_shardings(cfg, mesh, params),
                    "nu": shardlib.param_shardings(cfg, mesh, params),
                    "count": None},
        }
        restored, meta = ckpt.restore(args.ckpt_dir, tree)
        params, opt_state = restored["params"], restored["opt"]
        start_step = int(meta["step"]) + 1
        print(f"[train] resumed from step {meta['step']} "
              f"(elastic mesh {tuple(mesh.shape.values())})")

    step_fn = train_rt.jit_train_step(cfg, tcfg, mesh, params, opt_state,
                                      args.batch)

    stragglers = 0
    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = lm_batch(jnp.int32(step), batch=args.batch, seq=args.seq,
                         vocab=cfg.vocab_size, seed=args.seed)
        params, opt_state, metrics = step_fn(
            params, opt_state, batch, jnp.int32(step))
        loss = float(metrics["loss"])
        dt = time.time() - t0
        if dt > args.watchdog:
            stragglers += 1
            print(f"[train] WARNING step {step} straggled: {dt:.1f}s")
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt:.2f}s",
                  flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, {"params": params, "opt": opt_state},
                      step=step, metadata={"step": step, "seed": args.seed})
            ckpt.prune_old(args.ckpt_dir, keep=2)
        if args.crash_at is not None and step + 1 >= args.crash_at:
            raise RuntimeError(f"injected crash at step {step}")
    print(f"[train] done: {args.steps} steps, {stragglers} stragglers, "
          f"final loss {loss:.4f}")
    return loss


if __name__ == "__main__":
    main()
