"""Production training driver with fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_1_7b --steps 200 \
      --smoke --ckpt-dir /tmp/ckpt [--resume]

Behaviour:
  * auto-resume from the newest VALID checkpoint (corrupt ones skipped);
  * checkpoint every --ckpt-every steps, atomic, k-retention;
  * the data-pipeline cursor and RNG state live inside the checkpoint, so
    a restart reproduces the exact batch sequence (bitwise resume — see
    tests/test_fault_tolerance.py);
  * --watchdog respawns the training child process on crash (simulated
    node failure), resuming from the latest checkpoint;
  * elastic: --mesh d,m restores any checkpoint onto a new mesh shape.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

from repro import obs


def train_main(args) -> int:
    import jax
    from repro.configs.registry import get_config, get_smoke_config
    from repro.data.pipeline import PipelineState, TokenPipeline
    from repro.data.synthetic import human_like
    from repro.data.tokenizer import encode
    from repro.launch.mesh import local_mesh, make_mesh
    from repro.models.schema import init_params
    from repro.train.checkpoint import restore_latest, save_checkpoint
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.train_loop import make_train_step

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh((d, m), ("data", "model"))
    else:
        mesh = local_mesh()
    opt = AdamWConfig(learning_rate=args.lr, warmup_steps=20,
                      total_steps=args.steps,
                      grad_compress=args.grad_compress)

    corpus = encode(human_like("wiki", args.corpus_bytes, seed=1))
    pipe = TokenPipeline(corpus, global_batch=args.batch,
                         seq_len=args.seq_len)

    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_state = init_opt_state(params, opt)
    state_like = {"params": params, "opt": opt_state,
                  "pipe": {"step": np.zeros((), np.int64)}}
    start = 0
    if args.ckpt_dir:
        restored, step = restore_latest(args.ckpt_dir, state_like)
        if restored is not None:
            params = restored["params"]
            opt_state = restored["opt"]
            start = int(restored["pipe"]["step"])
            pipe.state.step = start
            obs.log("train.resume", restored=step, continuing=start)

    step_fn = make_train_step(cfg, mesh, opt=opt,
                              num_microbatches=args.microbatches,
                              global_batch=args.batch,
                              loss_block=args.loss_block)
    t0 = time.time()
    for step in range(start, args.steps):
        batch = {"tokens": pipe.global_batch_array(step)}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        pipe.advance()
        if step % args.log_every == 0:
            obs.log("train.step", step=step,
                    loss=round(float(metrics["loss"]), 4),
                    gnorm=round(float(metrics["grad_norm"]), 3),
                    elapsed_s=round(time.time() - t0, 1))
        if args.crash_at is not None and step == args.crash_at:
            # StreamHandler flushes per record, so this line survives the
            # hard exit below (os._exit skips interpreter buffers)
            obs.log("train.fault_injection", step=step)
            os._exit(42)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1, {
                "params": params, "opt": opt_state,
                "pipe": {"step": np.asarray(step + 1, np.int64)},
            })
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, {
            "params": params, "opt": opt_state,
            "pipe": {"step": np.asarray(args.steps, np.int64)},
        })
    obs.log("train.done", final_loss=round(float(metrics["loss"]), 4))
    return 0


def watchdog(args) -> int:
    """Respawn the trainer until it exits cleanly (node-failure recovery).

    This parent must never initialize a JAX backend: a chip belongs to one
    process, and a parent holding it would make every child trainer fail
    or hang. All JAX work stays in train_main, which only children run."""
    attempts = 0
    argv = [a for a in sys.argv[1:] if a != "--watchdog"]
    while attempts < args.max_restarts + 1:
        rc = subprocess.call([sys.executable, "-m", "repro.launch.train",
                              *argv])
        if rc == 0:
            return 0
        attempts += 1
        obs.log_error("train.watchdog_restart", rc=rc, restart=attempts)
        # after a crash, never replay the same fault injection
        if "--crash-at" in argv:
            i = argv.index("--crash-at")
            argv = argv[:i] + argv[i + 2:]
        argv = [a for a in argv if not a.startswith("--crash-at=")]
    return 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--loss-block", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--corpus-bytes", type=int, default=1 << 20)
    ap.add_argument("--mesh", default=None, help="data,model e.g. 2,4")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--crash-at", type=int, default=None,
                    help="fault injection: hard-exit at this step")
    ap.add_argument("--watchdog", action="store_true")
    ap.add_argument("--max-restarts", type=int, default=3)
    args = ap.parse_args()
    # the trainer's operational log is its stdout contract: the watchdog
    # test greps the child's stdout for train.resume / train.done
    obs.configure(stream=sys.stdout)
    if args.watchdog:
        raise SystemExit(watchdog(args))
    raise SystemExit(train_main(args))


if __name__ == "__main__":
    main()
