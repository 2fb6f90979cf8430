"""End-to-end training driver with checkpoint/restart fault tolerance.

Runs on whatever devices the host has (CPU tests use the unit mesh; a TPU
pod picks up the full mesh). With --svff the job runs as a Tenant under the
SVFFManager — pause/reconf-able mid-run via the QMP socket (the paper's
deployment shape); without it, a plain standalone loop.

Restart semantics: --resume finds the newest valid checkpoint (manifest is
written last, so a crash mid-save is invisible) and continues with
bit-identical data order (batches are a pure function of step).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.store import CheckpointStore
from repro.configs import (OptimizerConfig, SHAPES, list_archs,
                           make_run_config)
from repro.data.pipeline import Prefetcher, SyntheticSource
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import local_mesh_config, make_mesh_from_config
from repro.runtime.partitioning import ShardingRules
from repro.train.step import init_train_state, make_train_step


def build(args):
    mesh_cfg = local_mesh_config()
    overrides = {}
    if args.lr:
        overrides["optimizer"] = OptimizerConfig(lr=args.lr,
                                                 warmup=args.warmup)
    run = make_run_config(args.arch, args.shape, mesh=mesh_cfg,
                          smoke=args.smoke, microbatch=args.microbatch,
                          **overrides)
    if args.batch or args.seq:
        shape = dataclasses.replace(
            run.shape,
            global_batch=args.batch or run.shape.global_batch,
            seq_len=args.seq or run.shape.seq_len)
        run = dataclasses.replace(run, shape=shape)
    mesh = (make_mesh_from_config(mesh_cfg)
            if mesh_cfg.num_devices > 1 else None)
    rules = ShardingRules(mesh_cfg, run, mesh) if mesh else None
    return run, rules


def train(args) -> dict:
    run, rules = build(args)
    store = CheckpointStore(os.path.join(args.workdir, "ckpt"),
                            keep=args.keep)
    step_fn = jax.jit(make_train_step(run, rules,
                                      total_steps=args.steps))
    state = init_train_state(run, jax.random.key(run.seed))
    start = 0
    if args.resume and store.latest() is not None:
        state = store.restore(store.latest(), state)
        state = jax.tree.map(jnp.asarray, state)
        start = int(state["step"])
        print(f"[train] resumed from step {start}", flush=True)

    src = SyntheticSource(run, batch_override=run.shape.global_batch,
                          seq_override=run.shape.seq_len)
    pf = Prefetcher(src, depth=2, start_step=start)
    log_path = os.path.join(args.workdir, "metrics.jsonl")
    os.makedirs(args.workdir, exist_ok=True)
    tokens_per_step = run.shape.global_batch * run.shape.seq_len
    t_start = time.perf_counter()
    last = {}
    try:
        for i in range(start, args.steps):
            step_idx, batch = pf.next()
            assert step_idx == i
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            last = {k: float(v) for k, v in metrics.items()}
            last.update(step=i + 1, step_s=dt,
                        tokens_per_s=tokens_per_step / dt)
            with open(log_path, "a") as f:
                f.write(json.dumps(last) + "\n")
            if args.log_every and (i + 1) % args.log_every == 0:
                print(f"[train] step {i+1} loss {last['loss']:.4f} "
                      f"({last['tokens_per_s']:.0f} tok/s)", flush=True)
            if args.checkpoint_every and (i + 1) % args.checkpoint_every == 0:
                store.save_async(i + 1, state)
            if args.crash_at and (i + 1) == args.crash_at:
                print("[train] simulated crash", flush=True)
                store.wait()
                os._exit(17)        # hard kill: restart path must recover
    finally:
        pf.stop()
    store.wait()
    store.save(args.steps, state)
    last["wall_s"] = time.perf_counter() - t_start
    return last


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list_archs())
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.0)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--crash-at", type=int, default=0,
                    help="simulate a hard crash after N steps (testing)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    last = train(args)
    print(json.dumps(last))
    return 0


if __name__ == "__main__":
    sys.exit(main())
