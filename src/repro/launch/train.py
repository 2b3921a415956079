"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 50 --update-ratio 0.2 --update-layers 2 --ckpt-dir /tmp/run1

Runs the DGSU fine-tuning loop with checkpoint/restart (auto-resume from
the latest checkpoint in --ckpt-dir), preemption handling (SIGTERM ->
emergency save), and straggler monitoring. It runs in one process on its
default device (one TPU chip, or the CPU) and builds no mesh. On a TPU
backend `--compact-grads` runs the Pallas kernels, compiled; on the CPU
the jnp oracle path. The step is compiled ahead of the first step, so its
compile time and Pallas kernel sites are reported apart from step times.
"""
from __future__ import annotations

import argparse
import itertools
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import (OptimizerConfig, ShapeConfig, SparseUpdateConfig,
                           TrainConfig, get_config, get_smoke_config)
from repro.core.sparse_update import SelSpec
from repro.data import lm_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import kernel_launch_count
from repro.runtime import RestartableLoop, StragglerMonitor
from repro.train import make_train_state, make_train_step


def build_argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["sgd", "momentum", "adamw"])
    ap.add_argument("--dense", action="store_true", help="disable DGSU")
    ap.add_argument("--compact-grads", action="store_true",
                    help="compact-gradient path: never scatter a full-shape "
                         "dW; optimizer updates gathered blocks only")
    ap.add_argument("--update-ratio", type=float, default=0.2)
    ap.add_argument("--update-layers", type=int, default=0,
                    help="last-K scan blocks (0 = solve from budget)")
    ap.add_argument("--memory-budget-mb", type=float, default=0.0)
    ap.add_argument("--channel-block", type=int,
                    default=SparseUpdateConfig.channel_block,
                    help="channels per selection block; a width with no "
                         "divisor this large takes its largest divisor")
    ap.add_argument("--phase-j", type=int, default=10)
    ap.add_argument("--phase-k", type=int, default=30)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    """Train; returns {"losses", "step_s", "compile_s", "kernel_sites",
    "sel_leaves"}: per-step losses and wall times (each ends when the loss
    is on the host), the ahead-of-time compile time, the number of Pallas
    kernel sites in the compiled step (0 off TPU, where kernels are
    inlined), and the selectable leaves on the compact path (the fused
    path holds two sites per leaf)."""
    args = build_argparser().parse_args(argv)
    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    sparse = SparseUpdateConfig(
        enabled=not args.dense,
        update_ratio=args.update_ratio,
        num_update_layers=args.update_layers,
        memory_budget_bytes=int(args.memory_budget_mb * 2**20),
        channel_block=args.channel_block,
        phase_fixed_early=args.phase_j,
        phase_dynamic=args.phase_k,
        phase_fixed_late=max(0, args.steps - args.phase_j - args.phase_k),
        seed=args.seed,
    )
    tc = TrainConfig(
        model=cfg, shape=shape, sparse=sparse,
        optimizer=OptimizerConfig(kind=args.optimizer, learning_rate=args.lr,
                                  warmup_steps=min(20, args.steps // 10),
                                  decay_steps=args.steps),
        steps=args.steps, checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt_dir, seed=args.seed,
        compact_grads=args.compact_grads and not args.dense)

    key = jax.random.PRNGKey(args.seed)
    state, plan = make_train_state(tc, key)
    if not args.dense:
        from repro.core import selected_fraction
        print(f"[train] DGSU plan: trainable steps/segment={plan.seg_trainable} "
              f"ratio={args.update_ratio} -> "
              f"{100*selected_fraction(plan, cfg):.2f}% of params per iter")
    step_raw = make_train_step(tc, plan, donate=True)
    step_fn = jax.jit(step_raw, donate_argnums=step_raw.donate_argnums)
    to_device = lambda batch: {k: jnp.asarray(v) for k, v in batch.items()}

    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        latest = mgr.latest_step()
        if latest is not None:
            state, meta = mgr.restore(latest, target=state)
            start = int(meta["step"])
            print(f"[train] resumed from step {start}")

    data = lm_batches(shape.global_batch, shape.seq_len, cfg.vocab_size,
                      seed=args.seed, start_step=start)
    first = next(data)
    data = itertools.chain([first], data)
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, to_device(first)).compile()
    compile_s = time.perf_counter() - t0
    kernel_sites = kernel_launch_count(compiled.as_text())
    sel_leaves = sum(
        len(jax.tree.leaves(plan.spec[seg],
                            is_leaf=lambda x: isinstance(x, SelSpec)))
        for seg, k in plan.seg_trainable.items() if k) \
        if tc.compact_grads else 0
    print(f"[train] step compiled in {compile_s:.1f}s on "
          f"{jax.default_backend()}: {kernel_sites} Pallas kernel sites, "
          f"{sel_leaves} compact selectable leaves", flush=True)
    monitor = StragglerMonitor(
        on_straggler=lambda s, d, m: print(
            f"[straggler] step {s}: {d*1e3:.0f}ms vs median {m*1e3:.0f}ms"))

    def on_metrics(step, metrics):
        if step % args.log_every == 0 or step == args.steps:
            print(f"[train] step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"ce={float(metrics['ce']):.4f}", flush=True)

    losses, step_s = [], []

    def wrapped_step(state, batch):
        t0 = time.perf_counter()
        state, metrics = compiled(state, to_device(batch))
        losses.append(float(metrics["loss"]))     # waits for the step
        step_s.append(time.perf_counter() - t0)
        return state, metrics

    if mgr is not None:
        loop = RestartableLoop(mgr, state, args.steps,
                               checkpoint_every=args.ckpt_every,
                               straggler=monitor)
        result = loop.run(wrapped_step, data, start_step=start,
                          on_metrics=on_metrics)
        print(f"[train] done at step {result['step']}; "
              f"stragglers={len(result['stragglers'])} "
              f"emergency={result['emergency']}")
    else:
        for step, batch in zip(range(start, args.steps), data):
            state, metrics = wrapped_step(state, batch)
            monitor.record(step_s[-1])
            on_metrics(step + 1, metrics)
        print("[train] done")
    return {"losses": losses, "step_s": step_s, "compile_s": compile_s,
            "kernel_sites": kernel_sites, "sel_leaves": sel_leaves}


if __name__ == "__main__":
    main()
