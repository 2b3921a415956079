"""Kernel microbenchmarks: jnp oracle vs Pallas(interpret) wall time on CPU
(correctness-path timing only — TPU timing requires hardware), the
compute-skip ratio the block-sparse dW kernel achieves by construction, the
fused single-launch kernels vs the PR 1 per-shard / per-(K, shard)
loop-of-launches baselines (wall time AND static launch-site counts), and a
dense-scatter vs compact-gradient train-step comparison (step time and
compiler-reported peak temp memory).

Besides the CSV rows, `run()` fills the module-level RECORDS list with
machine-readable dicts (op, variant, shape, ratio, us, launches); kernel
records additionally carry roofline context (`kernel_roofline`: flops,
bytes, arith_intensity, bound) so each BENCH_kernels.json row shows which
side of the TPU v5e ridge point the op sits on next to its launch count.
`benchmarks.run` dumps them to BENCH_kernels.json so the perf trajectory is
tracked across PRs."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.batched_dw import batched_dw_kernel
from repro.kernels.masked_dw import (block_sparse_dw_kernel,
                                     block_sparse_dw_pipelined_kernel)
from repro.kernels.scatter_blocks import block_scatter_update_kernel
from repro.launch.hlo_analysis import kernel_launch_count

RECORDS: list[dict] = []      # machine-readable output (BENCH_kernels.json)
BENCH_JSON = "BENCH_kernels.json"

RIDGE = 197e12 / 819e9   # TPU v5e bf16 FLOP/s over HBM bytes/s


def kernel_roofline(flops: float, bytes_: float) -> dict:
    """Classify one kernel by arithmetic intensity against the TPU v5e
    ridge point: below it the kernel is memory-bound, above it
    compute-bound."""
    ai = float(flops) / max(float(bytes_), 1.0)
    return {
        "flops": float(flops),
        "bytes": float(bytes_),
        "arith_intensity": ai,
        "ridge_flops_per_byte": RIDGE,
        "bound": "compute" if ai >= RIDGE else "memory",
    }


def _time(fn, *args, n=5):
    """Mean wall time per call in µs; one untimed warmup call first."""
    jax.block_until_ready(fn(*args))          # warmup: compile + first run
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def _launches(fn, *args) -> int:
    return kernel_launch_count(jax.make_jaxpr(fn)(*args))


def run() -> list[tuple]:
    RECORDS.clear()
    rows = []
    m, k, n, block = 512, 256, 512, 64
    x = jnp.asarray(np.random.default_rng(0).normal(size=(m, k)), jnp.float32)
    dy = jnp.asarray(np.random.default_rng(1).normal(size=(m, n)), jnp.float32)
    for ratio in (0.125, 0.25, 0.5, 1.0):
        n_sel = max(1, int(n // block * ratio))
        idx = jnp.arange(n_sel, dtype=jnp.int32)[None]      # [1 shard, n_sel]
        jr = jax.jit(lambda x, dy, idx: ref.block_sparse_dw_ref(x, dy, idx, block))
        t_ref = _time(jr, x, dy, idx)
        flops_skip = 1.0 - n_sel / (n // block)
        rows.append((f"kernel/masked_dw_r{ratio}", t_ref,
                     f"jnp_oracle;compute_skipped={flops_skip:.0%}"))
        sel = n_sel * block
        RECORDS.append({"op": "masked_dw", "variant": "jnp_oracle",
                        "shape": f"m{m}k{k}n{n}b{block}", "ratio": ratio,
                        "us": t_ref, "launches": 0,
                        **kernel_roofline(2.0 * m * k * sel,
                                          4.0 * (m * k + m * sel + k * sel))})
    # dense dW for comparison
    jd = jax.jit(lambda x, dy: jnp.einsum("mk,mn->kn", x, dy))
    rows.append(("kernel/dense_dw", _time(jd, x, dy), "baseline"))
    rows += fusion_comparison()
    rows += batched_dw_comparison()
    rows += train_step_comparison()
    return rows


def batched_dw_comparison() -> list[tuple]:
    """MoE expert-batched compact dW: the single-launch `batched_dw` kernel
    over rows grouped by expert (grid spans shards x selected blocks x
    each expert's row tiles) vs a loop of one launch per expert. Same eager-dispatch timing discipline as
    `fusion_comparison` (each un-jitted pallas_call pays a full dispatch —
    the cost the batching removes); launch-site counts are exact on any
    backend."""
    rows = []
    rng = np.random.default_rng(5)
    e, m, k, s, nb, blk = 4, 64, 64, 2, 8, 16
    n_sel = 2                                   # ratio 0.25
    n = s * nb * blk
    x = jnp.asarray(rng.normal(size=(e * m, k)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(e * m, n)), jnp.float32)
    sizes = jnp.full((e,), m, jnp.int32)
    idx = jnp.asarray(
        np.stack([rng.choice(nb, n_sel, replace=False) for _ in range(s)]),
        jnp.int32)

    def dw_batched(x, dy, idx):
        return batched_dw_kernel(x, dy, sizes, idx, block=blk, tm=m, tk=k,
                                 interpret=True)

    def dw_per_expert_loop(x, dy, idx):         # one launch per expert
        outs = [block_sparse_dw_kernel(x[ei * m:(ei + 1) * m],
                                       dy[ei * m:(ei + 1) * m], idx,
                                       block=blk, tm=m, tk=k, interpret=True)
                for ei in range(e)]
        return jnp.stack(outs)

    shape = f"e{e}m{m}k{k}s{s}nb{nb}b{blk}"
    sel = s * n_sel * blk
    rl = kernel_roofline(2.0 * e * m * k * sel,
                         4.0 * e * (m * k + m * sel + k * sel))
    for variant, fn in (("fused", dw_batched),
                        ("per_expert_loop", dw_per_expert_loop)):
        us = _time(fn, x, dy, idx, n=3)          # eager: dispatch per launch
        launches = _launches(fn, x, dy, idx)
        rows.append((f"kernel/batched_dw_{variant}", us,
                     f"launches={launches};eager_dispatch"))
        RECORDS.append({"op": "batched_dw", "variant": variant,
                        "shape": shape, "ratio": n_sel / nb, "us": us,
                        "launches": launches, "timing": "eager_dispatch",
                        **rl})

    def dw_pipelined(x2, dy2, idx):
        return block_sparse_dw_pipelined_kernel(x2, dy2, idx, block=blk,
                                                tm=32, tk=k, interpret=True)

    us = _time(dw_pipelined, x[:m], dy[:m], idx, n=3)
    launches = _launches(dw_pipelined, x[:m], dy[:m], idx)
    rows.append(("kernel/dw_pipelined", us,
                 f"launches={launches};eager_dispatch"))
    RECORDS.append({"op": "masked_dw", "variant": "pipelined",
                    "shape": f"m{m}k{k}s{s}nb{nb}b{blk}",
                    "ratio": n_sel / nb, "us": us, "launches": launches,
                    "timing": "eager_dispatch",
                    **kernel_roofline(2.0 * m * k * sel,
                                      4.0 * (m * k + m * sel + k * sel))})
    return rows


def fusion_comparison() -> list[tuple]:
    """Fused single-launch kernels vs the PR 1 loop-of-launches baselines.

    dW and writeback are timed EAGERLY: each un-jitted pallas_call pays a
    full dispatch — the CPU-interpret analogue of kernel-launch overhead,
    which is exactly the cost the fusion removes (under jit, interpret mode
    carries every output buffer through its grid loop, an emulation
    artifact that anti-correlates with launch count). The fused optimizer
    is timed jitted vs the jitted jnp gather->rule->scatter path it
    replaces. Launch-site counts are backend-independent."""
    rows = []
    rng = np.random.default_rng(2)
    m, k, s, nb, blk = 128, 64, 4, 8, 16
    n_sel = 2                                   # ratio 0.25
    n = s * nb * blk
    loc = nb * blk
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    idx = jnp.asarray(
        np.stack([rng.choice(nb, n_sel, replace=False) for _ in range(s)]),
        jnp.int32)

    def dw_fused(x, dy, idx):
        return block_sparse_dw_kernel(x, dy, idx, block=blk, tm=m, tk=k,
                                      interpret=True)

    def dw_loop(x, dy, idx):                    # PR 1: one launch per shard
        outs = [block_sparse_dw_kernel(x, dy[:, si * loc:(si + 1) * loc],
                                       idx[si:si + 1], block=blk, tm=m, tk=k,
                                       interpret=True)
                for si in range(s)]
        return jnp.concatenate(outs, axis=1)

    shape = f"m{m}k{k}s{s}nb{nb}b{blk}"
    sel = s * n_sel * blk
    rl = kernel_roofline(2.0 * m * k * sel,
                         4.0 * (m * k + m * sel + k * sel))
    for variant, fn in (("fused", dw_fused), ("per_shard_loop", dw_loop)):
        us = _time(fn, x, dy, idx, n=3)          # eager: dispatch per launch
        launches = _launches(fn, x, dy, idx)
        rows.append((f"kernel/dw_{variant}", us,
                     f"launches={launches};eager_dispatch"))
        RECORDS.append({"op": "masked_dw", "variant": variant, "shape": shape,
                        "ratio": n_sel / nb, "us": us, "launches": launches,
                        "timing": "eager_dispatch", **rl})

    k_steps, r = 3, 64
    w = jnp.asarray(rng.normal(size=(k_steps, r, n)), jnp.float32)
    upd = jnp.asarray(rng.normal(size=(k_steps, r, s, n_sel, blk)),
                      jnp.float32)
    idx2 = jnp.asarray(
        np.stack([[rng.choice(nb, n_sel, replace=False) for _ in range(s)]
                  for _ in range(k_steps)]), jnp.int32)

    def sc_fused(w, upd, idx2):
        return block_scatter_update_kernel(w, upd, idx2, tr=r, interpret=True)

    def sc_loop(w, upd, idx2):        # PR 1: one launch per (K, shard)
        outs = []
        for kk in range(k_steps):
            shards = [block_scatter_update_kernel(
                w[kk:kk + 1, :, si * loc:(si + 1) * loc],
                upd[kk:kk + 1, :, si:si + 1], idx2[kk:kk + 1, si:si + 1],
                tr=r, interpret=True) for si in range(s)]
            outs.append(jnp.concatenate(shards, axis=2))
        return jnp.concatenate(outs, axis=0)

    shape = f"K{k_steps}r{r}s{s}nb{nb}b{blk}"
    elems = k_steps * r * s * n_sel * blk        # touched weight elements
    rl = kernel_roofline(1.0 * elems, 4.0 * 3 * elems)
    for variant, fn in (("fused", sc_fused), ("per_k_shard_loop", sc_loop)):
        us = _time(fn, w, upd, idx2, n=3)        # eager: dispatch per launch
        launches = _launches(fn, w, upd, idx2)
        rows.append((f"kernel/writeback_{variant}", us,
                     f"launches={launches};eager_dispatch"))
        RECORDS.append({"op": "block_scatter_update", "variant": variant,
                        "shape": shape, "ratio": n_sel / nb, "us": us,
                        "launches": launches, "timing": "eager_dispatch",
                        **rl})

    # fused optimizer: one in-place launch vs jnp gather -> rule -> scatter
    from functools import partial

    from repro.kernels.fused_block_opt import fused_block_opt_kernel
    g = jnp.asarray(rng.normal(size=(k_steps, r, s, n_sel, blk)), jnp.float32)
    mu = jnp.zeros((k_steps, r, n), jnp.float32)
    lr, t = jnp.float32(0.05), jnp.float32(1.0)

    def opt_fused(w, g, idx2, lr, t, mu):
        return fused_block_opt_kernel(w, g, idx2, lr, t, mu, kind="momentum",
                                      momentum=0.9, tr=r, interpret=True)

    opt_jnp = jax.jit(partial(ref.fused_block_opt_ref, kind="momentum",
                              momentum=0.9))
    rl = kernel_roofline(4.0 * elems, 4.0 * 5 * elems)  # mu+w rmw per elem
    for variant, fn, jfn in (("fused", opt_fused, jax.jit(opt_fused)),
                             ("gather_jnp_scatter", None, opt_jnp)):
        us = _time(jfn, w, g, idx2, lr, t, mu)
        launches = _launches(fn, w, g, idx2, lr, t, mu) if fn else 0
        rows.append((f"kernel/block_opt_{variant}", us,
                     f"launches={launches}"))
        RECORDS.append({"op": "fused_block_opt", "variant": variant,
                        "shape": shape, "ratio": n_sel / nb, "us": us,
                        "launches": launches, "timing": "jit", **rl})
    return rows


def train_step_comparison() -> list[tuple]:
    """Dense-scatter vs compact-gradient jitted train step on the llama3
    smoke config: per-step wall time plus the compiler's temp-allocation
    estimate (the buffer class holding gradient scratch), and the static
    kernel-launch-site count of the kernels-enabled compact step (constant
    in the trainable-layer count K — the fused-path guarantee)."""
    from repro.configs import (OptimizerConfig, ShapeConfig,
                               SparseUpdateConfig, TrainConfig,
                               get_smoke_config)
    from repro.core.sparse_update import use_kernels
    from repro.train import make_train_state, make_train_step

    cfg = get_smoke_config("llama3-8b")
    shape = ShapeConfig("bench", 64, 8, "train")
    tc = TrainConfig(
        model=cfg, shape=shape,
        sparse=SparseUpdateConfig(update_ratio=0.25, num_update_layers=2,
                                  channel_block=8),
        optimizer=OptimizerConfig(kind="momentum", momentum=0.9,
                                  learning_rate=0.05))
    state, plan = make_train_state(tc, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (shape.global_batch, shape.seq_len),
                                          0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(2),
                                          (shape.global_batch, shape.seq_len),
                                          0, cfg.vocab_size)}
    rows = []
    for label, compact in (("dense_scatter", False), ("compact", True)):
        step = jax.jit(make_train_step(tc, plan, compact_grads=compact))
        # compile once (AOT) and run the compiled executable directly
        compiled = step.lower(state, batch).compile()
        try:
            mem = compiled.memory_analysis()
            temp = int(getattr(mem, "temp_size_in_bytes", 0))
        except Exception:
            temp = 0
        s, m = compiled(state, batch)      # warm up
        jax.block_until_ready(jax.tree.leaves(s))
        t0 = time.perf_counter()
        n = 5
        for _ in range(n):
            s, m = compiled(s, batch)
        jax.block_until_ready(jax.tree.leaves(s))
        us = (time.perf_counter() - t0) / n * 1e6
        rows.append((f"train_step/{label}", us,
                     f"temp_bytes={temp};loss={float(m['loss']):.4f}"))
        RECORDS.append({"op": "train_step", "variant": label, "shape": "llama3-smoke",
                        "ratio": 0.25, "us": us, "launches": 0,
                        "temp_bytes": temp})
    step_k = make_train_step(tc, plan, compact_grads=True)
    with use_kernels(True):
        launches = kernel_launch_count(jax.make_jaxpr(step_k)(state, batch))
    rows.append(("train_step/compact_kernels_launch_sites", launches,
                 "constant_per_selectable_leaf"))
    RECORDS.append({"op": "train_step", "variant": "compact_kernels",
                    "shape": "llama3-smoke", "ratio": 0.25, "us": 0.0,
                    "launches": launches})
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
