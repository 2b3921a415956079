"""Pallas TPU kernel: expert-batched block-sparse weight-gradient matmul.

The MoE expert analogue of `masked_dw`: dW is computed ONLY for selected
output-channel blocks, for EVERY expert of a stacked expert leaf, in ONE
`pallas_call`. PR 3 certified the dense-layer compact train step at a
constant launch count per leaf, but the expert path still ran a per-expert
jnp einsum backward (a ROADMAP Kernels open item); this kernel closes it —
the grid spans experts as well as TP shards and selected blocks, and the
scalar-prefetched [n_shards, n_sel] index table routes the dY BlockSpec to
`shard_base + idx[s, j]` exactly as in the 2D kernel (the selection is
shared across experts: the framework selects per weight, not per expert).

    x:   [E, C, K]          per-expert activation buffers (capacity C)
    dy:  [E, C, N]          upstream gradient (N = n_shards * n_blocks * block)
    idx: [n_shards, n_sel]  selected block indices, local to each shard
    out: [E, K, n_shards, n_sel, block]   compact dW (fp32)

As in `masked_dw`, the kernel writes the flat view
[E, K, n_shards * n_sel * block] with a (TK, block) out block per expert,
so the trailing block dims are tiles the TPU compiler accepts.

Grid: (E, n_shards, n_sel, K/TK, C/TM); C is the contraction ("arbitrary")
innermost dimension, accumulated into a VMEM scratch across grid steps.
Unselected blocks are never read, computed, or written.

`batched_dw_pipelined_kernel` is the double-buffered variant (the other
ROADMAP Kernels open item): x and dy stay in HBM (`memory_space=ANY`) and a
`pltpu.emit_pipeline` inner grid streams the C tiles through VMEM with
explicit double buffering, so VMEM residency is bounded by two tiles per
operand plus the [TK, block] accumulator no matter how large C grows —
select it when a whole [C, TK] stripe stops fitting VMEM (`kernels.ops`
holds the policy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.masked_dw import interpret_pipeline


def _kernel(idx_ref, x_ref, dy_ref, out_ref, acc_ref, *, n_m: int):
    mi = pl.program_id(4)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)        # [TM, TK]
    dy = dy_ref[0].astype(jnp.float32)      # [TM, block]
    acc_ref[...] += jax.lax.dot_general(
        x, dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [TK, block]

    @pl.when(mi == n_m - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def batched_dw_kernel(x, dy, idx, *, block: int, tm: int = 128,
                      tk: int = 128, interpret: bool = False):
    """Compact per-expert dW: [E, K, n_shards, n_sel, block] fp32, one
    launch for all experts and shards. idx: [n_shards, n_sel]. C and K must
    divide their tiles."""
    e, m, k = x.shape
    n = dy.shape[-1]
    n_shards, n_sel = idx.shape
    tm = min(tm, m)
    tk = min(tk, k)
    assert dy.shape[:2] == (e, m)
    assert m % tm == 0 and k % tk == 0 and n % (n_shards * block) == 0
    n_blocks = n // (n_shards * block)   # blocks per shard
    n_m = m // tm

    grid = (e, n_shards, n_sel, k // tk, n_m)
    out = pl.pallas_call(
        functools.partial(_kernel, n_m=n_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tm, tk),
                             lambda ei, si, ji, ki, mi, idx_ref:
                             (ei, mi, ki)),
                pl.BlockSpec((1, tm, block),
                             lambda ei, si, ji, ki, mi, idx_ref:
                             (ei, mi, si * n_blocks + idx_ref[si, ji])),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, block),
                lambda ei, si, ji, ki, mi, idx_ref:
                (ei, ki, si * n_sel + ji)),
            scratch_shapes=[pltpu.VMEM((tk, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n_shards * n_sel * block),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
        name="batched_dw",
    )(idx, x, dy)
    return out.reshape(e, k, n_shards, n_sel, block)


def _pipelined_kernel(idx_ref, x_hbm, dy_hbm, out_ref, acc_ref, *,
                      tm: int, tk: int, block: int, n_m: int, n_blocks: int):
    ei = pl.program_id(0)
    si = pl.program_id(1)
    ji = pl.program_id(2)
    ki = pl.program_id(3)
    blk_idx = si * n_blocks + idx_ref[si, ji]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(x_ref, dy_ref):
        acc_ref[...] += jax.lax.dot_general(
            x_ref[0].astype(jnp.float32), dy_ref[0].astype(jnp.float32),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    pltpu.emit_pipeline(
        body,
        grid=(n_m,),
        in_specs=[
            pl.BlockSpec((1, tm, tk), lambda mi: (ei, mi, ki)),
            pl.BlockSpec((1, tm, block), lambda mi: (ei, mi, blk_idx)),
        ],
        out_specs=(),
    )(x_hbm, dy_hbm)
    out_ref[...] = acc_ref[...]


def batched_dw_pipelined_kernel(x, dy, idx, *, block: int, tm: int = 128,
                                tk: int = 128, interpret: bool = False):
    """Double-buffered `batched_dw_kernel`: same contract, but x/dy live in
    HBM and an inner `emit_pipeline` streams the C-tiles — VMEM holds two
    in-flight tiles per operand regardless of C."""
    e, m, k = x.shape
    n = dy.shape[-1]
    n_shards, n_sel = idx.shape
    tm = min(tm, m)
    tk = min(tk, k)
    assert dy.shape[:2] == (e, m)
    assert m % tm == 0 and k % tk == 0 and n % (n_shards * block) == 0
    n_blocks = n // (n_shards * block)
    n_m = m // tm

    grid = (e, n_shards, n_sel, k // tk)
    with interpret_pipeline(interpret):
        out = pl.pallas_call(
            functools.partial(_pipelined_kernel, tm=tm, tk=tk, block=block,
                              n_m=n_m, n_blocks=n_blocks),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                    pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                ],
                out_specs=pl.BlockSpec(
                    (None, tk, block),
                    lambda ei, si, ji, ki, idx_ref:
                    (ei, ki, si * n_sel + ji)),
                scratch_shapes=[pltpu.VMEM((tk, block), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((e, k, n_shards * n_sel * block),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "parallel")),
            interpret=interpret,
            name="batched_dw_pipelined",
        )(idx, x, dy)
    return out.reshape(e, k, n_shards, n_sel, block)
