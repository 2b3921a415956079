"""Pallas TPU kernel: block-sparse weight-gradient matmul, single launch.

The paper's core compute saving — dW is computed ONLY for selected output-
channel blocks. The selected block indices are scalar-prefetched so the
BlockSpec index_map routes each grid step directly to its selected dY
column block; unselected blocks are never read, computed, or written
(compute AND HBM traffic skipped by construction — the TPU-native analogue
of the paper's skipped gradient loops).

ONE `pallas_call` covers every TP shard: the grid spans shards as well as
selected blocks, and the scalar-prefetched [n_shards, n_sel] index table
routes the dY BlockSpec to `shard_base + idx[s, j]`. No Python shard loop.

    x:   [M, K]            activations (fan-in K)
    dy:  [M, N]            upstream gradient (N = n_shards * n_blocks * block)
    idx: [n_shards, n_sel] selected block indices, local to each shard
    out: [K, n_shards, n_sel, block]   compact dW (fp32)

The kernel writes the row-major flat view [K, n_shards * n_sel * block] of
that layout with a (TK, block) out block at column block `s * n_sel + j`,
so both trailing block dims are tiles the TPU compiler accepts (multiples
of (8, 128)); the reshape back to the compact layout is free.

Grid: (n_shards, n_sel, K/TK, M/TM); M is the contraction ("arbitrary")
innermost dimension, accumulated into a VMEM scratch across grid steps.
MXU alignment: block and TK should be multiples of 128 on real hardware
(full configs use channel_block=128); interpret-mode tests sweep smaller
shapes against the ref.py oracle.

`block_sparse_dw_pipelined_kernel` is the double-buffered variant (ROADMAP
Kernels open item): x and dy stay in HBM (`memory_space=ANY`) and a
`pltpu.emit_pipeline` inner grid streams the M tiles through VMEM with
explicit double buffering — VMEM residency is two tiles per operand plus
the [TK, block] accumulator no matter how large M grows. `kernels.ops`
selects it when a whole contraction stripe stops fitting VMEM.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, x_ref, dy_ref, out_ref, acc_ref, *, n_m: int):
    mi = pl.program_id(3)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)      # [TM, TK]
    dy = dy_ref[...].astype(jnp.float32)    # [TM, block]
    acc_ref[...] += jax.lax.dot_general(
        x, dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [TK, block]

    @pl.when(mi == n_m - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def block_sparse_dw_kernel(x, dy, idx, *, block: int, tm: int = 128,
                           tk: int = 128, interpret: bool = False):
    """Compact dW: [K, n_shards, n_sel, block] fp32, one launch for all
    shards. idx: [n_shards, n_sel]. Shapes must divide tiles."""
    m, k = x.shape
    n = dy.shape[1]
    n_shards, n_sel = idx.shape
    tm = min(tm, m)
    tk = min(tk, k)
    assert m % tm == 0 and k % tk == 0 and n % (n_shards * block) == 0
    n_blocks = n // (n_shards * block)   # blocks per shard
    n_m = m // tm

    grid = (n_shards, n_sel, k // tk, n_m)
    out = pl.pallas_call(
        functools.partial(_kernel, n_m=n_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda si, ji, ki, mi, idx_ref: (mi, ki)),
                pl.BlockSpec((tm, block),
                             lambda si, ji, ki, mi, idx_ref:
                             (mi, si * n_blocks + idx_ref[si, ji])),
            ],
            out_specs=pl.BlockSpec(
                (tk, block),
                lambda si, ji, ki, mi, idx_ref: (ki, si * n_sel + ji)),
            scratch_shapes=[pltpu.VMEM((tk, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((k, n_shards * n_sel * block),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="masked_dw",
    )(idx, x, dy)
    return out.reshape(k, n_shards, n_sel, block)


@contextlib.contextmanager
def interpret_pipeline(interpret: bool):
    """Let `pltpu.emit_pipeline` trace in interpret mode off-TPU.

    The pipeline helper sizes its DMA-slice tiling from the device's TPU
    generation, which a CPU device does not have. That tiling only truncates
    out-of-bounds edge blocks, and these kernels require tile-divisible
    shapes, so an interpret-mode trace pins a v4-class answer. Compiled
    calls (interpret=False) keep the real lookup."""
    if not interpret:
        yield
        return
    from jax._src.pallas.mosaic import pipeline
    real = pipeline._get_tpu_generation
    pipeline._get_tpu_generation = lambda: 4
    try:
        yield
    finally:
        pipeline._get_tpu_generation = real


def _pipelined_kernel(idx_ref, x_hbm, dy_hbm, out_ref, acc_ref, *,
                      tm: int, tk: int, block: int, n_m: int, n_blocks: int):
    si = pl.program_id(0)
    ji = pl.program_id(1)
    ki = pl.program_id(2)
    blk_idx = si * n_blocks + idx_ref[si, ji]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(x_ref, dy_ref):
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), dy_ref[...].astype(jnp.float32),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    pltpu.emit_pipeline(
        body,
        grid=(n_m,),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda mi: (mi, ki)),
            pl.BlockSpec((tm, block), lambda mi: (mi, blk_idx)),
        ],
        out_specs=(),
    )(x_hbm, dy_hbm)
    out_ref[...] = acc_ref[...]


def block_sparse_dw_pipelined_kernel(x, dy, idx, *, block: int, tm: int = 128,
                                     tk: int = 128, interpret: bool = False):
    """Double-buffered `block_sparse_dw_kernel`: same contract, but x/dy
    live in HBM and an inner `emit_pipeline` streams the M tiles."""
    m, k = x.shape
    n = dy.shape[1]
    n_shards, n_sel = idx.shape
    tm = min(tm, m)
    tk = min(tk, k)
    assert m % tm == 0 and k % tk == 0 and n % (n_shards * block) == 0
    n_blocks = n // (n_shards * block)
    n_m = m // tm

    grid = (n_shards, n_sel, k // tk)
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
                    (tk, block),
                    lambda si, ji, ki, idx_ref: (ki, si * n_sel + ji)),
                scratch_shapes=[pltpu.VMEM((tk, block), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((k, n_shards * n_sel * block),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=interpret,
            name="masked_dw_pipelined",
        )(idx, x, dy)
    return out.reshape(k, n_shards, n_sel, block)
