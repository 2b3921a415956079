"""Pallas TPU kernel: in-place block scatter-update for the compact path.

The compact-gradient train step updates only the selected output-channel
blocks of each weight; this kernel writes those updated blocks back into
the full weight WITHOUT sweeping (or even reading) the unselected columns.
The weight input is aliased to the output (`input_output_aliases`), so on
TPU the update is a true in-place write touching n_sel/n_blocks of the
tensor — HBM traffic proportional to the selection ratio, the memory-side
twin of `masked_dw`'s compute skip.

ONE `pallas_call` covers the whole stacked leaf: the grid spans the K
trainable scan-steps AND the TP shards (PR 1 launched K x n_shards
separate 2D kernels from a Python loop), with the scalar-prefetched
[K, n_shards, n_sel] index table routing each grid step's output block to
`shard_base + idx[k, s, j]`.

    w:   [K, R, N]                     stacked weight, R = flattened non-out
                                       dims, N = n_shards * n_blocks * block
    upd: [K, R, n_shards, n_sel, block]  updated values for selected blocks
    idx: [K, n_shards, n_sel]          selected block indices, shard-local
    out: [K, R, N]                     w with the selected blocks overwritten

The kernel reads `upd` through its row-major flat view
[K, R, n_shards * n_sel * block], so each (TR, block) tile sits at column
block `s * n_sel + j` and both trailing block dims are tiles the TPU
compiler accepts.

Grid: (K, n_shards, n_sel, R/TR). If idx contains duplicates within a
(k, shard) the highest grid step wins (the sel dims are "arbitrary", i.e.
sequential) — selection never produces duplicates within a shard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, w_ref, upd_ref, out_ref):
    del idx_ref, w_ref
    out_ref[...] = upd_ref[...].astype(out_ref.dtype)


def block_scatter_update_kernel(w, upd, idx, *, tr: int = 256,
                                interpret: bool = False):
    """out = w with blocks idx overwritten by upd. Shapes as module doc."""
    k, r, n = w.shape
    n_shards, n_sel = idx.shape[1], idx.shape[2]
    block = upd.shape[-1]
    assert upd.shape == (k, r, n_shards, n_sel, block)
    assert idx.shape == (k, n_shards, n_sel)
    assert n % (n_shards * block) == 0
    n_blocks = n // (n_shards * block)   # blocks per shard
    tr = min(tr, r)
    assert r % tr == 0

    grid = (k, n_shards, n_sel, r // tr)
    out_spec = pl.BlockSpec(
        (1, tr, block),
        lambda kk, si, ji, ri, idx_ref:
        (kk, ri, si * n_blocks + idx_ref[kk, si, ji]))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                out_spec,
                pl.BlockSpec((1, tr, block),
                             lambda kk, si, ji, ri, idx_ref:
                             (kk, ri, si * n_sel + ji)),
            ],
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((k, r, n), w.dtype),
        input_output_aliases={1: 0},   # w aliases out: unselected blocks kept
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary",
                                 "parallel")),
        interpret=interpret,
        name="scatter_blocks",
    )(idx, w, upd.reshape(k, r, n_shards * n_sel * block))
