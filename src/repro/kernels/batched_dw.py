"""Pallas TPU kernel: block-sparse weight gradient of grouped expert rows.

The MoE expert analogue of `masked_dw`: dW is computed ONLY for the
selected output-channel blocks, for EVERY expert of a stacked expert leaf,
in ONE `pallas_call`. The expert layer's dropless dispatch sorts its rows
by expert, so expert e owns the rows [offsets[e], offsets[e + 1]) of x and
dY; rows past the last group belong to no expert and are never read.

    x:           [M, K]          rows sorted by expert
    dy:          [M, N]          upstream gradient (N = n_shards * n_blocks
                                 * block)
    group_sizes: [E]             rows per expert, in order
    idx:         [n_shards, n_sel] selected block indices, local to each
                                 shard (shared by all experts)
    out:         [E, K, n_shards, n_sel, block]   compact dW (fp32)

The row offsets and a visit list are scalar-prefetched: visit v works on
row tile `tile_ids[v]` for expert `group_ids[v]` and masks the tile's rows
outside that expert's range. A tile that two experts share is visited once
by each; an expert with no rows gets one visit, which writes its zeros.
The grid's last dimension is the number of visits, a traced value, so the
tiles past the live rows are never visited: the work scales with the rows
routed here, not with the dispatch's static bound.

Grid: (n_shards, n_sel, K/TK, visits); the visits are the contraction
("arbitrary") dimension, accumulated into a VMEM scratch that is zeroed at
an expert's first visit and written to its (TK, block) out block at its
last. The kernel writes the flat view [E, K, n_shards * n_sel * block].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def group_visits(group_sizes, m: int, tm: int):
    """(offsets [E+1], group_ids [V], tile_ids [V], visits) for rows in
    groups of `group_sizes` over an [m, ...] operand in row tiles of tm. V
    is the static bound m / tm + 2E - 1; `visits` <= V is how many are
    live: every tile a group's rows touch, and one for each empty group."""
    e = group_sizes.shape[0]
    n_tiles = m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, n_tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tm, first)
    per_group = last - first + 1
    bound = n_tiles + 2 * e - 1
    group_ids = jnp.repeat(jnp.arange(e, dtype=jnp.int32), per_group,
                           total_repeat_length=bound)
    begin = jnp.cumsum(per_group) - per_group
    tile_ids = first[group_ids] + jnp.arange(bound, dtype=jnp.int32) \
        - begin[group_ids]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group_ids, jnp.clip(tile_ids, 0, n_tiles - 1),
            per_group.sum())


def _kernel(off_ref, gid_ref, tid_ref, idx_ref, x_ref, dy_ref, out_ref,
            acc_ref, *, tm: int):
    v = pl.program_id(3)
    last = pl.num_programs(3) - 1
    g = gid_ref[v]

    @pl.when((v == 0) | (gid_ref[jnp.maximum(v - 1, 0)] != g))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = tid_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (rows >= off_ref[g]) & (rows < off_ref[g + 1])
    x = jnp.where(mine, x_ref[...], jnp.zeros_like(x_ref))
    dy = jnp.where(mine, dy_ref[...], jnp.zeros_like(dy_ref))
    acc_ref[...] += jax.lax.dot_general(
        x, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((v == last) | (gid_ref[jnp.minimum(v + 1, last)] != g))
    def _flush():
        out_ref[...] = acc_ref[...]


def batched_dw_kernel(x, dy, group_sizes, idx, *, block: int, tm: int = 512,
                      tk: int = 512, interpret: bool = False):
    """Compact per-expert dW: [E, K, n_shards, n_sel, block] fp32, one
    launch for all experts and shards. M and K must divide their tiles."""
    m, k = x.shape
    n = dy.shape[-1]
    e = group_sizes.shape[0]
    n_shards, n_sel = idx.shape
    tm = min(tm, m)
    tk = min(tk, k)
    assert dy.shape[0] == m
    assert m % tm == 0 and k % tk == 0 and n % (n_shards * block) == 0
    n_blocks = n // (n_shards * block)   # blocks per shard
    offsets, group_ids, tile_ids, visits = group_visits(group_sizes, m, tm)

    grid = (n_shards, n_sel, k // tk, visits)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda si, ji, ki, v, off, gid, tid, idx_ref:
                             (tid[v], ki)),
                pl.BlockSpec((tm, block),
                             lambda si, ji, ki, v, off, gid, tid, idx_ref:
                             (tid[v], si * n_blocks + idx_ref[si, ji])),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, block),
                lambda si, ji, ki, v, off, gid, tid, idx_ref:
                (gid[v], ki, si * n_sel + ji)),
            scratch_shapes=[pltpu.VMEM((tk, block), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n_shards * n_sel * block),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="batched_dw",
    )(offsets, group_ids, tile_ids, idx, x, dy)
    return out.reshape(e, k, n_shards, n_sel, block)
