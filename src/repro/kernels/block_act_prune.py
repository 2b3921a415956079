"""Pallas TPU kernel: ZeBRA block activation pruning (paper §III-A.2).

Zero every `block`-wide channel run whose max |x| falls below the
threshold. Tiled elementwise kernel — one VMEM tile in, one out; the
block max is computed in-register (no extra HBM traffic).

    x: [R, C] -> same shape, sub-threshold blocks zeroed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref, *, block: int, threshold: float):
    x = x_ref[...]
    tr, tc = x.shape
    xb = x.reshape(tr, tc // block, block)
    keep = (jnp.abs(xb).max(axis=-1, keepdims=True) >= threshold)
    o_ref[...] = (xb * keep.astype(x.dtype)).reshape(tr, tc)


def block_act_prune_kernel(x, *, threshold: float = 0.15, block: int = 2,
                           tr: int = 256, tc: int = 512,
                           interpret: bool = False):
    r, c = x.shape
    tr = min(tr, r)
    tc = min(tc, c)
    assert r % tr == 0 and c % tc == 0 and tc % block == 0
    return pl.pallas_call(
        functools.partial(_kernel, block=block, threshold=threshold),
        grid=(r // tr, c // tc),
        in_specs=[pl.BlockSpec((tr, tc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        interpret=interpret,
        name="block_act_prune",
    )(x)
