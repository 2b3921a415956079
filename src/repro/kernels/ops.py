"""Jit'd wrappers integrating the Pallas kernels with the framework.

On a TPU backend every call compiles to Mosaic. Off TPU (the CPU test
suite) the kernels run in interpret mode — the Pallas body executes as it
would be staged for TPU, validating index maps and block logic, but not
the chip's tiling rules: `check_tpu_tiling` enforces those up front.

Every logical op here is ONE `pallas_call`: the dW, writeback, and fused
optimizer kernels take the whole stacked leaf (all trainable scan-steps and
all TP shards) in a single grid launch — the lowered compact train step
contains a constant number of kernel launches per selectable weight leaf
(verified by `launch.hlo_analysis.kernel_launch_count`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from typing import Optional

from repro.kernels.batched_dw import (batched_dw_kernel,
                                      batched_dw_pipelined_kernel)
from repro.kernels.block_act_prune import block_act_prune_kernel
from repro.kernels.masked_dw import (block_sparse_dw_kernel,
                                     block_sparse_dw_pipelined_kernel)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Mosaic takes a block whose last dim is a multiple of the vreg's 128 lanes
# or the whole array dim
LANES = 128


def check_tpu_tiling(leaf: str, w_shape, spec) -> None:
    """On a TPU backend, raise a readable error for a selectable leaf whose
    compact-path kernels Mosaic would refuse: the channel block is the lane
    dim of every dW / optimizer / writeback block, and the fan-in tile is
    the lane dim of the dW kernels' activation block."""
    if jax.default_backend() != "tpu":
        return
    k, n = w_shape[-2], w_shape[-1]
    if spec.block % LANES:
        raise ValueError(
            f"leaf {leaf!r} (out width {n}): channel block {spec.block} is "
            f"not a multiple of the TPU's {LANES} lanes; use a channel block "
            f"of {LANES} (a width with no such divisor cannot take the "
            f"compact-path kernels)")
    tk = _pick_tile(k, LANES)
    if tk != k and tk % LANES:
        raise ValueError(
            f"leaf {leaf!r}: fan-in {k} has no {LANES}-lane tile (picked "
            f"{tk}); the dW kernels need a fan-in that is a multiple of "
            f"{LANES} or at most {LANES}")


def _pick_tile(r: int, cap: int = 256) -> int:
    """Largest divisor of r that is <= cap (grid tile along a lead dim)."""
    for d in range(min(r, cap), 0, -1):
        if r % d == 0:
            return d
    return 1


# Budget for choosing the double-buffered dW variants: once a whole
# contraction stripe ([M, TK] activations + [M, block] dY, the worst case
# the automatic pallas pipeline may keep resident while it revisits M
# tiles) exceeds this, route through the `emit_pipeline` kernels whose VMEM
# footprint is two in-flight tiles per operand + the accumulator no matter
# how long the contraction is. ~half of a v4 core's 16 MiB VMEM.
VMEM_STRIPE_BUDGET_BYTES = 8 * 1024 * 1024


def _use_pipelined(m: int, tk: int, block: int, itemsize: int,
                   pipelined: Optional[bool]) -> bool:
    if pipelined is not None:
        return pipelined
    return m * (tk + block) * itemsize > VMEM_STRIPE_BUDGET_BYTES


def block_sparse_dw(x2, dy2, idx, spec, pipelined: Optional[bool] = None):
    """compact_dw kernel entry (see core.sparse_update.compact_dw).

    x2: [M, K], dy2: [M, N], idx: [n_shards, n_sel] ->
    [K, n_shards, n_sel, block] fp32, in ONE launch for all shards.
    pipelined: force the double-buffered variant (None = auto by VMEM
    stripe residency).
    """
    m, k = x2.shape
    tm, tk = _pick_tile(m, 128), _pick_tile(k, 128)
    kern = block_sparse_dw_pipelined_kernel if _use_pipelined(
        m, tk, spec.block, x2.dtype.itemsize, pipelined) \
        else block_sparse_dw_kernel
    return kern(x2, dy2, idx, block=spec.block, tm=tm, tk=tk,
                interpret=_interpret())


def block_sparse_dw_batched(x3, dy3, idx, spec, pipelined: Optional[bool] = None):
    """Expert-batched compact dW (see core.sparse_update.compact_dw_batched).

    x3: [E, C, K], dy3: [E, C, N], idx: [n_shards, n_sel] ->
    [E, K, n_shards, n_sel, block] fp32, in ONE launch for all experts and
    shards (the MoE expert leaf's whole backward is a single kernel)."""
    e, m, k = x3.shape
    tm, tk = _pick_tile(m, 128), _pick_tile(k, 128)
    kern = batched_dw_pipelined_kernel if _use_pipelined(
        m, tk, spec.block, x3.dtype.itemsize, pipelined) \
        else batched_dw_kernel
    return kern(x3, dy3, idx, block=spec.block, tm=tm, tk=tk,
                interpret=_interpret())


def block_scatter_update(w, vals, idx, spec):
    """Compact-path weight writeback (see core.sparse_update): overwrite the
    selected blocks of a stacked leaf with their updated values, in ONE
    aliased launch over (K, n_shards, n_sel, rows).

    w:    [K, *lead, N]                 (N = n_shards * n_blocks * block)
    vals: [K, *lead, n_shards, n_sel, block]
    idx:  [K, n_shards, n_sel]

    Stacked EXPERT leaves ride the same launch: an MoE weight
    [K, E, d, N] flattens its (E, d) lead dims into the kernel's row
    dimension R — the block rule is elementwise per row, so expert
    boundaries need no grid dimension of their own and the writeback stays
    one launch regardless of n_experts.
    """
    from repro.kernels.scatter_blocks import block_scatter_update_kernel

    k = w.shape[0]
    n = w.shape[-1]
    r = 1
    for d in w.shape[1:-1]:
        r *= d
    w3 = w.reshape(k, r, n)
    v5 = vals.reshape(k, r, spec.n_shards, spec.n_sel, spec.block)
    out = block_scatter_update_kernel(w3, v5, idx, tr=_pick_tile(r),
                                      interpret=_interpret())
    return out.reshape(w.shape)


def fused_block_optimizer(oc, p, g_sel, idx, spec, mu, nu, lr, t):
    """`optim.apply_updates_mixed`'s selectable-leaf rule as ONE in-place
    kernel: gather + SGD/momentum/AdamW block rule + writeback fused, with
    the optimizer-state blocks updated in the same pass.

    p: [K, *lead, N]; g_sel: [K, *lead, n_shards, n_sel, block];
    idx: [K, n_shards, n_sel]; mu/nu: fp32 like p or None.
    Returns (p', mu', nu') with None for absent state.

    Stacked EXPERT leaves ([K, E, d, N] with compact grads
    [K, E, d, n_shards, n_sel, block]) flatten (E, d) into the row
    dimension like `block_scatter_update` — the optimizer stays one launch
    per leaf independent of n_experts.
    """
    from repro.kernels.fused_block_opt import fused_block_opt_kernel

    kind = "adamw" if nu is not None else \
        ("momentum" if mu is not None else "sgd")
    k = p.shape[0]
    n = p.shape[-1]
    r = 1
    for d in p.shape[1:-1]:
        r *= d
    p3 = p.reshape(k, r, n)
    g5 = g_sel.reshape(k, r, spec.n_shards, spec.n_sel, spec.block)
    mu3 = mu.reshape(k, r, n) if mu is not None else None
    nu3 = nu.reshape(k, r, n) if nu is not None else None
    w_new, mu_new, nu_new = fused_block_opt_kernel(
        p3, g5, idx, lr, t, mu3, nu3, kind=kind, momentum=oc.momentum,
        beta1=oc.beta1, beta2=oc.beta2, eps=oc.eps,
        weight_decay=oc.weight_decay, tr=_pick_tile(r),
        interpret=_interpret())
    return (w_new.reshape(p.shape),
            mu_new.reshape(p.shape) if mu_new is not None else None,
            nu_new.reshape(p.shape) if nu_new is not None else None)


def block_act_prune(x, threshold: float = 0.15, block: int = 2):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    tr = _pick_tile(x2.shape[0])
    c = shape[-1]
    tc = c if c < 512 else max(d for d in (512, 256, 128, 64) if c % d == 0)
    out = block_act_prune_kernel(x2, threshold=threshold, block=block,
                                 tr=tr, tc=tc, interpret=_interpret())
    return out.reshape(shape)
