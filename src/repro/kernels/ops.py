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

from repro.kernels.batched_dw import batched_dw_kernel
from repro.kernels.block_act_prune import block_act_prune_kernel
from repro.kernels.masked_dw import (block_sparse_dw_kernel,
                                     block_sparse_dw_pipelined_kernel)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Mosaic takes a block whose last dim is a multiple of the vreg's 128 lanes
# or the whole array dim
LANES = 128


def lanes() -> int:
    """The lane multiple the kernels' channel blocks are padded to: the
    vreg's 128 lanes when compiling for a TPU; 1 (no padding) in interpret
    mode, whose tests use narrow blocks."""
    return 1 if _interpret() else LANES


def _padded(spec):
    """spec with its channel block rounded up to whole `lanes()`: a 576-wide
    latent projection's block of 96 rides in 128 lanes, zeros past 96."""
    return spec._replace(block=-(-spec.block // lanes()) * lanes())


def _pad_blocks(a, spec, pad):
    """a [..., n_shards * n_blocks * block] (or [..., block] per block of a
    compact layout) -> the same with every block zero-padded to pad.block."""
    if pad.block == spec.block:
        return a
    ab = a.reshape(a.shape[:-1] + (-1, spec.block))
    widths = [(0, 0)] * (ab.ndim - 1) + [(0, pad.block - spec.block)]
    return jnp.pad(ab, widths).reshape(a.shape[:-1] + (-1,))


def _unpad_blocks(a, spec, pad):
    """The inverse of `_pad_blocks`: the first spec.block of every block."""
    if pad.block == spec.block:
        return a
    ab = a.reshape(a.shape[:-1] + (-1, pad.block))[..., :spec.block]
    return ab.reshape(a.shape[:-1] + (-1,))


def check_tpu_tiling(leaf: str, w_shape, spec) -> None:
    """On a TPU backend, raise a readable error for a selectable leaf whose
    compact-path dW kernel Mosaic would refuse: the fan-in tile is the lane
    dim of the dW kernels' activation block. (A channel block that is not a
    whole number of lanes is padded to one: `_padded`.)"""
    if jax.default_backend() != "tpu":
        return
    k = w_shape[-2]
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
    pad = _padded(spec)
    tm, tk = _pick_tile(m, 128), _pick_tile(k, 128)
    kern = block_sparse_dw_pipelined_kernel if _use_pipelined(
        m, tk, pad.block, x2.dtype.itemsize, pipelined) \
        else block_sparse_dw_kernel
    out = kern(x2, _pad_blocks(dy2, spec, pad), idx, block=pad.block, tm=tm,
               tk=tk, interpret=_interpret())
    return out[..., :spec.block]


def _lane_tile(k: int, cap: int) -> int:
    """The whole of k up to `cap`, else its largest divisor <= 512 that is a
    whole number of lanes (the dW kernels' fan-in tile)."""
    if k <= cap:
        return k
    for d in range(512, 0, -LANES):
        if k % d == 0:
            return d
    return _pick_tile(k, LANES)


def block_sparse_dw_batched(x2, dy2, group_sizes, idx, spec):
    """Expert-batched compact dW (see core.sparse_update.compact_dw_batched).

    x2: [M, K], dy2: [M, N] with rows sorted by expert in groups of
    `group_sizes` [E], idx: [n_shards, n_sel] ->
    [E, K, n_shards, n_sel, block], in ONE launch for all experts and
    shards (the MoE expert leaf's whole backward is a single kernel). Rows
    go in tiles of up to 512 and the whole fan-in up to 2048 in one tile,
    so a step holds a [512, 2048] activation tile and does a 512-row
    matmul, and the steps are few."""
    m, k = x2.shape
    pad = _padded(spec)
    out = batched_dw_kernel(x2, _pad_blocks(dy2, spec, pad), group_sizes, idx,
                            block=pad.block, tm=_pick_tile(m, 512),
                            tk=_lane_tile(k, 2048), interpret=_interpret())
    return out[..., :spec.block]


def _matmul_tile(d: int) -> int:
    """A grouped matmul's tile along a contraction or output dim: the whole
    dim up to 1536 (1408-wide experts in one tile), else 512."""
    return d if d <= 1536 else _pick_tile(d, 512)


def grouped_matmul(x, w, group_sizes, transpose: bool = False):
    """Rows of x [M, K], in consecutive groups of `group_sizes` [E], times
    their group's w [E, K, N] (w [E, N, K] transposed with `transpose`), in
    x's dtype with float32 accumulation: the megablox grouped matmul, whose
    grid visits only the row tiles the groups cover, so the work follows
    the live rows and not M. Rows past the last group are left unwritten."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    m, k = x.shape
    n = w.shape[1] if transpose else w.shape[2]
    return gmm(x, w, group_sizes, preferred_element_type=x.dtype,
               tiling=(_pick_tile(m, 512), _matmul_tile(k), _matmul_tile(n)),
               transpose_rhs=transpose, interpret=_interpret())


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
    pad = _padded(spec)
    w3 = _pad_blocks(w.reshape(k, r, n), spec, pad)
    v5 = _pad_blocks(vals, spec, pad).reshape(k, r, spec.n_shards,
                                              spec.n_sel, pad.block)
    out = block_scatter_update_kernel(w3, v5, idx, tr=_pick_tile(r),
                                      interpret=_interpret())
    return _unpad_blocks(out, spec, pad).reshape(w.shape)


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
    pad = _padded(spec)
    flat = lambda a: None if a is None else _pad_blocks(a.reshape(k, r, n),
                                                        spec, pad)
    back = lambda a: None if a is None else _unpad_blocks(
        a, spec, pad).reshape(p.shape)
    g5 = _pad_blocks(g_sel, spec, pad).reshape(k, r, spec.n_shards,
                                               spec.n_sel, pad.block)
    w_new, mu_new, nu_new = fused_block_opt_kernel(
        flat(p), g5, idx, lr, t, flat(mu), flat(nu), kind=kind,
        momentum=oc.momentum, beta1=oc.beta1, beta2=oc.beta2, eps=oc.eps,
        weight_decay=oc.weight_decay, tr=_pick_tile(r),
        interpret=_interpret())
    return back(w_new), back(mu_new), back(nu_new)


def block_act_prune(x, threshold: float = 0.15, block: int = 2):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    tr = _pick_tile(x2.shape[0])
    c = shape[-1]
    tc = c if c < 512 else max(d for d in (512, 256, 128, 64) if c % d == 0)
    out = block_act_prune_kernel(x2, threshold=threshold, block=block,
                                 tr=tr, tc=tc, interpret=_interpret())
    return out.reshape(shape)
