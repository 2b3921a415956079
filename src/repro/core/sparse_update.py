"""Dynamic gradient sparse update — the paper's core, as JAX autodiff machinery.

Two mechanisms (paper §III-B):

1. **Layer selection**: only the last-K scan-blocks of the network are
   trainable. Implemented by splitting the stacked layer params into
   (frozen-prefix, trainable-suffix); `jax.grad` w.r.t. the suffix only means
   XLA never materializes backward residuals for the prefix — the paper's
   "discard the corresponding output features" memory saving.

2. **Channel selection**: within trainable layers, each weight's *output
   channel blocks* are selected with ratio r. `smm` (sparse matmul) is a
   drop-in `x @ w` whose custom VJP computes dW **only for the selected
   blocks** (a compact [K, r·N] matmul instead of [K, N]) and scatters into a
   zero buffer. dX is always dense (needed to keep propagating). The block
   granularity (default 128) is the TPU adaptation: MXU-aligned tiles that
   the Pallas kernel (`repro.kernels.masked_dw`) can skip wholesale.

Selection indices are *data* (int32 arrays), so the dynamic phase of
Algorithm 1 re-randomizes them every step without recompilation.

Selection layout: for a weight with output dim N sharded over `n_shards` TP
shards, `idx` has shape [n_shards, n_sel] holding block indices *local to
each shard* — every shard updates the same number of blocks (the paper's
equal-sparsity-per-PE rule, reborn as TP load balance).

Compact-gradient path (`compact_grads=True` in the train step)
--------------------------------------------------------------
The dense-scatter path above still materializes a full-shape dW per weight
(`_scatter_blocks` writes the compact blocks into a [K, N] zero buffer) and
the optimizer then sweeps the whole tensor. The compact path never leaves
the [*, n_shards, n_sel, block] layout:

1. `gather_param_blocks` pulls the selected blocks of each selectable leaf
   into a compact `w_sel` companion tensor; the train step differentiates
   w.r.t. `w_sel` while the full weight enters the forward matmul with its
   gradient stopped.
2. `_smm_compact` / `_smm_batched_compact` compute the identical forward
   `x @ w` but their VJP emits the compact `compact_dw` /
   `compact_dw_batched` result directly as the cotangent of `w_sel` — no
   zero buffer, no full-shape scatter. Under `use_kernels` both are single
   Pallas launches (`kernels.masked_dw` for 2D weights,
   `kernels.batched_dw` for stacked expert weights: one grid over
   experts x shards x selected blocks).
3. `repro.optim.apply_updates_mixed` clips, applies the SGD/momentum/AdamW
   rule on the gathered blocks (gathering the matching optimizer-state
   blocks), and writes the result back with `scatter_param_blocks` (or the
   Pallas `kernels.scatter_blocks` in-place kernel under `use_kernels`).

Equivalence guarantees vs the dense-scatter path:

- SGD (momentum 0, no weight decay): bitwise identical — the dense path's
  update is the identity outside the selection and performs the exact same
  fp32 arithmetic inside it (`gather(scatter(dw_sel)) == dw_sel`, and the
  fp32->param-dtype cast round-trips untouched values).
- momentum / AdamW with a FIXED selection (phase 0/2 of Algorithm 1, or any
  window without reselection): identical, because optimizer state outside
  the selection stays zero in the dense sweep and untouched in the compact
  path.
- Under dynamic reselection the compact path implements the documented
  "stale state frozen" semantics exactly: deselected blocks keep their
  momentum frozen and their weights fixed. The dense sweep instead lets
  stale momentum keep decaying *and moving* deselected weights — an
  artifact of the sweep, not a property of the algorithm.
- `grad_clip > 0` changes the reduction shape of the global-norm sum, so
  equality holds to float-accumulation order (allclose, not bitwise).
- Weight decay in the compact path touches only selected blocks (the
  paper's "2% of weights updated per step" discipline); the dense sweep
  decays every weight.
"""
from __future__ import annotations

import contextlib
import threading
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

_FLAGS = threading.local()


class SelSpec(NamedTuple):
    """Static (trace-time) description of one weight's channel selection."""
    block: int        # channels per block
    n_shards: int     # TP shards of the out dim
    n_sel: int        # selected blocks per shard
    n_blocks: int     # total blocks per shard


@contextlib.contextmanager
def use_kernels(enabled: Optional[bool] = True):
    """Route the compact dW, optimizer and writeback through the Pallas
    kernels (True) or the jnp oracle (False). None restores the default:
    kernels exactly when the backend is a TPU. Read at trace time."""
    prev = getattr(_FLAGS, "kernels", None)
    _FLAGS.kernels = enabled
    try:
        yield
    finally:
        _FLAGS.kernels = prev


def kernels_enabled() -> bool:
    flag = getattr(_FLAGS, "kernels", None)
    return jax.default_backend() == "tpu" if flag is None else flag


@contextlib.contextmanager
def compact_allreduce(enabled: bool = True):
    """Gradient compression (beyond-paper, EXPERIMENTS.md §Perf): force the
    data-parallel reduction of dW onto the COMPACT selected-block tensor.

    A sharding constraint marks dw_sel as replicated across the DP axes, so
    XLA inserts the cross-data all-reduce there — r x the bytes of the
    full-shape gradient. The scatter to full shape then runs on already-
    replicated operands and needs no further collective."""
    prev = getattr(_FLAGS, "cgr", False)
    _FLAGS.cgr = enabled
    try:
        yield
    finally:
        _FLAGS.cgr = prev


def compact_allreduce_enabled() -> bool:
    return getattr(_FLAGS, "cgr", False)


def compress_grads(grads_segments: dict, sel_idx: dict, spec_tree: dict,
                   logical_tree: Optional[dict] = None):
    """Gradient-compression rewrite (used when compact_allreduce is on):

        dw  ->  scatter(constrain(gather(dw, idx)), idx)

    Selected-block gathers of dw equal dw's only nonzero content, so the
    rewrite is exact. The constraint marks the COMPACT tensor replicated
    across the DP axes (while keeping each leaf's natural TP sharding on its
    other dims, from `logical_tree` = param_logical_specs segments), so XLA
    places the cross-data all-reduce there — r x the full-gradient bytes
    (the paper's selected-channels idea applied to the interconnect)."""
    from repro.sharding import constrain

    def leaf(dw, idx, spec: SelSpec, logical):
        k_steps = dw.shape[0]
        lead = dw.shape[:-1]                   # [K(, E), in]
        dwb = dw.reshape(lead + (spec.n_shards, spec.n_blocks, spec.block))
        # idx: [K, n_shards, n_sel] -> broadcast into the gather
        bidx = idx.reshape((k_steps,) + (1,) * (len(lead) - 1)
                           + (spec.n_shards, spec.n_sel, 1))
        bidx = jnp.broadcast_to(bidx, lead + (spec.n_shards, spec.n_sel,
                                              spec.block))
        dw_sel = jnp.take_along_axis(dwb, bidx, axis=len(lead) + 1)
        # keep the leaf's natural TP sharding on its non-out dims; the out
        # dim's TP sharding (if any) rides the n_shards dim.
        if logical is not None and len(logical) == len(dw.shape):
            in_axes = tuple(logical[:-1])
            out_tp = logical[-1] if spec.n_shards > 1 else None
        else:
            in_axes = ("layers",) + (None,) * (len(lead) - 1)
            out_tp = "ff" if spec.n_shards > 1 else None
        dw_sel = constrain(dw_sel, *in_axes, out_tp, None, None)
        zeros = jnp.zeros_like(dwb)
        dw_new = jnp.put_along_axis(zeros, bidx, dw_sel.astype(dw.dtype),
                                    axis=len(lead) + 1, inplace=False)
        return dw_new.reshape(dw.shape)

    def walk(g, i, s, lg):
        if isinstance(s, SelSpec):
            return leaf(g, i, s, lg)
        if isinstance(s, dict):
            return {k: (walk(g[k], i[k], s[k],
                            (lg or {}).get(k) if isinstance(lg, dict) else None)
                        if k in s else g[k])
                    for k in g}
        return g

    out = {}
    for seg, g in grads_segments.items():
        if sel_idx.get(seg) is None or seg not in spec_tree:
            out[seg] = g
            continue
        lg = (logical_tree or {}).get(seg)
        out[seg] = walk(g, sel_idx[seg], spec_tree[seg], lg)
    return out


# ---------------------------------------------------------------------------
# sparse matmul
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _smm(x, w, idx, spec: SelSpec):
    return jnp.matmul(x, w)


def _smm_fwd(x, w, idx, spec: SelSpec):
    return jnp.matmul(x, w), (x, w, idx)


def _gather_blocks(dy2, idx, spec: SelSpec):
    """dy2: [M, N] -> selected blocks [M, n_shards, n_sel, block]."""
    m = dy2.shape[0]
    dyb = dy2.reshape(m, spec.n_shards, spec.n_blocks, spec.block)
    return jnp.take_along_axis(dyb, idx[None, :, :, None], axis=2)


def _scatter_blocks(dw_sel, idx, spec: SelSpec, k: int, dtype):
    """dw_sel: [K, n_shards, n_sel, block] -> full [K, N] with zeros elsewhere."""
    zeros = jnp.zeros((k, spec.n_shards, spec.n_blocks, spec.block), dtype)
    full = jnp.put_along_axis(
        zeros, jnp.broadcast_to(idx[None, :, :, None],
                                (k, spec.n_shards, spec.n_sel, spec.block)),
        dw_sel.astype(dtype), axis=2, inplace=False)
    return full.reshape(k, spec.n_shards * spec.n_blocks * spec.block)


def compact_dw(x2, dy2, idx, spec: SelSpec):
    """The paper's compute skip: dW for selected blocks only.

    x2: [M, K], dy2: [M, N] -> [K, n_shards, n_sel, block]
    """
    if kernels_enabled():
        from repro.kernels import ops as kops
        return kops.block_sparse_dw(x2, dy2, idx, spec)
    if spec.n_sel == spec.n_blocks:
        # full selection: the gather is a pure permutation, so let the einsum
        # consume a reshaped VIEW of dy2 and reorder the (M-times smaller)
        # output instead of materializing a gathered copy of the activations
        dyb = dy2.reshape(dy2.shape[0], spec.n_shards, spec.n_blocks,
                          spec.block)
        dw_all = jnp.einsum("mk,msnb->ksnb", x2, dyb,
                            preferred_element_type=jnp.float32)
        return jnp.take_along_axis(dw_all, idx[None, :, :, None], axis=2)
    dy_sel = _gather_blocks(dy2, idx, spec)
    return jnp.einsum("mk,msnb->ksnb", x2, dy_sel,
                      preferred_element_type=jnp.float32)


def compact_dw_batched(x3, dy3, idx, spec: SelSpec):
    """Expert-batched compute skip: per-expert dW for selected blocks only.

    x3: [E, C, K], dy3: [E, C, N] -> [E, K, n_shards, n_sel, block].
    Under `use_kernels` this is ONE Pallas launch for all experts x shards x
    selected blocks (`kernels.batched_dw`); the jnp fallback below is the
    oracle the kernel is verified against."""
    if kernels_enabled():
        from repro.kernels import ops as kops
        return kops.block_sparse_dw_batched(x3, dy3, idx, spec)
    e, m, _ = x3.shape
    dyb = dy3.reshape(e, m, spec.n_shards, spec.n_blocks, spec.block)
    dy_sel = jnp.take_along_axis(dyb, idx[None, None, :, :, None], axis=3)
    return jnp.einsum("eck,ecsnb->eksnb", x3, dy_sel,
                      preferred_element_type=jnp.float32)


def _smm_bwd(spec: SelSpec, res, dy):
    x, w, idx = res
    k, n = w.shape[-2], w.shape[-1]
    dx = jnp.matmul(dy, jnp.swapaxes(w, -1, -2))
    x2 = x.reshape(-1, k)
    dy2 = dy.reshape(-1, n)
    dw_sel = compact_dw(x2, dy2, idx, spec)
    dw = _scatter_blocks(dw_sel, idx, spec, k, w.dtype)
    return dx.astype(x.dtype), dw, None


_smm.defvjp(_smm_fwd, _smm_bwd)


# compact-VJP variant: same forward, but the weight gradient comes out as
# the compact [K, n_shards, n_sel, block] cotangent of `w_sel` (the gathered
# selected blocks) — nothing full-shape is ever scattered. The caller passes
# `w` with its gradient stopped; its (zero) cotangent is DCE'd by XLA.

@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _smm_compact(x, w, w_sel, idx, spec: SelSpec):
    return jnp.matmul(x, w)


def _smm_compact_fwd(x, w, w_sel, idx, spec: SelSpec):
    return jnp.matmul(x, w), (x, w, idx)


def _smm_compact_bwd(spec: SelSpec, res, dy):
    x, w, idx = res
    k, n = w.shape[-2], w.shape[-1]
    dx = jnp.matmul(dy, jnp.swapaxes(w, -1, -2))
    dw_sel = compact_dw(x.reshape(-1, k), dy.reshape(-1, n), idx, spec)
    return (dx.astype(x.dtype), jnp.zeros_like(w),
            dw_sel.astype(w.dtype), None)


_smm_compact.defvjp(_smm_compact_fwd, _smm_compact_bwd)


def smm(x, w, sel, name: str):
    """Sparse matmul: `x @ w` with channel-block-sparse dW.

    sel: None (dense backward), a pair (idx_dict, spec_dict), or a triple
    (idx_dict, spec_dict, wsel_dict). idx_dict[name] is int32
    [n_shards, n_sel], spec_dict[name] a SelSpec. With a triple, the VJP is
    COMPACT: the gradient flows to wsel_dict[name] (the gathered selected
    blocks) instead of being scattered into a full-shape dW. Weights absent
    from the dicts fall back to dense backward.
    """
    if sel is None:
        return jnp.matmul(x, w)
    idx_dict, spec_dict = sel[0], sel[1]
    if idx_dict is None or name not in idx_dict:
        return jnp.matmul(x, w)
    idx, spec = idx_dict[name], spec_dict[name]
    if kernels_enabled():
        from repro.kernels import ops as kops
        kops.check_tpu_tiling(name, w.shape, spec)
    wsel_dict = sel[2] if len(sel) > 2 else None
    if wsel_dict is not None and name in wsel_dict:
        if w.ndim == 2:
            return _smm_compact(x, w, wsel_dict[name], idx, spec)
        return _smm_batched_compact(x, w, wsel_dict[name], idx, spec)
    if w.ndim == 2:
        return _smm(x, w, idx, spec)
    return _smm_batched(x, w, idx, spec)


# batched (expert) variant: x [E, C, K], w [E, K, N]
@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _smm_batched(x, w, idx, spec: SelSpec):
    return jnp.einsum("eck,ekn->ecn", x, w)


def _smmb_fwd(x, w, idx, spec):
    return jnp.einsum("eck,ekn->ecn", x, w), (x, w, idx)


def _smmb_bwd(spec: SelSpec, res, dy):
    x, w, idx = res
    e, c, k = x.shape
    n = w.shape[-1]
    dx = jnp.einsum("ecn,ekn->eck", dy, w)
    dw_sel = compact_dw_batched(x, dy, idx, spec)
    zeros = jnp.zeros((e, k, spec.n_shards, spec.n_blocks, spec.block), w.dtype)
    dw = jnp.put_along_axis(
        zeros, jnp.broadcast_to(idx[None, None, :, :, None],
                                (e, k, spec.n_shards, spec.n_sel, spec.block)),
        dw_sel.astype(w.dtype), axis=3, inplace=False).reshape(e, k, n)
    return dx.astype(x.dtype), dw, None


_smm_batched.defvjp(_smmb_fwd, _smmb_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _smm_batched_compact(x, w, w_sel, idx, spec: SelSpec):
    return jnp.einsum("eck,ekn->ecn", x, w)


def _smmbc_fwd(x, w, w_sel, idx, spec):
    return jnp.einsum("eck,ekn->ecn", x, w), (x, w, idx)


def _smmbc_bwd(spec: SelSpec, res, dy):
    x, w, idx = res
    dx = jnp.einsum("ecn,ekn->eck", dy, w)
    dw_sel = compact_dw_batched(x, dy, idx, spec)
    return (dx.astype(x.dtype), jnp.zeros_like(w),
            dw_sel.astype(w.dtype), None)


_smm_batched_compact.defvjp(_smmbc_fwd, _smmbc_bwd)


# ---------------------------------------------------------------------------
# compact-path block gather/scatter (params and optimizer state)
# ---------------------------------------------------------------------------

def _block_idx(idx, spec: SelSpec, lead: tuple, k: int):
    """Broadcast [K, n_shards, n_sel] indices into the blocked-leaf layout."""
    bidx = idx.reshape((k,) + (1,) * len(lead)
                       + (spec.n_shards, spec.n_sel, 1))
    return jnp.broadcast_to(
        bidx, (k,) + lead + (spec.n_shards, spec.n_sel, spec.block))


def gather_param_blocks(w, idx, spec: SelSpec):
    """Stacked leaf [K, *lead, N] -> compact [K, *lead, n_shards, n_sel,
    block] of the selected blocks. idx: [K, n_shards, n_sel]."""
    k = w.shape[0]
    lead = w.shape[1:-1]
    wb = w.reshape((k,) + lead + (spec.n_shards, spec.n_blocks, spec.block))
    return jnp.take_along_axis(wb, _block_idx(idx, spec, lead, k),
                               axis=len(lead) + 2)


def scatter_param_blocks(w, vals, idx, spec: SelSpec):
    """Inverse write of gather_param_blocks: overwrite the selected blocks of
    `w` with `vals` (unselected blocks untouched — the operand is the live
    tensor, NOT a zero buffer). Routes to the Pallas in-place kernel under
    `use_kernels`."""
    if kernels_enabled():
        from repro.kernels import ops as kops
        return kops.block_scatter_update(w, vals.astype(w.dtype), idx, spec)
    k = w.shape[0]
    lead = w.shape[1:-1]
    wb = w.reshape((k,) + lead + (spec.n_shards, spec.n_blocks, spec.block))
    out = jnp.put_along_axis(wb, _block_idx(idx, spec, lead, k),
                             vals.astype(w.dtype), axis=len(lead) + 2,
                             inplace=False)
    return out.reshape(w.shape)


def map_selectable(tree, spec_tree, fn):
    """Apply `fn` to every leaf of `tree` that has a SelSpec in `spec_tree`
    (matched positionally); other leaves pass through unchanged. Works on
    the trainable tree: spec_tree is keyed {"segments": {seg: {leaf: ...}}}
    style via plan.spec — pass `{"segments": plan.spec}`-shaped trees."""
    def walk(node, spec):
        if isinstance(spec, SelSpec):
            return fn(node)
        if isinstance(node, dict):
            return {key: (walk(val, spec[key])
                          if isinstance(spec, dict) and key in spec else val)
                    for key, val in node.items()}
        return node
    return walk(tree, spec_tree)


def gather_selected_tree(segments, idx_tree, spec_tree):
    """Compact companion tree for the trainable segments: for each SelSpec
    leaf, the gathered selected blocks; segments without selection map to
    None. segments/idx_tree/spec_tree are keyed by segment name."""
    def walk(stack, idx, spec):
        if isinstance(spec, SelSpec):
            return gather_param_blocks(stack, idx, spec)
        return {key: walk(stack[key], idx[key], spec[key]) for key in spec}

    out = {}
    for seg, spec in spec_tree.items():
        if idx_tree.get(seg) is None or seg not in segments or not spec:
            out[seg] = None
            continue
        out[seg] = walk(segments[seg], idx_tree[seg], spec)
    return out


# ---------------------------------------------------------------------------
# layer-level split (frozen prefix / trainable suffix over scan stacks)
# ---------------------------------------------------------------------------

def split_stack(stack, n_trainable: int):
    """Split stacked layer params [L, ...] into (frozen [L-K], trainable [K])."""
    if n_trainable <= 0:
        return stack, None
    frozen = jax.tree.map(lambda a: a[: a.shape[0] - n_trainable], stack)
    trainable = jax.tree.map(lambda a: a[a.shape[0] - n_trainable:], stack)
    depth = jax.tree.leaves(stack)[0].shape[0]
    if n_trainable >= depth:
        return None, stack
    return frozen, trainable


def merge_stack(frozen, trainable):
    if frozen is None:
        return trainable
    if trainable is None:
        return frozen
    return jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                        frozen, trainable)
