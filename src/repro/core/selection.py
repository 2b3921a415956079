"""Layer/channel selection (paper §III-B.1) + memory-budget solver.

Selection criterion is the paper's: *later layers first* with a *constant*
channel update ratio `r`, sized so the backward working set fits the memory
budget `M`. No target-dataset statistics are used (the paper's realism
argument vs SparseUpdate/TinyTrain).

TPU adaptation: channels are selected in MXU-aligned blocks, equally many
per TP shard of each weight's output dim (the paper's equal-sparsity-per-PE
rule as TP load balance).
"""
from __future__ import annotations

import dataclasses
import zlib
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, SparseUpdateConfig
from repro.core.sparse_update import SelSpec
from repro.core import memory as memmod
from repro.models import transformer as T
from repro.models.registry import abstract_params
from repro.sharding import current_rules

# weight leaves that participate in channel selection (out-channel blocks)
SELECTABLE = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
              "in_proj", "out_proj", "wg", "wr", "wkv_a", "wkv_b"}
# excluded even though matmul-shaped: tiny recurrence/router params
EXCLUDED = {"router", "x_proj", "dt_proj", "A_log", "wA", "wB", "mu", "u",
            "w0", "conv_w"}


@dataclass(frozen=True)
class SelectionPlan:
    """Static plan: which scan-steps are trainable per segment and the
    channel-block spec for every selectable weight leaf."""
    seg_trainable: dict[str, int]          # segment -> trailing steps trainable
    spec: dict[str, Any]                   # segment -> nested {leaf: SelSpec}
    update_ratio: float
    channel_block: int
    seed: int
    update_embeddings: bool = False

    def total_steps(self) -> int:
        return sum(self.seg_trainable.values())


def _largest_divisor_leq(n: int, k: int) -> int:
    for d in range(min(n, k), 0, -1):
        if n % d == 0:
            return d
    return 1


def _sharded_out(path_names: tuple[str, ...], leaf_shape) -> int:
    """TP shard count of the out dim, from the logical specs."""
    from repro.models.specs import _leaf_spec

    class _L:  # minimal shim with .ndim
        def __init__(s, nd): s.ndim = nd
    spec = _leaf_spec(path_names, _L(len(leaf_shape)))
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return 1
    mesh_axis = rules.rules.get(spec[-1]) if spec[-1] else None
    if mesh_axis is None:
        return 1
    size = rules.mesh.shape[mesh_axis]
    return size if leaf_shape[-1] % size == 0 else 1


def _build_spec_tree(cfg, seg_stack_abs, ratio: float, block_req: int,
                     path_prefix: tuple[str, ...] = ()) -> dict:
    """Walk a segment's abstract stacked params; SelSpec per selectable leaf."""
    out = {}
    for name, sub in seg_stack_abs.items():
        if isinstance(sub, dict):
            child = _build_spec_tree(cfg, sub, ratio, block_req,
                                     path_prefix + (name,))
            if child:
                out[name] = child
            continue
        if name in EXCLUDED or name not in SELECTABLE or sub.ndim < 3:
            continue  # ndim<3: unstacked 1D bias etc (stacked 2D weight = ndim 3)
        out_dim = sub.shape[-1]
        n_shards = _sharded_out(path_prefix + (name,), sub.shape[1:])
        loc = out_dim // n_shards
        block = _largest_divisor_leq(loc, block_req)
        n_blocks = loc // block
        n_sel = max(1, int(round(ratio * n_blocks)))
        out[name] = SelSpec(block=block, n_shards=n_shards, n_sel=n_sel,
                            n_blocks=n_blocks)
    return out


def build_plan(cfg: ModelConfig, sp: SparseUpdateConfig,
               per_device_batch_tokens: int = 0) -> SelectionPlan:
    """Build the selection plan. If sp.num_update_layers == 0, solve the
    largest last-K under sp.memory_budget_bytes via the memory model."""
    segs = T.segment_layout(cfg)
    abs_params = abstract_params(cfg)

    spec = {}
    for seg in segs:
        spec[seg.name] = _build_spec_tree(cfg, abs_params["segments"][seg.name],
                                          sp.update_ratio, sp.channel_block)

    total_steps = sum(s.steps for s in segs)
    if sp.num_update_layers > 0:
        k_steps = min(sp.num_update_layers, total_steps)
    elif sp.memory_budget_bytes > 0:
        k_steps = memmod.solve_max_layers(cfg, sp, per_device_batch_tokens)
    else:
        k_steps = total_steps

    # distribute trainable steps from the END (later layers first — paper)
    seg_trainable = {}
    remaining = k_steps
    for seg in reversed(segs):
        take = min(seg.steps, remaining)
        seg_trainable[seg.name] = take
        remaining -= take
    return SelectionPlan(seg_trainable=seg_trainable, spec=spec,
                         update_ratio=sp.update_ratio,
                         channel_block=sp.channel_block, seed=sp.seed,
                         update_embeddings=sp.update_embeddings)


# ---------------------------------------------------------------------------
# index generation
# ---------------------------------------------------------------------------

def _rand_idx(key, steps: int, spec: SelSpec):
    """Random n_sel of n_blocks per (step, shard): [steps, n_shards, n_sel]."""
    u = jax.random.uniform(key, (steps, spec.n_shards, spec.n_blocks))
    return jnp.argsort(u, axis=-1)[..., : spec.n_sel].astype(jnp.int32)


def random_selection(plan: SelectionPlan, key) -> dict:
    """Fresh random channel-block selection (used every step of the dynamic
    phase). Returns idx tree: segment -> nested {leaf: [K, n_shards, n_sel]}."""
    idx = {}
    for seg_name, steps in plan.seg_trainable.items():
        if steps == 0:
            idx[seg_name] = None
            continue
        leaves, treedef = jax.tree_util.tree_flatten(
            plan.spec[seg_name], is_leaf=lambda x: isinstance(x, SelSpec))
        # stable across processes (builtin hash() varies with PYTHONHASHSEED,
        # which would break checkpoint-resume selection determinism)
        seg_salt = zlib.crc32(seg_name.encode()) % 2**31
        keys = jax.random.split(jax.random.fold_in(key, seg_salt),
                                max(1, len(leaves)))
        idx_leaves = [_rand_idx(k, steps, sp) for k, sp in zip(keys, leaves)]
        idx[seg_name] = jax.tree_util.tree_unflatten(treedef, idx_leaves)
    return idx


def magnitude_selection(plan: SelectionPlan, params) -> dict:
    """Initial selection: per shard, the n_sel blocks with largest weight L2
    norm (paper's offline importance — no target data needed)."""
    idx = {}
    for seg_name, steps in plan.seg_trainable.items():
        if steps == 0:
            idx[seg_name] = None
            continue
        stack = params["segments"][seg_name]
        k_slice = lambda a: a[a.shape[0] - steps:]
        idx[seg_name] = _magnitude_tree(plan.spec[seg_name], stack, k_slice)
    return idx


def _magnitude_tree(spec_tree, stack, k_slice):
    out = {}
    for name, sub in spec_tree.items():
        if isinstance(sub, dict):
            out[name] = _magnitude_tree(sub, stack[name], k_slice)
            continue
        sp: SelSpec = sub
        w = k_slice(stack[name])                       # [K, ..., out]
        k = w.shape[0]
        wb = w.reshape(k, -1, sp.n_shards, sp.n_blocks, sp.block)
        norms = jnp.sqrt((wb.astype(jnp.float32) ** 2).sum(axis=(1, 4)))
        order = jnp.argsort(-norms, axis=-1)
        out[name] = order[..., : sp.n_sel].astype(jnp.int32)
    return out


def selected_fraction(plan: SelectionPlan, cfg) -> float:
    """Fraction of total params updated per iteration (paper: ~2%)."""
    abs_params = abstract_params(cfg)
    total = sum(x.size for x in jax.tree.leaves(abs_params))
    upd = 0
    for seg_name, steps in plan.seg_trainable.items():
        if steps == 0:
            continue
        stack = abs_params["segments"][seg_name]
        upd += _selected_params(plan.spec[seg_name], stack, steps)
    return upd / total


def _selected_params(spec_tree, stack, steps) -> int:
    n = 0
    for name, sub in spec_tree.items():
        if isinstance(sub, dict):
            n += _selected_params(sub, stack[name], steps)
            continue
        sp: SelSpec = sub
        leaf = stack[name]
        per_step = leaf.size // leaf.shape[0]
        n += int(per_step * steps * (sp.n_sel / sp.n_blocks))
    return n
