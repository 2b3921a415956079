"""Logical-axis partition specs for every param leaf, derived from the
abstract param tree by leaf-name rules (megatron-style TP + EP).

Used by the dry-run (NamedSharding for pjit in_shardings) and by the
selection planner (a weight's out-dim TP degree = its selection shard count).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.models.registry import abstract_params

# leaf name -> logical axes of the *trailing* dims (leading "layers"/"expert"
# axes are added automatically from ndim).
_RULES_2D = {
    # attention (column-parallel qkv, row-parallel o)
    "wq": ("model_d", "heads"),
    "wk": ("model_d", "kv_heads"),
    "wv": ("model_d", "kv_heads"),
    "wo": ("heads", "model_d"),
    # latent attention: the latent projection replicated, its per-head
    # decompression column-parallel
    "wkv_a": ("model_d", None),
    "wkv_b": (None, "heads"),
    # mlp (column-parallel up/gate, row-parallel down)
    "w_gate": ("model_d", "ff"),
    "w_up": ("model_d", "ff"),
    "w_down": ("ff", "model_d"),
    # mamba
    "in_proj": ("model_d", "d_inner"),
    "out_proj": ("d_inner", "model_d"),
    "x_proj": ("d_inner", None),
    "dt_proj": (None, "d_inner"),
    "A_log": ("d_inner", None),
    # moe router (replicated)
    "router": ("model_d", None),
    # rwkv decay lora (replicated: heads not divisible by wide TP)
    "wA": (None, None),
    "wB": (None, None),
    "u": (None, None),
    "mu": (None, None),
}
_RULES_1D = {
    "conv_b": ("d_inner",),
    "dt_bias": ("d_inner",),
    "D": ("d_inner",),
    "w0": (None,),
}
# rwkv time-mix square mats are replicated (40 heads ∤ 16-way TP); its
# channel-mix uses the regular mlp-style rules below.
_RWKV_TIME_REPLICATED = {"wr", "wk", "wv", "wg", "wo"}
_RWKV_CHAN = {"wk": ("model_d", "ff"), "wv": ("ff", "model_d"),
              "wr": (None, None)}


def _leaf_spec(path: tuple[str, ...], leaf) -> tuple[Optional[str], ...]:
    name = path[-1]
    ndim = leaf.ndim
    inside = [p for p in path[:-1]]

    if name == "tok":                                   # embed [V, d]
        return ("vocab", "model_d")
    if path[-2:] == ("lm_head", "w") or (len(path) >= 2 and path[-2] == "lm_head"):
        return ("model_d", "vocab")
    if name in ("scale", "bias"):                       # norms
        return ("layers",) * (ndim - 1) + (None,)
    if name == "conv_w":                                # [*, K, d_inner]
        return ("layers",) * (ndim - 2) + (None, "d_inner")

    in_time_mix = "time" in inside
    in_chan_mix = "chan" in inside
    if in_time_mix and name in _RWKV_TIME_REPLICATED:
        return ("layers",) * (ndim - 2) + (None, None)
    if in_chan_mix and name in _RWKV_CHAN:
        return ("layers",) * (ndim - 2) + _RWKV_CHAN[name]

    if name in _RULES_1D:
        return ("layers",) * (ndim - 1) + _RULES_1D[name]
    if name in _RULES_2D:
        base = _RULES_2D[name]
        lead = ndim - 2
        # moe expert stacks: [layers, E, in, out] -> expert axis sharded
        if lead >= 1 and name in ("w_gate", "w_up", "w_down") \
                and "moe" in inside and "shared" not in inside:
            lead_axes = ("layers",) * (lead - 1) + ("expert",)
            # expert-sharded weights are NOT TP-sharded on ff
            inner = tuple(None if a == "ff" else a for a in base)
            return lead_axes + inner
        return ("layers",) * lead + base
    if name in ("w", "b"):                              # CNN leaves (no TP)
        return (None,) * ndim
    # fallback: replicated
    return ("layers",) * max(0, ndim - 1) + (None,) * min(1, ndim)


def param_logical_specs(cfg):
    """Tree of logical-axis tuples mirroring init_params(cfg)."""
    tree = abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]

    def path_names(path):
        out = []
        for k in path:
            if hasattr(k, "key"):
                out.append(str(k.key))
        return tuple(out)

    specs = {}
    for path, leaf in flat:
        specs[path_names(path)] = _leaf_spec(path_names(path), leaf)
    # rebuild nested structure
    return _unflatten(specs)


def _unflatten(flat: dict[tuple[str, ...], tuple]) -> dict:
    root: dict = {}
    for path, val in flat.items():
        node = root
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = val
    return root
