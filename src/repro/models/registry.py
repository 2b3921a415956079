"""Arch registry: config -> model functions, plus analytic parameter counts
(used by the memory-budget solver)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mamba as M
from repro.models import rwkv6 as R
from repro.models import transformer as T


def init_params(cfg: ModelConfig, key):
    return T.init_params(cfg, key)


def abstract_params(cfg: ModelConfig):
    """Shape/dtype tree without allocating (for dry-run and planning)."""
    return jax.eval_shape(lambda k: T.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count via abstract init. active_only: count only
    top-k routed experts (for MoE MODEL_FLOPS = 6·N_active·D)."""
    tree = abstract_params(cfg)
    total = sum(x.size for x in jax.tree.leaves(tree))
    if not active_only or cfg.moe is None:
        return total
    # subtract inactive routed-expert params
    seg = tree["segments"]
    inactive = 0
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    frac_inactive = (e - k) / e

    def walk(node):
        nonlocal inactive
        if isinstance(node, dict):
            for name, sub in node.items():
                if name in ("w_gate", "w_up", "w_down") and hasattr(sub, "ndim") \
                        and sub.ndim == 4:  # [L, E, in, out]
                    inactive += int(sub.size * frac_inactive)
                elif isinstance(sub, dict):
                    walk(sub)
    walk(seg)
    return total - inactive


def flops_per_token(cfg: ModelConfig, train: bool = True) -> float:
    """MODEL_FLOPS per token: 6·N (train) or 2·N (inference) on active
    params, plus attention score FLOPs are excluded (reported separately)."""
    n = param_count(cfg, active_only=True)
    return (6.0 if train else 2.0) * n
