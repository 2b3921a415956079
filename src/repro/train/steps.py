"""Train-state and train-step builders (dense baseline + DGSU sparse).

The state is a plain dict pytree (msgpack-serializable for checkpoints):

    {"step", "params_trainable", "params_frozen", "opt", "sel_idx", "rng"}

One compiled train_step serves all three schedule phases: the dynamic phase
only changes the *values* of sel_idx (int32 data, re-randomized in-graph).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.core import (build_plan, magnitude_selection, random_selection)
from repro.core.schedule import maybe_reselect
from repro.core.selection import SelectionPlan
from repro.core.sparse_update import split_stack
from repro.models import transformer as T
from repro.optim import apply_updates, apply_updates_mixed, init_opt_state

TrainState = dict  # alias: plain pytree


def split_params(params, plan: SelectionPlan):
    """Split full params into (frozen, trainable) trees per the plan."""
    frozen: dict = {"segments": {}}
    trainable: dict = {"segments": {}}
    for key in params:
        if key == "segments":
            continue
        if plan.update_embeddings and key in ("embed", "lm_head"):
            trainable[key] = params[key]
        else:
            frozen[key] = params[key]
    for seg_name, stack in params["segments"].items():
        k = plan.seg_trainable.get(seg_name, 0)
        f, t = split_stack(stack, k)
        if f is not None:
            frozen["segments"][seg_name] = f
        if t is not None:
            trainable["segments"][seg_name] = t
    return frozen, trainable


def merge_params(frozen, trainable):
    """Inverse of split_params (for checkpoint export / eval)."""
    from repro.core.sparse_update import merge_stack
    out = {}
    for tree in (frozen, trainable):
        for key, val in (tree or {}).items():
            if key == "segments":
                continue
            out[key] = val
    segs = {}
    f_segs = (frozen or {}).get("segments", {})
    t_segs = (trainable or {}).get("segments", {})
    for name in set(f_segs) | set(t_segs):
        segs[name] = merge_stack(f_segs.get(name), t_segs.get(name))
    out["segments"] = segs
    return out


def make_train_state(tc: TrainConfig, key, params=None,
                     selection_init: str = "magnitude") -> tuple[TrainState, SelectionPlan]:
    cfg = tc.model
    kp, ks = jax.random.split(key)
    if params is None:
        params = T.init_params(cfg, kp)
    if tc.sparse.enabled:
        tokens_per_device = tc.shape.global_batch * tc.shape.seq_len  # 1 host
        plan = build_plan(cfg, tc.sparse, tokens_per_device)
        if selection_init == "magnitude":
            sel_idx = magnitude_selection(plan, params)
        else:  # "random": trace-friendly (dry-run abstract state)
            sel_idx = random_selection(plan, kp)
    else:
        plan = build_plan(cfg, tc.sparse.__class__(
            enabled=False, update_ratio=1.0,
            num_update_layers=10**9, channel_block=tc.sparse.channel_block))
        sel_idx = None
    frozen, trainable = split_params(params, plan)
    state = {
        "step": jnp.zeros((), jnp.int32),
        "params_trainable": trainable,
        "params_frozen": frozen,
        "opt": init_opt_state(tc.optimizer, trainable),
        "sel_idx": sel_idx,
        "rng": ks,
    }
    return state, plan


def make_train_step(tc: TrainConfig, plan: SelectionPlan,
                    use_selection: bool = True, donate: bool = True,
                    compact_grads: Optional[bool] = None):
    """Returns a jit-able train_step(state, batch) -> (state, metrics).

    donate: whether the caller should donate the state argument when jitting
    (the returned function carries the matching `donate_argnums` attribute —
    jit as `jax.jit(fn, donate_argnums=fn.donate_argnums)` so the old
    state's buffers are reused in place; pass donate=False when the same
    input state must stay live across calls, e.g. A/B comparisons).

    compact_grads (default: tc.compact_grads) routes every segment weight
    with a SelSpec through the compact-gradient path: the backward emits the
    [K, n_shards, n_sel, block] dW directly (no full-shape zero-buffer
    scatter), the optimizer updates gathered weight/state blocks, and the
    result is scatter-written into the full weights once. Non-selectable
    leaves (norms, routers, embeddings) keep the dense path."""
    cfg = tc.model
    remat = tc.remat != "none"
    if compact_grads is None:
        compact_grads = tc.compact_grads

    def train_step(state, batch):
        step = state["step"]
        key = jax.random.fold_in(state["rng"], step)
        sel_idx = state["sel_idx"]
        if use_selection and tc.sparse.enabled and sel_idx is not None:
            sel_idx = maybe_reselect(plan, tc.sparse, sel_idx, step, key)
            sel = (sel_idx, plan.spec)
        else:
            sel = None

        trainable = state["params_trainable"]
        if compact_grads and sel is not None:
            from repro.core.sparse_update import (gather_selected_tree,
                                                  map_selectable)
            wsel = gather_selected_tree(trainable.get("segments", {}),
                                        sel_idx, plan.spec)
            spec_top = {"segments": plan.spec}

            def loss_of(diff):
                t_tree, ws = diff
                # selectable leaves only feed the forward matmul; their
                # gradient arrives compactly via `ws`
                stopped = map_selectable(t_tree, spec_top,
                                         jax.lax.stop_gradient)
                return T.loss_fn(cfg, (state["params_frozen"], stopped),
                                 batch, sel=(sel_idx, plan.spec, ws),
                                 remat=remat)

            (loss, metrics), (g_dense, g_sel) = jax.value_and_grad(
                loss_of, has_aux=True)((trainable, wsel))
            new_params, new_opt = apply_updates_mixed(
                tc.optimizer, trainable, g_dense, g_sel, state["opt"], step,
                sel_idx, plan.spec)
        else:
            def loss_of(t_tree):
                return T.loss_fn(cfg, (state["params_frozen"], t_tree),
                                 batch, sel=sel, remat=remat)

            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(trainable)
            from repro.core.sparse_update import (compact_allreduce_enabled,
                                                  compress_grads)
            if (compact_allreduce_enabled() and sel is not None
                    and "segments" in grads):
                from repro.models.specs import param_logical_specs
                logical = param_logical_specs(cfg).get("segments", {})
                grads = dict(grads)
                grads["segments"] = compress_grads(grads["segments"], sel_idx,
                                                   plan.spec, logical)
            new_params, new_opt = apply_updates(tc.optimizer, trainable,
                                                grads, state["opt"], step)
        new_state = {
            "step": step + 1,
            "params_trainable": new_params,
            "params_frozen": state["params_frozen"],
            "opt": new_opt,
            "sel_idx": sel_idx,
            "rng": state["rng"],
        }
        metrics = dict(metrics)
        metrics["loss"] = loss
        return new_state, metrics

    train_step.donate_argnums = (0,) if donate else ()
    return train_step


def make_online_wave(cfg, sparse, optimizer, plan: SelectionPlan, *,
                     wave_tokens: int, kernels: Optional[bool] = None,
                     remat: str = "selected"):
    """Builds the serve engine's online personalization train wave.

    Returns a jit-able `wave(trainable_base, frozen, delta_vals, sel_idx,
    batch, rng) -> (new_delta_vals, metrics)` that advances one user's
    compact delta (`repro.core.delta`) by one step of the existing 2-launch
    compact train step, WITHOUT touching the shared base params:

      1. materialize `base + delta` for the trainable suffix (gather-add +
         scatter — a transient copy of only the K trainable layers),
      2. run the compact-gradient train step on it (step index pinned to 0
         so the three-phase schedule never reselects; requires
         `sparse.phase_fixed_early >= 1`),
      3. re-extract `gather(new) - gather(base)` as the updated delta.

    The reported loss is computed BEFORE the update, so a falling sequence
    of wave losses on one user's traffic demonstrates personalization.
    Restricted to stateless optimizers (sgd, momentum 0) — per-user state
    is the delta and nothing else, matching the compact step's bitwise
    guarantee. `kernels` is baked in at trace time via `use_kernels` (None:
    the Pallas kernels exactly on a TPU backend), keeping the pinned
    2-launch-per-leaf property: the materialize/extract gathers stay on the
    jnp path and add no launches.
    """
    from repro.configs.base import ShapeConfig
    from repro.core.delta import apply_delta_tree, extract_delta_tree
    from repro.core.sparse_update import use_kernels

    assert optimizer.kind == "sgd" and optimizer.momentum == 0.0, (
        "online waves keep no per-user optimizer state: use sgd, momentum 0")
    assert sparse.phase_fixed_early >= 1, (
        "wave pins step=0; phase_fixed_early=0 would reselect in-wave")
    tc = TrainConfig(model=cfg,
                     shape=ShapeConfig("wave", wave_tokens, 1, "train"),
                     sparse=sparse, optimizer=optimizer, remat=remat,
                     compact_grads=True)
    step = make_train_step(tc, plan, use_selection=True, donate=False,
                           compact_grads=True)

    def wave(trainable_base, frozen, delta_vals, sel_idx, batch, rng):
        base_segs = trainable_base.get("segments", {})
        pers = dict(trainable_base)
        pers["segments"] = apply_delta_tree(base_segs, delta_vals, sel_idx,
                                            plan.spec)
        state = {
            "step": jnp.zeros((), jnp.int32),
            "params_trainable": pers,
            "params_frozen": frozen,
            "opt": init_opt_state(tc.optimizer, pers),
            "sel_idx": sel_idx,
            "rng": rng,
        }
        with use_kernels(kernels):
            new_state, metrics = step(state, batch)
        new_vals = extract_delta_tree(
            base_segs, new_state["params_trainable"]["segments"], sel_idx,
            plan.spec)
        return new_vals, metrics

    return wave
