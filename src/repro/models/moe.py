"""Token-choice top-k MoE with expert parallelism.

Dispatch strategy ("local dispatch EP"): tokens stay on their data shard;
expert weights are sharded over the `model` mesh axis (EP). Each device
builds capacity-bounded buffers for *its own* experts from its local tokens
(sort-based, no one-hot dispatch tensors), runs its experts' FFNs, combines
locally, and a single psum over the model axis sums expert partial outputs.
Collectives per layer: one [T_loc, d] psum (forward) — no [E, C, d]
all-to-all / all-gather traffic.

Outside a mesh (CPU unit tests) the same code runs with E_local = E and the
psum skipped.

Serve-time tensor parallelism (inside the paged-decode shard_map) shards a
DIFFERENT axis: every expert is resident on every shard, but the expert
FFN hidden dim d_ff is split column-/row-parallel (like `apply_mlp`) — the
router and the sort-based dispatch run replicated, each shard computes its
d_ff slice of every routed token, and one psum over the model axis
reassembles the combined output. Decode batches are tiny, so sharding the
per-token FLOPs beats sharding the expert set.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.sparse_update import smm
from repro.models.common import dense_init
from repro.sharding import current_mapped_axis, current_rules, psum_mapped
from repro.models import layers as L


def init_moe(key, cfg, dtype):
    moe = cfg.moe
    d, ff, e = cfg.d_model, cfg.d_ff, moe.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, ff), in_axis=1, dtype=dtype),
        "w_up": dense_init(ks[2], (e, d, ff), in_axis=1, dtype=dtype),
        "w_down": dense_init(ks[3], (e, ff, d), in_axis=1, dtype=dtype),
    }
    if moe.num_shared_experts:
        p["shared"] = L.init_mlp(ks[4], cfg, dtype,
                                 d_ff=moe.num_shared_experts * ff)
    return p


def _expert_ffn(p_slice, cfg, buf, sel):
    """buf: [E_loc, C, d] -> [E_loc, C, d] (swiglu assumed for MoE archs)."""
    h = jax.nn.silu(smm(buf, p_slice["w_gate"], sel, "w_gate"))
    h = h * smm(buf, p_slice["w_up"], sel, "w_up")
    return smm(h, p_slice["w_down"], sel, "w_down")


def _dispatch_combine(cfg, x_flat, ids, weights, wp, sel, axis: Optional[str],
                      e_local: int, capacity: int):
    """Per-device MoE body. x_flat [T,d]; ids/weights [T,k]; wp: expert
    weights already sliced to this device's experts [E_loc, ...]."""
    t, d = x_flat.shape
    k = ids.shape[1]
    e = cfg.moe.num_experts
    m_idx = jax.lax.axis_index(axis) if axis is not None else 0

    flat_e = ids.reshape(-1)                       # [T*k]
    flat_w = weights.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)                    # stable
    se, sw, st = flat_e[order], flat_w[order], flat_t[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[se]           # position within expert
    valid = pos < capacity
    e_own = se - m_idx * e_local                   # local expert index
    own = (e_own >= 0) & (e_own < e_local) & valid
    dest = jnp.where(own, e_own * capacity + pos, e_local * capacity)

    buf = jnp.zeros((e_local * capacity + 1, d), x_flat.dtype)
    buf = buf.at[dest].set(x_flat[st], mode="drop")
    h = _expert_ffn(wp, cfg, buf[:-1].reshape(e_local, capacity, d), sel)

    gathered = jnp.take(h.reshape(e_local * capacity, d), dest, axis=0,
                        mode="fill", fill_value=0.0)
    contrib = gathered * jnp.where(own, sw, 0.0)[:, None].astype(gathered.dtype)
    y = jnp.zeros((t, d), contrib.dtype).at[st].add(contrib)
    if axis is not None:
        y = jax.lax.psum(y, axis)
    return y.astype(x_flat.dtype)


def apply_moe(p, cfg, x, sel=None):
    """x: [B, S, d] -> (y [B, S, d], aux_losses dict)."""
    moe = cfg.moe
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    t = x_flat.shape[0]
    e, k = moe.num_experts, moe.top_k

    logits = (x_flat.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # aux losses (switch-style load balance + z-loss)
    frac_tokens = jnp.mean(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = probs.mean(0)
    aux = {
        "load_balance": e * jnp.sum(frac_tokens * frac_probs),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }

    rules = current_rules()
    axis = rules.model_axis if rules is not None and rules.mesh is not None else None
    if axis is not None:
        mesh = rules.mesh
        n_model = mesh.shape[axis]
        t_loc = t // max(1, _batch_shards(rules))
        capacity = _capacity(t_loc, k, moe.capacity_factor, e)
        e_local = e // n_model
        names = ("w_gate", "w_up", "w_down")
        batch_spec = P(rules.rules.get("batch"), None)
        in_specs = [batch_spec, batch_spec, batch_spec] + \
            [P(axis, None, None)] * 3
        args = [x_flat, ids, weights] + [p[n] for n in names]
        # compact path: the wsel leaves must cross shard_map as explicit
        # arguments (sharded over experts like the weights) so their
        # cotangents flow back out; closure capture would drop them
        wsel = sel[2] if sel is not None and len(sel) > 2 else None
        if wsel is not None:
            in_specs += [P(axis, None, None, None, None)] * 3
            args += [wsel[n] for n in names]

            def body(xf, i, w, wg, wu, wd, wsg, wsu, wsd):
                sub = (sel[0], sel[1],
                       {"w_gate": wsg, "w_up": wsu, "w_down": wsd})
                return _dispatch_combine(
                    cfg, xf, i, w, {"w_gate": wg, "w_up": wu, "w_down": wd},
                    sub, axis, e_local, capacity)
        else:
            def body(xf, i, w, wg, wu, wd):
                return _dispatch_combine(
                    cfg, xf, i, w, {"w_gate": wg, "w_up": wu, "w_down": wd},
                    sel, axis, e_local, capacity)
        y_flat = jax.shard_map(
            body, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=P(rules.rules.get("batch"), None),
            check_vma=False,
        )(*args)
    else:
        capacity = _capacity(t, k, moe.capacity_factor, e)
        y_flat = _dispatch_combine(cfg, x_flat, ids, weights,
                                   {kk: p[kk] for kk in ("w_gate", "w_up", "w_down")},
                                   sel, None, e, capacity)
        # serve mesh (inside the paged-decode shard_map): router + dispatch
        # replicated, every expert resident, but the expert hidden dim
        # arrived d_ff-sharded — each shard's combine holds the partial
        # w_down contraction of its d_ff slice, one psum reassembles it
        if current_mapped_axis() is not None and \
                p["w_gate"].shape[-1] != cfg.d_ff:
            y_flat = psum_mapped(y_flat)

    y = y_flat.reshape(b, s, d)
    if "shared" in p:
        y = y + L.apply_mlp(p["shared"], cfg, x, sel=_shared_sel(sel),
                            d_ff=moe.num_shared_experts * cfg.d_ff)
    return y, aux


def _shared_sel(sel):
    if sel is None:
        return None
    idx, spec = sel[0], sel[1]
    if idx is None or "shared" not in idx or "shared" not in spec:
        return None
    return tuple(comp["shared"] for comp in sel)


def _capacity(t_loc: int, k: int, cf: float, e: int) -> int:
    c = int(t_loc * k * cf / e) + 1
    return max(8, min(c, t_loc * k))


def _batch_shards(rules) -> int:
    n = 1
    for a in rules.batch_axes:
        n *= rules.mesh.shape[a]
    return n
