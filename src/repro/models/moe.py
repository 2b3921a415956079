"""Token-choice top-k MoE: one layer that holds some of the routed experts.

The router scores every routed expert (`MoEConfig.routed`); the layer holds
`num_experts` of them, from global index `first_expert` on, and returns
their weighted part of the routed output plus the shared experts. What the
experts held elsewhere add is left out: with the layer split over chips by
expert (expert parallelism), each chip's share is this partial result, and
the shares, with the shared experts counted once, sum to the whole layer.

Routing (`MoEConfig.scoring`):
- "softmax": p = softmax(x Wr); the top-k of p, renormalised over the k;
- "sigmoid" (DeepSeek-V3): s = sigmoid(x Wr); the experts are the top-k of
  s + b, with b a per-layer correction bias that picks but does not weight
  (no gradient reaches it); the weights are s at those experts over their
  sum.
Both then scale the weights by `routed_scaling`. Scores are float32.

Dispatch is dropless: the T * k routing slots are sorted by expert, the
held experts' slots first (a counting sort), and the held experts' SwiGLU
runs as grouped matmuls over their groups of rows (`core.sparse_update.gmm`).
The buffers have a static bound of T * min(k, held) rows; the row gather,
the elementwise work, the combine, the grouped matmuls and the dW kernel
do no work past the live rows (`held_experts`).

Under a training mesh the held experts are sharded over the `model` axis:
the same layer runs per shard on its slice, with its first expert taken
from the axis index, and one psum over the axis sums the shards' parts.
Serve-time tensor parallelism (inside the paged-decode shard_map) instead
keeps every expert resident and shards the expert hidden dim d_ff: each
shard computes its d_ff slice of every routed row, and one psum reassembles
the output.

Loss terms (`aux`): "load_balance" is the switch-style E * sum_e f_e P_e
over the batch for softmax scoring, and for sigmoid scoring DeepSeek-V3's
sequence-wise term, per row sum_i f_i P_i with f_i = R / (k S) * the row's
slots on expert i and P_i the row's mean of the scores normalised over all
R experts, averaged over rows; "router_z" is the mean logsumexp(x Wr)^2.
Counters: "rows_held", the slots that land on held experts; "rows_max",
the largest held expert's rows.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.sparse_update import gmm
from repro.models.common import dense_init
from repro.sharding import current_mapped_axis, current_rules, psum_mapped
from repro.models import layers as L

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def init_moe(key, cfg, dtype):
    moe = cfg.moe
    d, ff, e = cfg.d_model, cfg.d_ff, moe.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, moe.routed), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, ff), in_axis=1, dtype=dtype),
        "w_up": dense_init(ks[2], (e, d, ff), in_axis=1, dtype=dtype),
        "w_down": dense_init(ks[3], (e, ff, d), in_axis=1, dtype=dtype),
    }
    if moe.scoring == "sigmoid":
        p["score_bias"] = jnp.zeros((moe.routed,), jnp.float32)
    if moe.num_shared_experts:
        p["shared"] = L.init_mlp(ks[4], cfg, dtype,
                                 d_ff=moe.num_shared_experts * ff)
    return p


def route(p, moe, x_flat):
    """x_flat [T, d] -> (ids [T, k] global expert ids, weights [T, k],
    probs [T, R] the scores normalised over all experts, logits [T, R])."""
    logits = jnp.matmul(x_flat.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    k = moe.top_k
    if moe.scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(s + jax.lax.stop_gradient(p["score_bias"]), k)
        weights = jnp.take_along_axis(s, ids, axis=-1)
        probs = s / s.sum(-1, keepdims=True)
    elif moe.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, k)
    else:
        raise ValueError(f"moe scoring {moe.scoring!r}")
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-20)
    return ids, weights * moe.routed_scaling, probs, logits


def _balance(moe, ids, probs, rows: int):
    """The balance loss term of `moe.scoring`'s family (module docstring)."""
    r, k = moe.routed, moe.top_k
    t = ids.shape[0]
    counts = jax.nn.one_hot(ids, r, dtype=jnp.float32).sum(1)    # [T, R]
    if moe.scoring == "sigmoid":
        s = t // rows
        f = counts.reshape(rows, s, r).sum(1) * (r / (k * s))
        pm = probs.reshape(rows, s, r).mean(1)
        return jnp.mean(jnp.sum(f * pm, axis=-1))
    return r * jnp.sum(counts.mean(0) / k * probs.mean(0))


# rows per step of the loops over the live rows (below)
CHUNK = 1024


def _live(start, c: int, n):
    """[c, 1] mask of the rows start .. start + c - 1 that lie below n."""
    return (start + jnp.arange(c) < n)[:, None]


def _over_live(n, c: int, body, init):
    """body(start, carry) over the chunks of c rows that hold the first n
    rows: a loop whose trip count follows n, not the buffers' bound."""
    return jax.lax.fori_loop(0, (n + c - 1) // c,
                             lambda i, carry: body(i * c, carry), init)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_live(src, idx, n, c: int):
    """out [B, d]: row r < n is src[idx[r]], every other row zero; the
    gradient scatters the live rows back into src."""
    def body(s, out):
        rows = jnp.take(src, jax.lax.dynamic_slice_in_dim(idx, s, c), axis=0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(_live(s, c, n), rows, 0), s, 0)
    return _over_live(n, c, body,
                      jnp.zeros((idx.shape[0], src.shape[1]), src.dtype))


def _gather_live_fwd(src, idx, n, c):
    # the residual keeps src's row count in a zero-width array
    return _gather_live(src, idx, n, c), (src[:, :0], idx, n)


def _gather_live_bwd(c, res, dout):
    empty, idx, n = res
    shape = (empty.shape[0], dout.shape[1])

    def body(s, acc):
        g = jax.lax.dynamic_slice_in_dim(dout, s, c).astype(jnp.float32)
        return acc.at[jax.lax.dynamic_slice_in_dim(idx, s, c)].add(
            jnp.where(_live(s, c, n), g, 0))
    dsrc = _over_live(n, c, body, jnp.zeros(shape, jnp.float32))
    return dsrc.astype(dout.dtype), None, None


_gather_live.defvjp(_gather_live_fwd, _gather_live_bwd)


def _swiglu(g, u):
    return jax.nn.silu(g) * u


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _swiglu_live(g, u, n, c: int):
    """silu(g) * u on the rows below n, zero on every other row."""
    def body(s, out):
        h = _swiglu(jax.lax.dynamic_slice_in_dim(g, s, c),
                    jax.lax.dynamic_slice_in_dim(u, s, c))
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(_live(s, c, n), h, 0), s, 0)
    return _over_live(n, c, body, jnp.zeros(g.shape, g.dtype))


def _swiglu_live_fwd(g, u, n, c):
    return _swiglu_live(g, u, n, c), (g, u, n)


def _swiglu_live_bwd(c, res, dh):
    g, u, n = res

    def body(s, acc):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, s, c)
        _, vjp = jax.vjp(_swiglu, cut(g), cut(u))
        dg, du = vjp(jnp.where(_live(s, c, n), cut(dh), 0))
        return (jax.lax.dynamic_update_slice_in_dim(acc[0], dg, s, 0),
                jax.lax.dynamic_update_slice_in_dim(acc[1], du, s, 0))
    dg, du = _over_live(n, c, body, (jnp.zeros(g.shape, g.dtype),
                                     jnp.zeros(u.shape, u.dtype)))
    return dg, du, None


_swiglu_live.defvjp(_swiglu_live_fwd, _swiglu_live_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _combine_live(ys, tok, w, n, t: int, c: int):
    """y [t, d] float32: for every row r < n, w[r] * ys[r] added into token
    tok[r]'s row."""
    def body(s, y):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, s, c)
        part = cut(w)[:, None] * cut(ys).astype(jnp.float32)
        return y.at[cut(tok)].add(jnp.where(_live(s, c, n), part, 0))
    return _over_live(n, c, body, jnp.zeros((t, ys.shape[1]), jnp.float32))


def _combine_live_fwd(ys, tok, w, n, t, c):
    return _combine_live(ys, tok, w, n, t, c), (ys, tok, w, n)


def _combine_live_bwd(t, c, res, dy):
    ys, tok, w, n = res

    def body(s, acc):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, s, c)
        live = _live(s, c, n)
        gy = jnp.take(dy, cut(tok), axis=0)
        dys = jnp.where(live, cut(w)[:, None] * gy, 0).astype(ys.dtype)
        dw = jnp.where(live[:, 0], jnp.sum(
            cut(ys).astype(jnp.float32) * gy, axis=-1), 0)
        return (jax.lax.dynamic_update_slice_in_dim(acc[0], dys, s, 0),
                jax.lax.dynamic_update_slice_in_dim(acc[1], dw, s, 0))
    dys, dw = _over_live(n, c, body, (jnp.zeros(ys.shape, ys.dtype),
                                      jnp.zeros(w.shape, w.dtype)))
    return dys, None, dw, None


_combine_live.defvjp(_combine_live_fwd, _combine_live_bwd)


def held_experts(wp, x_flat, ids, weights, first, sel):
    """The held experts' weighted part of the routed output, dropless.
    x_flat [T, d]; ids, weights [T, k] (global expert ids); wp: the held
    experts' stacked weights [E_h, ...], global ids first .. first + E_h - 1.
    Returns (y [T, d] float32, rows per held expert [E_h]).

    The held experts' rows lie first in buffers of a static bound, T *
    min(k, E_h) rounded up to whole chunks; the row gather, the SwiGLU's
    elementwise work and the combine (a scatter-add of each live row into
    its token) loop over the chunks that hold live rows, in both
    directions, and the grouped matmuls skip the tiles past them: the work
    follows the live rows, not the bound."""
    t, d = x_flat.shape
    k = ids.shape[1]
    e_h = wp["w_gate"].shape[0]
    c = min(CHUNK, t * min(k, e_h))
    bound = -(-t * min(k, e_h) // c) * c
    with jax.named_scope("dispatch"):
        local = ids.reshape(-1) - first
        key = jnp.where((local >= 0) & (local < e_h), local, e_h)
        onehot = key[:, None] == jnp.arange(e_h + 1)             # [T k, E+1]
        counts = onehot.sum(0, dtype=jnp.int32)
        rank = jnp.cumsum(onehot, axis=0, dtype=jnp.int32)
        pos = (jnp.cumsum(counts) - counts)[key] + \
            jnp.take_along_axis(rank, key[:, None], axis=1)[:, 0] - 1
        slot = jnp.zeros((max(bound, t * k),), jnp.int32).at[pos].set(
            jnp.arange(t * k, dtype=jnp.int32))[:bound]          # row -> slot
        sizes = counts[:e_h]
        n = sizes.sum()
        tok = slot // k
        xs = _gather_live(x_flat, tok, n, c)
    with jax.named_scope("experts"):
        h = _swiglu_live(gmm(xs, wp["w_gate"], sizes, sel, "w_gate"),
                         gmm(xs, wp["w_up"], sizes, sel, "w_up"), n, c)
        ys = gmm(h, wp["w_down"], sizes, sel, "w_down")
    with jax.named_scope("dispatch"):
        y = _combine_live(ys, tok, jnp.take(weights.reshape(-1), slot), n,
                          t, c)
    return y, sizes


def apply_moe(p, cfg, x, sel=None):
    """x: [B, S, d] -> (y [B, S, d], aux dict: load_balance, router_z,
    rows_held, rows_max)."""
    moe = cfg.moe
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    with jax.named_scope("router"):
        ids, weights, probs, logits = route(p, moe, x_flat)
    aux = {"load_balance": _balance(moe, ids, probs, b),
           "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)}

    wp = {n: p[n] for n in EXPERT_LEAVES}
    rules = current_rules()
    axis = rules.model_axis if rules is not None and rules.mesh is not None \
        else None
    if axis is not None:
        mesh = rules.mesh
        e_local = moe.num_experts // mesh.shape[axis]
        batch_spec = P(rules.rules.get("batch"), None)
        in_specs = [batch_spec] * 3 + [P(axis, None, None)] * 3
        args = [x_flat, ids, weights] + [p[n] for n in EXPERT_LEAVES]
        # compact path: the wsel leaves must cross shard_map as explicit
        # arguments (sharded over experts like the weights) so their
        # cotangents flow back out; closure capture would drop them
        wsel = sel[2] if sel is not None and len(sel) > 2 else None
        if wsel is not None:
            in_specs += [P(axis, None, None, None, None)] * 3
            args += [wsel[n] for n in EXPERT_LEAVES]

        every = (axis,) + tuple(rules.batch_axes)

        def body(xf, i, w, *leaves):
            sub = sel
            if wsel is not None:
                sub = (sel[0], sel[1], dict(zip(EXPERT_LEAVES, leaves[3:])))
            first = moe.first_expert + jax.lax.axis_index(axis) * e_local
            y, sizes = held_experts(dict(zip(EXPERT_LEAVES, leaves[:3])), xf,
                                    i, w, first, sub)
            return (jax.lax.psum(y, axis),
                    jax.lax.psum(sizes.sum().astype(jnp.float32), every),
                    jax.lax.pmax(sizes.max().astype(jnp.float32), every))

        y_flat, rows_held, rows_max = jax.shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=(batch_spec, P(), P()), check_vma=False)(*args)
    else:
        y_flat, sizes = held_experts(wp, x_flat, ids, weights,
                                     moe.first_expert, sel)
        rows_held = sizes.sum().astype(jnp.float32)
        rows_max = sizes.max().astype(jnp.float32)
        # serve mesh (inside the paged-decode shard_map): every expert
        # resident, the expert hidden dim arrived d_ff-sharded — each
        # shard's combine holds the partial w_down contraction of its d_ff
        # slice, one psum reassembles it
        if current_mapped_axis() is not None and \
                p["w_gate"].shape[-1] != cfg.d_ff:
            y_flat = psum_mapped(y_flat)
    aux["rows_held"], aux["rows_max"] = rows_held, rows_max

    y = y_flat.astype(x.dtype).reshape(b, s, d)
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


