"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax.numpy as jnp


def block_sparse_dw_ref(x, dy, idx, block: int):
    """x: [M,K], dy: [M,N], idx: [n_shards,n_sel] ->
    [K, n_shards, n_sel, block] fp32 (the compact-path dW layout)."""
    m, k = x.shape
    n = dy.shape[1]
    n_shards, n_sel = idx.shape
    dyb = dy.reshape(m, n_shards, n // (n_shards * block), block)
    dy_sel = jnp.take_along_axis(dyb, idx[None, :, :, None], axis=2)
    return jnp.einsum("mk,msjb->ksjb", x.astype(jnp.float32),
                      dy_sel.astype(jnp.float32))


def batched_dw_ref(x, dy, group_sizes, idx, block: int):
    """Per-expert compact dW oracle: x: [M,K], dy: [M,N] with rows sorted by
    expert in groups of `group_sizes` [E] (rows past the last group belong
    to none), idx: [n_shards,n_sel] -> [E, K, n_shards, n_sel, block] fp32
    (the expert-batched compact-path layout; a dense per-expert einsum over
    the expert's rows, gathered at the selection)."""
    m, k = x.shape
    n = dy.shape[-1]
    n_shards, n_sel = idx.shape
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(m)[None, :]
    own = ((rows >= (ends - group_sizes)[:, None])
           & (rows < ends[:, None])).astype(jnp.float32)        # [E, M]
    dyb = dy.reshape(m, n_shards, n // (n_shards * block), block)
    dy_sel = jnp.take_along_axis(dyb, idx[None, :, :, None], axis=2)
    return jnp.einsum("em,mk,msjb->eksjb", own, x.astype(jnp.float32),
                      dy_sel.astype(jnp.float32))


def _block_idx5(idx, r: int, block: int):
    """[K, S, n_sel] -> broadcast gather/scatter index [K, R, S, n_sel, blk]."""
    k, s, n_sel = idx.shape
    return jnp.broadcast_to(idx[:, None, :, :, None], (k, r, s, n_sel, block))


def block_scatter_update_ref(w, upd, idx, block: int):
    """w: [K,R,N], upd: [K,R,n_shards,n_sel,block], idx: [K,n_shards,n_sel]
    -> w with the selected blocks overwritten (unselected untouched)."""
    k, r, n = w.shape
    n_shards = idx.shape[1]
    wb = w.reshape(k, r, n_shards, n // (n_shards * block), block)
    out = jnp.put_along_axis(wb, _block_idx5(idx, r, block),
                             upd.astype(w.dtype), axis=3, inplace=False)
    return out.reshape(k, r, n)


def fused_block_opt_ref(w, g, idx, lr, t, mu=None, nu=None, *, kind: str,
                        momentum: float = 0.0, beta1: float = 0.9,
                        beta2: float = 0.999, eps: float = 1e-8,
                        weight_decay: float = 0.0):
    """Gather -> optimizer block rule -> scatter, as three jnp passes (the
    un-fused oracle for fused_block_opt; arithmetic mirrors
    optim.optimizers._leaf_update). Shapes as fused_block_opt's module doc;
    returns (w', mu', nu') with None for absent state."""
    k, r, n = w.shape
    block = g.shape[-1]
    n_shards = idx.shape[1]
    bidx = _block_idx5(idx, r, block)

    def gather(a):
        ab = a.reshape(k, r, n_shards, n // (n_shards * block), block)
        return jnp.take_along_axis(ab, bidx, axis=3)

    def scatter(a, vals):
        ab = a.reshape(k, r, n_shards, n // (n_shards * block), block)
        out = jnp.put_along_axis(ab, bidx, vals.astype(a.dtype), axis=3,
                                 inplace=False)
        return out.reshape(k, r, n)

    p32 = gather(w).astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    if kind == "sgd":
        new = p32 - lr * g32
        if weight_decay:
            new = new - lr * weight_decay * p32
        return scatter(w, new), None, None
    if kind == "momentum":
        mu_new = momentum * gather(mu) + g32
        new = p32 - lr * mu_new
        if weight_decay:
            new = new - lr * weight_decay * p32
        return scatter(w, new), scatter(mu, mu_new), None
    if kind == "adamw":
        mu_new = beta1 * gather(mu) + (1 - beta1) * g32
        nu_new = beta2 * gather(nu) + (1 - beta2) * g32 * g32
        mu_hat = mu_new / (1 - beta1 ** t)
        nu_hat = nu_new / (1 - beta2 ** t)
        new = p32 - lr * (mu_hat / (jnp.sqrt(nu_hat) + eps)
                          + weight_decay * p32)
        return scatter(w, new), scatter(mu, mu_new), scatter(nu, nu_new)
    raise ValueError(kind)


def block_act_prune_ref(x, threshold: float = 0.15, block: int = 2):
    c = x.shape[-1]
    xb = x.reshape(x.shape[:-1] + (c // block, block))
    keep = (jnp.abs(xb).max(axis=-1, keepdims=True) >= threshold)
    return (xb * keep.astype(x.dtype)).reshape(x.shape)


def wkv6_ref(r, k, v, w, u):
    """Sequential RWKV-6 recurrence oracle (the recurrence of models/rwkv6,
    from a zero state): r,k,v,w: [BH, T, D]; u: [D] -> y [BH, T, D] fp32."""
    import jax

    def step(s, rkvw):
        rt, kt, vt, wt = rkvw
        kv = kt[:, :, None] * vt[:, None, :]
        y = jnp.einsum("bd,bde->be", rt, u[None, :, None] * kv + s)
        return wt[:, :, None] * s + kv, y

    bh, t, d = r.shape
    s0 = jnp.zeros((bh, d, d), jnp.float32)
    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0) for x in (r, k, v, w))
    _, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1)
