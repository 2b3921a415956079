"""Pallas TPU kernel: fused compact-path optimizer block update.

PR 1's compact optimizer did gather -> jnp rule -> scatter as three
separate full passes over the selected blocks (three kernel families, three
rounds of HBM traffic). This kernel fuses all three into ONE in-place grid
launch per weight leaf: the BlockSpec index maps ARE the gather (the weight
and optimizer-state inputs are routed straight to their selected column
blocks), the SGD / momentum / AdamW rule runs on the tile in VMEM, and the
aliased outputs ARE the writeback — weights and optimizer state update in
the same pass, touching n_sel/n_blocks of each tensor.

    w:   [K, R, N]                       stacked weight (any float dtype)
    g:   [K, R, n_shards, n_sel, block]  compact gradient (selected blocks)
    idx: [K, n_shards, n_sel]            selected block indices, shard-local
    mu:  [K, R, N] fp32                  first moment (momentum/adamw) or None
    nu:  [K, R, N] fp32                  second moment (adamw) or None
    lr, t: traced fp32 scalars (learning rate, adamw bias-correction step);
           lr and AdamW's bias corrections 1 - beta**t are computed outside
           the kernel (Mosaic has no scalar pow) and scalar-prefetched
           alongside idx.

Returns (w', mu', nu') with None for absent state; every input tensor is
aliased to its output, so unselected blocks are never read or written.

The per-tile arithmetic mirrors `repro.optim.optimizers._leaf_update`
exactly (fp32 compute, cast back to the param dtype): SGD is bitwise
identical to the jnp gather/update/scatter oracle; momentum/AdamW are
allclose (elementwise, so in practice also bitwise).

The compact gradient is read through its row-major flat view
[K, R, n_shards * n_sel * block] (column block `s * n_sel + j`), so every
block's trailing dims are tiles the TPU compiler accepts.

Grid: (K, n_shards, n_sel, R/TR); selection dims are "arbitrary"
(sequential) so a duplicate index cannot race — selection never produces
duplicates within a shard anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rule(kind: str, hp: dict, lr, bc1, bc2, p, g, mu, nu):
    """The optimizer block rule on fp32 tiles; mirrors _leaf_update, with
    the AdamW bias corrections bc = 1 - beta**t passed in."""
    if kind == "sgd":
        new = p - lr * g
        if hp["weight_decay"]:
            new = new - lr * hp["weight_decay"] * p
        return new, None, None
    if kind == "momentum":
        mu_new = hp["momentum"] * mu + g
        new = p - lr * mu_new
        if hp["weight_decay"]:
            new = new - lr * hp["weight_decay"] * p
        return new, mu_new, None
    if kind == "adamw":
        b1, b2 = hp["beta1"], hp["beta2"]
        mu_new = b1 * mu + (1 - b1) * g
        nu_new = b2 * nu + (1 - b2) * g * g
        mu_hat = mu_new / bc1
        nu_hat = nu_new / bc2
        new = p - lr * (mu_hat / (jnp.sqrt(nu_hat) + hp["eps"])
                        + hp["weight_decay"] * p)
        return new, mu_new, nu_new
    raise ValueError(kind)


def _kernel(idx_ref, hyper_ref, *refs, kind: str, hp: dict):
    del idx_ref
    lr, bc1, bc2 = hyper_ref[0], hyper_ref[1], hyper_ref[2]
    n_state = {"sgd": 0, "momentum": 1, "adamw": 2}[kind]
    ins, outs = refs[: 2 + n_state], refs[2 + n_state:]
    w_ref, g_ref = ins[0], ins[1]
    p = w_ref[0].astype(jnp.float32)                 # [TR, block]
    g = g_ref[0].astype(jnp.float32)
    mu = ins[2][0] if n_state >= 1 else None         # fp32 already
    nu = ins[3][0] if n_state >= 2 else None
    new, mu_new, nu_new = _rule(kind, hp, lr, bc1, bc2, p, g, mu, nu)
    outs[0][...] = new.astype(outs[0].dtype)[None]
    if n_state >= 1:
        outs[1][...] = mu_new[None]
    if n_state >= 2:
        outs[2][...] = nu_new[None]


def fused_block_opt_kernel(w, g, idx, lr, t, mu=None, nu=None, *, kind: str,
                           momentum: float = 0.0, beta1: float = 0.9,
                           beta2: float = 0.999, eps: float = 1e-8,
                           weight_decay: float = 0.0, tr: int = 256,
                           interpret: bool = False):
    """One-launch fused block optimizer step; shapes as module doc.

    kind: "sgd" (no state), "momentum" (mu), "adamw" (mu, nu)."""
    k, r, n = w.shape
    n_shards, n_sel = idx.shape[1], idx.shape[2]
    block = g.shape[-1]
    assert g.shape == (k, r, n_shards, n_sel, block)
    assert idx.shape == (k, n_shards, n_sel)
    assert n % (n_shards * block) == 0
    n_blocks = n // (n_shards * block)
    tr = min(tr, r)
    assert r % tr == 0
    n_state = {"sgd": 0, "momentum": 1, "adamw": 2}[kind]
    assert (mu is not None) == (n_state >= 1)
    assert (nu is not None) == (n_state >= 2)

    t = jnp.asarray(t, jnp.float32)
    hyper = jnp.stack([jnp.asarray(lr, jnp.float32),
                       1 - beta1 ** t, 1 - beta2 ** t])
    full_spec = pl.BlockSpec(
        (1, tr, block),
        lambda kk, si, ji, ri, idx_ref, hyper_ref:
        (kk, ri, si * n_blocks + idx_ref[kk, si, ji]))
    g_spec = pl.BlockSpec(
        (1, tr, block),
        lambda kk, si, ji, ri, idx_ref, hyper_ref:
        (kk, ri, si * n_sel + ji))

    g_flat = g.reshape(k, r, n_shards * n_sel * block)
    operands = [w, g_flat] + [s for s in (mu, nu) if s is not None]
    in_specs = [full_spec, g_spec] + [full_spec] * n_state
    out_specs = [full_spec] * (1 + n_state)
    out_shape = [jax.ShapeDtypeStruct((k, r, n), w.dtype)] \
        + [jax.ShapeDtypeStruct((k, r, n), jnp.float32)] * n_state
    # operand numbering includes the two scalar-prefetch args (idx, hyper):
    # w is operand 2, mu 4, nu 5 -> aliased onto outputs 0, 1, 2.
    aliases = {2: 0}
    if n_state >= 1:
        aliases[4] = 1
    if n_state >= 2:
        aliases[5] = 2

    hp = {"momentum": momentum, "beta1": beta1, "beta2": beta2, "eps": eps,
          "weight_decay": weight_decay}
    grid = (k, n_shards, n_sel, r // tr)
    out = pl.pallas_call(
        functools.partial(_kernel, kind=kind, hp=hp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=tuple(out_specs),
        ),
        out_shape=tuple(out_shape),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary",
                                 "parallel")),
        interpret=interpret,
        name="fused_block_opt",
    )(idx, hyper, *operands)
    w_new = out[0]
    mu_new = out[1] if n_state >= 1 else None
    nu_new = out[2] if n_state >= 2 else None
    return w_new, mu_new, nu_new
