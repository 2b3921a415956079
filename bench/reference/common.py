"""Pieces shared by the plain references: matmul precision modes, norms,
cross-entropy, the random channel-block draw of the dynamic phase, and the
optimizer rules on selected blocks.

Nothing here imports the program. Every reference computes in float32 with
`precision="highest"` matmuls; the control mode `fp8` rounds both operands
of every matmul to float8 (e4m3, scaled per tensor to its largest
magnitude) before an exact float32 product, which is what a later change
that moved the matmuls to fp8 would compute.
"""
from __future__ import annotations

import zlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

F8_MAX = 448.0   # largest finite float8_e4m3fn


def _fp8(a):
    """a rounded to float8 e4m3 under a per-tensor scale; the gradient
    passes straight through, as in fp8 training that keeps its gradients
    in higher precision."""
    a = a.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    scale = jax.lax.stop_gradient(F8_MAX / amax)
    q = (a * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return a + jax.lax.stop_gradient(q - a)


class Sel(NamedTuple):
    """A weight [in, out] whose selected output blocks carry a trainable
    offset: w + delta on blocks idx. `mm` computes x @ w + the selected
    columns' x @ delta, so the gradient reaches delta without a full-shape
    weight gradient ever existing. A stacked expert weight [E, in, out]
    carries delta [E, in, n_sel, block] under one idx for all experts;
    `expert(w, e)` takes one expert's [in, out] view of either."""
    w: jax.Array
    idx: jax.Array
    delta: jax.Array
    block: int


def expert(w, e: int):
    """Expert e of a stacked expert weight [E, in, out], or of its Sel."""
    if isinstance(w, Sel):
        return Sel(w.w[e], w.idx, w.delta[e], w.block)
    return w[e]


def _einsum(spec, a, b, mode):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def mm(spec: str, a, b, mode: str):
    """einsum in float32 at the highest precision; `mode="fp8"` first rounds
    both operands to scaled float8. `b` may be a `Sel` (then `spec` must be
    `...d,de->...e`)."""
    if not isinstance(b, Sel):
        return _einsum(spec, a, b, mode)
    y = _einsum(spec, a, jax.lax.stop_gradient(b.w), mode)
    d_in, n_sel = b.delta.shape[0], b.delta.shape[1]
    extra = _einsum(spec, a, b.delta.reshape(d_in, n_sel * b.block), mode)
    lead = y.shape[:-1]
    yb = y.reshape(lead + (-1, b.block))
    yb = yb.at[..., b.idx, :].add(extra.reshape(lead + (n_sel, b.block)))
    return yb.reshape(y.shape)


def layernorm(p, x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def rmsnorm(p, x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
    return y * p["scale"].astype(jnp.float32)


def norm(p, x):
    """LayerNorm where the norm's weights hold a bias, else RMSNorm."""
    return layernorm(p, x) if "bias" in p else rmsnorm(p, x)


def mean_cross_entropy(h, w_head, labels, mode: str, chunk: int = 1024):
    """Mean next-token cross-entropy of hidden states h [T, d] under the
    head w_head [d, V], in row blocks so the float32 logits stay small."""
    t = h.shape[0]

    @jax.checkpoint
    def part(h, y):
        logits = mm("td,dv->tv", h, w_head, mode)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    total = jnp.zeros((), jnp.float32)
    for s in range(0, t, chunk):
        total = total + part(h[s:s + chunk], labels[s:s + chunk])
    return total / t


# ---------------------------------------------------------------------------
# the dynamic phase's random channel-block draw
# ---------------------------------------------------------------------------

def block_of(out_dim: int, block_req: int) -> int:
    """Channels per block: the largest divisor of the width up to the
    requested block."""
    for d in range(min(out_dim, block_req), 0, -1):
        if out_dim % d == 0:
            return d
    return 1


def sel_spec(out_dim: int, ratio: float, block_req: int):
    """(block, n_blocks, n_sel) of one selectable weight."""
    block = block_of(out_dim, block_req)
    n_blocks = out_dim // block
    return block, n_blocks, max(1, int(round(ratio * n_blocks)))


def leaf_experts(leaf) -> int:
    """Experts of a selectable leaf (path, in_dim, out_dim[, experts]): 0
    for a plain [in, out] weight."""
    return leaf[3] if len(leaf) > 3 else 0


def draw_selection(state_key, step: int, segment: str, leaves, k_layers: int,
                   ratio: float, block_req: int) -> dict:
    """The selection of one step of the dynamic phase: for each selectable
    leaf (`leaves` is [(path, in_dim, out_dim[, experts])] in the order of
    the sorted leaf paths), n_sel of n_blocks blocks per trainable layer,
    the first n_sel of a uniform random permutation; an expert leaf's draw
    is shared by all its experts. The key is the train state's key folded
    with the step and then with the segment's crc32, split once per leaf.
    Returns {path: int32 [k_layers, n_sel]}."""
    key = jax.random.fold_in(state_key, step)
    key = jax.random.fold_in(key, zlib.crc32(segment.encode()) % 2**31)
    keys = jax.random.split(key, max(1, len(leaves)))
    out = {}
    for k, leaf in zip(keys, leaves):
        _block, n_blocks, n_sel = sel_spec(leaf[2], ratio, block_req)
        u = jax.random.uniform(k, (k_layers, 1, n_blocks))
        out[leaf[0]] = jnp.argsort(u, axis=-1)[:, 0, :n_sel].astype(jnp.int32)
    return out


def gather_blocks(w, idx, block: int):
    """w [..., out] -> the selected blocks [..., n_sel, block]; every
    leading axis (fan-in, experts) is kept."""
    wb = w.reshape(w.shape[:-1] + (-1, block))
    return jnp.take(wb, idx, axis=-2)


def set_blocks(w, idx, vals, block: int):
    wb = w.reshape(w.shape[:-1] + (-1, block))
    return wb.at[..., idx, :].set(vals.astype(w.dtype)).reshape(w.shape)


def rule(opt: dict, t, p, g, mu, nu):
    """One optimizer rule in float32 on the given values (a block gather or
    a whole leaf). t is the 1-based step. Returns (p', mu', nu')."""
    lr = opt["learning_rate"]
    g = g.astype(jnp.float32)
    p = p.astype(jnp.float32)
    if opt["kind"] == "sgd" and opt["momentum"] == 0.0:
        return p - lr * g - lr * opt["weight_decay"] * p, None, None
    if opt["kind"] == "adamw":
        b1, b2 = opt["beta1"], opt["beta2"]
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        mh = mu / (1 - b1 ** t)
        nh = nu / (1 - b2 ** t)
        new = p - lr * (mh / (jnp.sqrt(nh) + opt["eps"])
                        + opt["weight_decay"] * p)
        return new, mu, nu
    raise ValueError(f"optimizer {opt['kind']!r} has no reference rule")
