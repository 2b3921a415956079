"""Plain reference of RWKV-6 "Finch" (arXiv:2404.05892) as the benchmark's
configuration states it, in float32 `jax.numpy` with no kernels, cache or
batching tricks.

Block (pre-norm, residual):
    x += time_mix(LN(x));  x += channel_mix(LN(x))
time_mix, per head of size D, with token shift x' = x_{t-1} (0 at t=0):
    x_c = x + (x' - x) * mu_c                  c in r, k, v, g, w
    r, k, v, g = x_r Wr, x_k Wk, x_v Wv, x_g Wg
    w_t = exp(-exp(w0 + tanh(x_w A) B))        data-dependent decay
    y_t = r_t (diag(u) k_t v_t^T + S_{t-1});   S_t = diag(w_t) S_{t-1} + k_t v_t^T
    out = (LN_x(y) * silu(g)) Wo
channel_mix:
    out = sigmoid(x_r Wr') * (relu(x_k Wk')^2 Wv')
The embedding is followed by a LayerNorm (ln0); the head is LN then x W_head.

Departures from the published block, as the configuration records under
`assumed`: the five token-shift interpolations use a learned per-channel mu
(RWKV-5 style) and not Finch's data-dependent lerp; the decay's low-rank
path has rank 64.

The parameter tree is laid out as the program's checkpoint format (stacked
layers under segments/blocks), so the benchmark can hand the same weights to
both. The benchmark makes the weights; this module only reads them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import layernorm, mm

SEGMENT = "blocks"
DECAY_LORA = 64


def heads(m) -> int:
    return m["d_model"] // m["rwkv_head_dim"]


def leaf_specs(m) -> dict:
    """{path: (shape, dtype, init)} of every parameter; segment leaves carry
    the layer axis first. init: ("normal", std) | ("uniform", lo, hi) |
    ("const", v)."""
    d, ff, v, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    h, hd = heads(m), m["rwkv_head_dim"]
    bf, f32 = jnp.dtype(m["dtype"]), jnp.float32
    w = lambda i, o: ((L, i, o), bf, ("normal", i ** -0.5))
    norm = lambda dt, lead=(L,): {("scale",): (lead + (d,), dt, ("const", 1.0)),
                                  ("bias",): (lead + (d,), dt, ("const", 0.0))}
    out = {("embed", "tok"): ((v, d), bf, ("normal", 0.02)),
           ("lm_head", "w"): ((d, v), bf, ("normal", d ** -0.5))}
    for name, lead in (("ln0", ()), ("final_norm", ())):
        for k, s in norm(bf, lead).items():
            out[(name,) + k] = s
    seg = ("segments", SEGMENT)
    for ln in ("time_ln", "chan_ln"):
        for k, s in norm(bf).items():
            out[seg + (ln,) + k] = s
    t = seg + ("time",)
    out[t + ("mu",)] = ((L, 5, d), f32, ("uniform", 0.0, 1.0))
    for name in ("wr", "wk", "wv", "wg", "wo"):
        out[t + (name,)] = w(d, d)
    out[t + ("w0",)] = ((L, d), f32, ("uniform", -8.0, -4.0))
    out[t + ("wA",)] = ((L, d, DECAY_LORA), f32, ("normal", d ** -0.5))
    out[t + ("wB",)] = ((L, DECAY_LORA, d), f32,
                        ("normal", 0.1 * DECAY_LORA ** -0.5))
    out[t + ("u",)] = ((L, h, hd), f32, ("normal", 0.1))
    for k, s in norm(f32).items():
        out[t + ("ln_x",) + k] = s
    c = seg + ("chan",)
    out[c + ("mu",)] = ((L, 2, d), f32, ("uniform", 0.0, 1.0))
    out[c + ("wk",)] = w(d, ff)
    out[c + ("wv",)] = w(ff, d)
    out[c + ("wr",)] = w(d, d)
    return out


def selectable_leaves(m) -> list:
    """[(path within a layer, in_dim, out_dim)] of the weights whose output
    channel blocks the sparse update selects, in sorted path order."""
    d, ff = m["d_model"], m["d_ff"]
    leaves = [(("chan", "wk"), d, ff), (("chan", "wr"), d, d),
              (("chan", "wv"), ff, d)]
    leaves += [(("time", n), d, d) for n in ("wg", "wk", "wo", "wr", "wv")]
    return sorted(leaves)


def first_layer_input_matmuls(m) -> list:
    """Matmuls of a layer that read the layer's input directly: their input
    gradient is not required in the first trainable layer."""
    d = m["d_model"]
    return [(d, d)] * 4 + [(d, DECAY_LORA)]


def embed(params, tokens, mode):
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    return layernorm(params["ln0"], x)


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, w, u, chunk: int = 32):
    """r, k, v, w: [B, S, H, D]; token by token, remat per chunk."""
    b, s, h, d = r.shape
    s0 = jnp.zeros((b, h, d, d), jnp.float32)

    def tok(st, rkvw):
        rt, kt, vt, wt = rkvw
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhd,bhde->bhe", rt, u[None, :, :, None] * kv + st,
                       precision=jax.lax.Precision.HIGHEST)
        return wt[..., :, None] * st + kv, y

    def blk(st, xs):
        return jax.lax.scan(tok, st, xs)

    q = min(chunk, s)
    n = s // q
    split = lambda t: t.transpose(1, 0, 2, 3).reshape(n, q, b, h, d)
    _, ys = jax.lax.scan(jax.checkpoint(blk), s0,
                         tuple(split(t) for t in (r, k, v, w)))
    return ys.reshape(s, b, h, d).transpose(1, 0, 2, 3)


def _time_mix(m, x, mode, get):
    b, s, d = x.shape
    hd = m["rwkv_head_dim"]
    xp = _shift(x)
    mu = get("mu").astype(jnp.float32)
    xr, xk, xv, xg, xw = (x + (xp - x) * mu[i] for i in range(5))
    r = mm("bsd,de->bse", xr, get("wr"), mode).reshape(b, s, -1, hd)
    k = mm("bsd,de->bse", xk, get("wk"), mode).reshape(b, s, -1, hd)
    v = mm("bsd,de->bse", xv, get("wv"), mode).reshape(b, s, -1, hd)
    g = mm("bsd,de->bse", xg, get("wg"), mode)
    lora = mm("bsr,re->bse", jnp.tanh(mm("bsd,dr->bsr", xw, get("wA"), mode)),
              get("wB"), mode)
    w = jnp.exp(-jnp.exp(get("w0").astype(jnp.float32) + lora))
    y = _wkv(r, k, v, w.reshape(b, s, -1, hd), get("u").astype(jnp.float32))
    y = layernorm({"scale": get("ln_x", "scale"), "bias": get("ln_x", "bias")},
                  y.reshape(b, s, d))
    return mm("bsd,de->bse", y * jax.nn.silu(g), get("wo"), mode)


def _chan_mix(m, x, mode, get):
    xp = _shift(x)
    mu = get("mu").astype(jnp.float32)
    xk = x + (xp - x) * mu[0]
    xr = x + (xp - x) * mu[1]
    k = jnp.square(jax.nn.relu(mm("bsd,df->bsf", xk, get("wk"), mode)))
    kv = mm("bsf,fd->bsd", k, get("wv"), mode)
    return jax.nn.sigmoid(mm("bsd,de->bse", xr, get("wr"), mode)) * kv


def layer(m, get, x, mode):
    """One block. `get(*path)` returns the layer's leaf at `path`."""
    h = layernorm({"scale": get("time_ln", "scale"),
                   "bias": get("time_ln", "bias")}, x)
    x = x + _time_mix(m, h, mode, lambda *k: get("time", *k))
    h = layernorm({"scale": get("chan_ln", "scale"),
                   "bias": get("chan_ln", "bias")}, x)
    return x + _chan_mix(m, h, mode, lambda *k: get("chan", *k))


def head_weight(params):
    return params["lm_head"]["w"]


def flops_per_token(m, seq: int, k_train: int, ratio: float,
                    block_req: int) -> dict:
    """Required FLOPs per token of one sparse training step: the forward of
    every layer and the head; the input gradients through the trainable
    suffix and the head (not into the suffix's own input); the weight
    gradients of the selected blocks and of the suffix's dense leaves.
    Recompute is not counted. The wkv recurrence counts 4 D^2 per head and
    token forward (state update and read-out) and twice that backward."""
    from bench.reference.common import sel_spec
    d, ff, v, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    h, hd = heads(m), m["rwkv_head_dim"]
    mats = 5 * d * d + 2 * d * DECAY_LORA + d * ff + ff * d + d * d
    wkv = 4 * h * hd * hd
    fwd = L * (2 * mats + wkv) + 2 * d * v
    first = sum(i * o for i, o in first_layer_input_matmuls(m))
    dx = k_train * (2 * mats + 2 * wkv) - 2 * first + 2 * d * v
    dw_sel = 0
    for _path, i, o in selectable_leaves(m):
        block, n_blocks, n_sel = sel_spec(o, ratio, block_req)
        dw_sel += 2 * i * n_sel * block
    dense_w = 2 * (2 * d * DECAY_LORA)            # wA, wB
    dw = k_train * (dw_sel + dense_w)
    return {"forward": fwd, "backward": dx + dw, "total": fwd + dx + dw}
