"""Plain reference of a dense pre-norm decoder as Nemotron-4 15B
(arXiv:2402.16819) describes it, in float32 `jax.numpy` with no kernels or
cache.

Block (pre-norm, residual):
    x += Attn(LN(x));  x += MLP(LN(x))
Attn: grouped-query causal attention, H query heads and G key/value heads of
size D (query head i reads key/value head i // (H / G)); RoPE on q and k
with the rotate-half pairing (channel j with j + D/2) at frequency
theta^(-2j/D); softmax(q k^T / sqrt(D)); output projection Wo.
MLP: squared ReLU, relu(x W_up)^2 W_down. LayerNorm with scale and bias.
Untied embedding and head; the head is a final LN then x W_head.

The parameter tree is laid out as the program's checkpoint format (stacked
layers under segments/blocks), so the benchmark can hand the same weights to
both. The benchmark makes the weights; this module only reads them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference.common import layernorm, mm

SEGMENT = "blocks"
Q_CHUNK = 512


def head_dim(m) -> int:
    return m["d_model"] // m["num_heads"]


def leaf_specs(m) -> dict:
    d, ff, v, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    hd = head_dim(m)
    hq, hkv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    bf = jnp.dtype(m["dtype"])
    w = lambda i, o: ((L, i, o), bf, ("normal", i ** -0.5))
    out = {("embed", "tok"): ((v, d), bf, ("normal", 0.02)),
           ("lm_head", "w"): ((d, v), bf, ("normal", d ** -0.5)),
           ("final_norm", "scale"): ((d,), bf, ("const", 1.0)),
           ("final_norm", "bias"): ((d,), bf, ("const", 0.0))}
    seg = ("segments", SEGMENT)
    for ln in ("attn_ln", "mlp_ln"):
        out[seg + (ln, "scale")] = ((L, d), bf, ("const", 1.0))
        out[seg + (ln, "bias")] = ((L, d), bf, ("const", 0.0))
    a = seg + ("attn",)
    out[a + ("wq",)] = w(d, hq)
    out[a + ("wk",)] = w(d, hkv)
    out[a + ("wv",)] = w(d, hkv)
    out[a + ("wo",)] = w(hq, d)
    out[seg + ("mlp", "w_up")] = w(d, ff)
    out[seg + ("mlp", "w_down")] = w(ff, d)
    return out


def selectable_leaves(m) -> list:
    d, ff = m["d_model"], m["d_ff"]
    hd = head_dim(m)
    hq, hkv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    return sorted([(("attn", "wq"), d, hq), (("attn", "wk"), d, hkv),
                   (("attn", "wv"), d, hkv), (("attn", "wo"), hq, d),
                   (("mlp", "w_up"), d, ff), (("mlp", "w_down"), ff, d)])


def first_layer_input_matmuls(m) -> list:
    d = m["d_model"]
    hd = head_dim(m)
    return [(d, m["num_heads"] * hd), (d, m["num_kv_heads"] * hd),
            (d, m["num_kv_heads"] * hd)]


def embed(params, tokens, mode):
    return jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)


def _rope(x, theta):
    """x [B, S, H, D]: rotate-half RoPE at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs     # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, mode):
    """q [B, S, H, D], k/v [B, S, G, D]: causal GQA in query blocks."""
    b, s, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(b, s, g, h // g, d)

    @partial(jax.checkpoint, static_argnums=(3,))
    def block(qc, k, v, start):
        c = qc.shape[1]
        sc = mm("bqgrd,bkgd->bgrqk", qc, k, mode) / jnp.sqrt(jnp.float32(d))
        mask = (jnp.arange(s)[None, :]
                <= start + jnp.arange(c)[:, None])            # [q, k]
        sc = jnp.where(mask, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return mm("bgrqk,bkgd->bqgrd", pr, v, mode)

    outs = [block(q[:, i:i + Q_CHUNK], k, v, i) for i in range(0, s, Q_CHUNK)]
    return jnp.concatenate(outs, axis=1).reshape(b, s, h * d)


def layer(m, get, x, mode):
    b, s, d = x.shape
    hd = head_dim(m)
    h = layernorm({"scale": get("attn_ln", "scale"),
                   "bias": get("attn_ln", "bias")}, x)
    q = mm("bsd,de->bse", h, get("attn", "wq"), mode).reshape(b, s, -1, hd)
    k = mm("bsd,de->bse", h, get("attn", "wk"), mode).reshape(b, s, -1, hd)
    v = mm("bsd,de->bse", h, get("attn", "wv"), mode).reshape(b, s, -1, hd)
    theta = m["rope_theta"]
    o = _attention(_rope(q, theta), _rope(k, theta), v, mode)
    x = x + mm("bse,ed->bsd", o, get("attn", "wo"), mode)
    h = layernorm({"scale": get("mlp_ln", "scale"),
                   "bias": get("mlp_ln", "bias")}, x)
    u = jnp.square(jax.nn.relu(mm("bsd,df->bsf", h, get("mlp", "w_up"), mode)))
    return x + mm("bsf,fd->bsd", u, get("mlp", "w_down"), mode)


def head_weight(params):
    return params["lm_head"]["w"]


def flops_per_token(m, seq: int, k_train: int, ratio: float,
                    block_req: int) -> dict:
    """Required FLOPs per token of one sparse training step, as for rwkv6:
    forward of every layer and the head; input gradients through the
    trainable suffix and the head (not into the suffix's own input); weight
    gradients of the selected blocks. Causal attention counts
    2 * 2 * (S + 1) / 2 * H * D per token forward (scores and values over
    the average visible prefix) and twice that backward."""
    from bench.reference.common import sel_spec
    d, ff, v, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    hd = head_dim(m)
    hq, hkv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    mats = d * hq + 2 * d * hkv + hq * d + 2 * d * ff
    attn = 2 * 2 * (seq + 1) / 2 * hq
    fwd = L * (2 * mats + attn) + 2 * d * v
    first = sum(i * o for i, o in first_layer_input_matmuls(m))
    dx = k_train * (2 * mats + 2 * attn) - 2 * first + 2 * d * v
    dw = 0
    for _path, i, o in selectable_leaves(m):
        block, _n_blocks, n_sel = sel_spec(o, ratio, block_req)
        dw += 2 * i * n_sel * block
    dw *= k_train
    return {"forward": fwd, "backward": dx + dw, "total": fwd + dx + dw}
