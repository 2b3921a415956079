"""Plain reference of the program's `moe` family: a pre-norm decoder in the
layout of DeepSeekMoE 16B (Dai et al., arXiv:2401.06066), in float32
`jax.numpy` with no kernels, cache or dispatch buffers.

Layout (`moe.layout` "all_but_first", DeepSeekMoE's): one leading dense
layer (segment `first`), then layers whose MLP is a mixture of experts
(segment `blocks`).

Block (pre-norm, residual):
    x += Attn(N(x));  x += F(N(x))
Attn: causal attention with H query and G key/value heads of size D (query
head i reads key/value head i // (H / G)), rotate-half RoPE on q and k at
theta^(-2j/D), softmax(q k^T / sqrt(D)), output projection Wo; the pieces of
bench/reference/dense_lm.py. N: RMSNorm (or LayerNorm, as `norm_kind`).
F in the dense layer: SwiGLU, (silu(x Wg) * x Wu) Wd.
F in an expert layer, per token x:
    p = softmax(x Wr)                     router over all E experts
    (w, ids) = top-k of p;  w /= sum(w)   the top-k weights renormalised
    F(x) = sum_i w_i E_{ids_i}(x) + S(x)
with each routed expert E_e a SwiGLU of width d_ff and S the shared experts,
one SwiGLU of width num_shared_experts * d_ff. Every expert is applied to
every token and weighted by its gate, which is zero where the token is not
routed: no token is ever dropped, and an expert's gradient comes only from
the tokens routed to it.
Loss: the mean next-token cross-entropy plus, summed over the expert
layers, 0.01 * E * sum_e f_e P_e (f_e the share of the T * k routing slots
that chose expert e, P_e the mean router probability of e over the T
tokens) and 1e-3 * the mean over tokens of logsumexp(x Wr)^2, the weights
of the program's loss_fn. Both are over the whole batch: the layers return
their per-row sums and `aux_loss` combines them.

Departures from DeepSeekMoE, all as the program builds the family:
- the leading dense layer's width is 8 * d_ff (DeepSeekMoE 16B: 10944,
  not 8 * 1408);
- the balance loss is one switch-style term per layer at weight 0.01, not
  DeepSeekMoE's expert-level and device-level terms at their own weights,
  and a router z-loss is added;
- RMSNorm's epsilon is 1e-6;
- an expert's weights are stacked [E, in, out], and the sparse update
  selects one set of output channel blocks per layer for all experts.

The parameter tree is laid out as the program's checkpoint format (stacked
layers under segments/<name>), so the benchmark can hand the same weights
to both. The benchmark makes the weights; this module only reads them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import dense_lm
from bench.reference.common import expert, mm, norm, sel_spec

AUX_WEIGHT = 0.01
Z_WEIGHT = 1e-3
DENSE_WIDTH = 8          # the leading dense layer's width, in units of d_ff


def head_dim(m) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def segments(m) -> list:
    """The program's segments in order: [(name, layers)]."""
    layout = m["moe"].get("layout", "all")
    if layout != "all_but_first":
        raise ValueError(f"moe layout {layout!r} has no reference")
    return [("first", 1), ("blocks", m["num_layers"] - 1)]


def leaf_specs(m) -> dict:
    """{path: (shape, dtype, init)} of every parameter; segment leaves carry
    the layer axis first."""
    d, ff, v = m["d_model"], m["d_ff"], m["vocab_size"]
    e, ns = m["moe"]["num_experts"], m["moe"].get("num_shared_experts", 0)
    hd = head_dim(m)
    hq, hkv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    bf = jnp.dtype(m["dtype"])
    lnorm = m["norm_kind"] == "layernorm"
    out = {("embed", "tok"): ((v, d), bf, ("normal", 0.02)),
           ("lm_head", "w"): ((d, v), bf, ("normal", d ** -0.5)),
           ("final_norm", "scale"): ((d,), bf, ("const", 1.0))}
    if lnorm:
        out[("final_norm", "bias")] = ((d,), bf, ("const", 0.0))
    for name, n in segments(m):
        seg = ("segments", name)
        w = lambda *s: ((n,) + s, bf, ("normal", s[-2] ** -0.5))
        for ln in ("attn_ln", "mlp_ln"):
            out[seg + (ln, "scale")] = ((n, d), bf, ("const", 1.0))
            if lnorm:
                out[seg + (ln, "bias")] = ((n, d), bf, ("const", 0.0))
        a = seg + ("attn",)
        out[a + ("wq",)] = w(d, hq)
        out[a + ("wk",)] = w(d, hkv)
        out[a + ("wv",)] = w(d, hkv)
        out[a + ("wo",)] = w(hq, d)
        if name == "first":
            f = DENSE_WIDTH * ff
            out[seg + ("mlp", "w_gate")] = w(d, f)
            out[seg + ("mlp", "w_up")] = w(d, f)
            out[seg + ("mlp", "w_down")] = w(f, d)
            continue
        x = seg + ("moe",)
        out[x + ("router",)] = ((n, d, e), jnp.dtype(jnp.float32),
                                ("normal", d ** -0.5))
        out[x + ("w_gate",)] = w(e, d, ff)
        out[x + ("w_up",)] = w(e, d, ff)
        out[x + ("w_down",)] = w(e, ff, d)
        if ns:
            out[x + ("shared", "w_gate")] = w(d, ns * ff)
            out[x + ("shared", "w_up")] = w(d, ns * ff)
            out[x + ("shared", "w_down")] = w(ns * ff, d)
    return out


def selectable_leaves(m) -> list:
    """[(path within a layer, in_dim, out_dim[, experts])] of the weights of
    the last segment whose output channel blocks the sparse update selects,
    in sorted path order; an expert weight gives its expert count."""
    d, ff = m["d_model"], m["d_ff"]
    e, ns = m["moe"]["num_experts"], m["moe"].get("num_shared_experts", 0)
    hd = head_dim(m)
    hq, hkv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    leaves = [(("attn", "wq"), d, hq), (("attn", "wk"), d, hkv),
              (("attn", "wv"), d, hkv), (("attn", "wo"), hq, d),
              (("moe", "w_gate"), d, ff, e), (("moe", "w_up"), d, ff, e),
              (("moe", "w_down"), ff, d, e)]
    if ns:
        leaves += [(("moe", "shared", "w_gate"), d, ns * ff),
                   (("moe", "shared", "w_up"), d, ns * ff),
                   (("moe", "shared", "w_down"), ns * ff, d)]
    return sorted(leaves)


def expert_rows(m, mix) -> int:
    """Rows per expert that the program's dispatch hands the expert kernels
    for one step's batch (its capacity, filled or not):
    max(8, min(T k cf / E + 1, T k)) for the T tokens of the batch."""
    moe = m["moe"]
    t, k = mix["batch"] * mix["seq"], moe["top_k"]
    c = int(t * k * moe.get("capacity_factor", 1.25) / moe["num_experts"]) + 1
    return max(8, min(c, t * k))


def embed(params, tokens, mode):
    return jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)


def _norm(m, get, name, x):
    p = {"scale": get(name, "scale")}
    if m["norm_kind"] == "layernorm":
        p["bias"] = get(name, "bias")
    return norm(p, x)


def _attn(m, get, x, mode):
    b, s, _d = x.shape
    hd = head_dim(m)
    h = _norm(m, get, "attn_ln", x)
    q, k, v = (mm("bsd,de->bse", h, get("attn", n), mode).reshape(b, s, -1, hd)
               for n in ("wq", "wk", "wv"))
    theta = m["rope_theta"]
    o = dense_lm._attention(dense_lm._rope(q, theta), dense_lm._rope(k, theta),
                            v, mode)
    return x + mm("bse,ed->bsd", o, get("attn", "wo"), mode)


def _swiglu(x, wg, wu, wd, mode):
    h = jax.nn.silu(mm("...d,df->...f", x, wg, mode)) * mm(
        "...d,df->...f", x, wu, mode)
    return mm("...f,fd->...d", h, wd, mode)


def dense_layer(m, get, x, mode):
    """The leading dense layer: (x, no sums)."""
    x = _attn(m, get, x, mode)
    h = _norm(m, get, "mlp_ln", x)
    return x + _swiglu(h, get("mlp", "w_gate"), get("mlp", "w_up"),
                       get("mlp", "w_down"), mode), {}


def moe_layer(m, get, x, mode):
    """An expert layer: (x, its sums over the row's tokens: `count` of
    routing slots per expert, `prob` of router probability per expert, `z`
    of logsumexp(logits)^2)."""
    moe = m["moe"]
    e, k = moe["num_experts"], moe["top_k"]
    x = _attn(m, get, x, mode)
    h = _norm(m, get, "mlp_ln", x)
    b, s, d = h.shape
    t = h.reshape(b * s, d)
    logits = mm("td,de->te", t, get("moe", "router"), mode)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    gates = jnp.zeros((b * s, e), jnp.float32).at[
        jnp.arange(b * s)[:, None], ids].add(w)
    y = jnp.zeros((b * s, d), jnp.float32)
    for i in range(e):
        y = y + gates[:, i:i + 1] * _swiglu(
            t, expert(get("moe", "w_gate"), i), expert(get("moe", "w_up"), i),
            expert(get("moe", "w_down"), i), mode)
    if moe.get("num_shared_experts", 0):
        y = y + _swiglu(t, get("moe", "shared", "w_gate"),
                        get("moe", "shared", "w_up"),
                        get("moe", "shared", "w_down"), mode)
    sums = {"count": jax.nn.one_hot(ids, e, dtype=jnp.float32).sum((0, 1)),
            "prob": probs.sum(0),
            "z": jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)))}
    return x + y.reshape(b, s, d), sums


LAYERS = {"first": dense_layer, "blocks": moe_layer}


def aux_loss(m, sums: list, tokens: int):
    """The loss's router terms from every layer's sums over the batch's
    `tokens` tokens (a dense layer's sums are empty)."""
    e, k = m["moe"]["num_experts"], m["moe"]["top_k"]
    total = jnp.zeros((), jnp.float32)
    for s in sums:
        if not s:
            continue
        balance = e * jnp.sum((s["count"] / (tokens * k))
                              * (s["prob"] / tokens))
        total = total + AUX_WEIGHT * balance + Z_WEIGHT * s["z"] / tokens
    return total


def head_weight(params):
    return params["lm_head"]["w"]


def flops_per_token(m, seq: int, k_train: int, ratio: float,
                    block_req: int) -> dict:
    """Required FLOPs per token of one sparse training step, as for
    dense_lm: the forward of every layer (top_k routed experts, the shared
    experts and the router in each expert layer) and the head; input
    gradients through the trainable suffix and the head (not into the
    suffix's own input); weight gradients of the selected blocks (an expert
    leaf's over the top_k experts each token reaches) and of the router.
    Causal attention counts 2 * 2 * (S + 1) / 2 * H * D per token forward
    and twice that backward."""
    d, ff, v = m["d_model"], m["d_ff"], m["vocab_size"]
    moe = m["moe"]
    e, k = moe["num_experts"], moe["top_k"]
    ns = moe.get("num_shared_experts", 0)
    hd = head_dim(m)
    hq, hkv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    attn_w = 2 * d * hq + 2 * d * hkv
    attn = 2 * 2 * (seq + 1) / 2 * hq
    mats = {"first": attn_w + 3 * d * DENSE_WIDTH * ff,
            "blocks": attn_w + d * e + 3 * d * ff * (k + ns)}
    fwd = sum(n * (2 * mats[name] + attn) for name, n in segments(m))
    fwd += 2 * d * v
    first = d * hq + 2 * d * hkv
    dx = k_train * (2 * mats["blocks"] + 2 * attn) - 2 * first + 2 * d * v
    dw = 2 * d * e                                   # the router, dense
    for leaf in selectable_leaves(m):
        block, _n_blocks, n_sel = sel_spec(leaf[2], ratio, block_req)
        dw += 2 * leaf[1] * n_sel * block * (k if len(leaf) > 3 else 1)
    dw *= k_train
    return {"forward": fwd, "backward": dx + dw, "total": fwd + dx + dw}
