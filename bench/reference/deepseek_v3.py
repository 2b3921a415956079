"""Plain reference of the DeepSeek-V3 block as Moonlight-16B-A3B configures it
(huggingface.co/moonshotai/Moonlight-16B-A3B, config.json; DeepSeek-V3
technical report, arXiv:2412.19437, sections 2.1.1-2.1.2), in float32
`jax.numpy` with no kernels, cache or dispatch buffers, and this chip's
share of an expert-parallel layer.

Layout: one leading dense layer (segment `first`), then expert layers
(segment `blocks`). Block (pre-norm, residual), N RMSNorm:
    x += MLA(N(x));  x += F(N(x))
MLA, H heads, no query LoRA, nope / rope / value head sizes dn, dr, dv,
latent rank r:
    q = x Wq                         [H, dn + dr] per token
    [c | k_pe] = x Wkv_a;  c = N(c)  [r], [dr]
    [k_nope | v] = c Wkv_b           [H, dn + dv] per token
    q_pe, k_pe: rotate-half RoPE at theta^(-2j/dr); k_pe is one head
    shared by all heads; k = [k_nope | k_pe], q = [q_nope | q_pe]
    o = softmax(q k^T / sqrt(dn + dr)) v, causal, in query blocks; out o Wo
F in the dense layer: SwiGLU of width `moe.dense_d_ff`.
F in an expert layer, per token x (R routed experts, k per token):
    s = sigmoid(x Wr)                         float32, all R experts
    ids = top-k of s + b                      b: the layer's correction
                                              bias; it picks, never weights
    w_i = s_ids_i / sum_j s_ids_j * routed_scaling
    F(x) = sum over the held experts e of ids: w_e E_e(x)  +  S(x)
with each expert a SwiGLU of width d_ff and S the shared experts, one
SwiGLU of width num_shared_experts * d_ff. This chip holds experts
first_expert .. first_expert + num_experts - 1; what the other experts add
is left out, as in the program. Every held expert is applied to every
token and weighted by its gate, zero where the token is not routed to it:
no token is dropped.
Loss: the mean next-token cross-entropy plus, over the expert layers,
alpha * DeepSeek-V3's sequence-wise balance loss: per row
sum_i f_i P_i, f_i = R / (k S) * the row's slots on expert i (of all R),
P_i the row's mean of s_i / sum_j s_j; averaged over rows. A layer returns
the row's term and a row count; `aux_loss` takes their ratio.

Departures from the published model, all as the program builds it:
- the correction bias is held fixed (the auxiliary-loss-free balancing
  rule that moves it between steps is not applied while fine-tuning);
- RoPE pairs dimension j with j + dr/2 (rotate-half), where the published
  weights pair neighbours: the same model up to a fixed permutation of
  Wq's and Wkv_a's rope columns;
- the harness's final norm (bench/reference/train_ref.py) has epsilon 1e-6,
  the program's the published 1e-5 (every other norm here is at
  `norm_eps`): a relative gap near 1e-6 at the head's input;
- an expert's weights are stacked [E, in, out], and the sparse update
  selects one set of output channel blocks per layer for all experts.

The parameter tree is laid out as the program's checkpoint format (stacked
layers under segments/<name>), so the benchmark can hand the same weights
to both. The benchmark makes the weights; this module only reads them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import dense_lm, moe_lm
from bench.reference.common import expert, mm, rmsnorm, sel_spec

Q_CHUNK = 512
BIAS = 0.1      # the correction bias's weights: uniform in [-BIAS, BIAS]

segments = moe_lm.segments
embed = moe_lm.embed
head_weight = moe_lm.head_weight


def _dims(m):
    """(H, dn, dr, dv, r)."""
    return (m["num_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"], m["kv_lora_rank"])


def _experts(m):
    """(held, routed, top_k, shared, first held)."""
    moe = m["moe"]
    e = moe["num_experts"]
    return (e, moe.get("num_routed_experts") or e, moe["top_k"],
            moe.get("num_shared_experts", 0), moe.get("first_expert", 0))


def _attn_mats(m) -> list:
    """(in, out) of the attention's weights, input projections first."""
    d = m["d_model"]
    h, dn, dr, dv, r = _dims(m)
    return [(d, h * (dn + dr)), (d, r + dr), (r, h * (dn + dv)), (h * dv, d)]


def leaf_specs(m) -> dict:
    """{path: (shape, dtype, init)} of every parameter; segment leaves carry
    the layer axis first."""
    d, ff, v = m["d_model"], m["d_ff"], m["vocab_size"]
    e, routed, _k, ns, _first = _experts(m)
    r = m["kv_lora_rank"]
    bf = jnp.dtype(m["dtype"])
    f32 = jnp.dtype(jnp.float32)
    out = {("embed", "tok"): ((v, d), bf, ("normal", 0.02)),
           ("lm_head", "w"): ((d, v), bf, ("normal", d ** -0.5)),
           ("final_norm", "scale"): ((d,), bf, ("const", 1.0))}
    for name, n in segments(m):
        seg = ("segments", name)
        w = lambda *s: ((n,) + s, bf, ("normal", s[-2] ** -0.5))
        for ln in ("attn_ln", "mlp_ln"):
            out[seg + (ln, "scale")] = ((n, d), bf, ("const", 1.0))
        a = seg + ("attn",)
        for leaf, shape in zip(("wq", "wkv_a", "wkv_b", "wo"), _attn_mats(m)):
            out[a + (leaf,)] = w(*shape)
        out[a + ("kv_a_norm", "scale")] = ((n, r), bf, ("const", 1.0))
        if name == "first":
            f = m["moe"]["dense_d_ff"]
            out[seg + ("mlp", "w_gate")] = w(d, f)
            out[seg + ("mlp", "w_up")] = w(d, f)
            out[seg + ("mlp", "w_down")] = w(f, d)
            continue
        x = seg + ("moe",)
        out[x + ("router",)] = ((n, d, routed), f32, ("normal", d ** -0.5))
        out[x + ("score_bias",)] = ((n, routed), f32,
                                    ("uniform", -BIAS, BIAS))
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
    e, _routed, _k, ns, _first = _experts(m)
    leaves = [(("attn", leaf), i, o) for leaf, (i, o) in
              zip(("wq", "wkv_a", "wkv_b", "wo"), _attn_mats(m))]
    leaves += [(("moe", "w_gate"), d, ff, e), (("moe", "w_up"), d, ff, e),
               (("moe", "w_down"), ff, d, e)]
    if ns:
        leaves += [(("moe", "shared", "w_gate"), d, ns * ff),
                   (("moe", "shared", "w_up"), d, ns * ff),
                   (("moe", "shared", "w_down"), ns * ff, d)]
    return sorted(leaves)


def expert_rows(m, mix) -> int:
    """Mean rows per held expert in one step's dispatch: T k / R for the T
    tokens of the batch (the dispatch is dropless; each held expert's rows
    are its routed tokens)."""
    _e, routed, k, _ns, _first = _experts(m)
    return mix["batch"] * mix["seq"] * k // routed


def _norm(m, scale, x):
    return rmsnorm({"scale": scale}, x, m.get("norm_eps", 1e-6))


def _attention(q, k, v, mode):
    """q, k [B, S, H, Dq], v [B, S, H, Dv]: causal attention in query
    blocks, scale 1/sqrt(Dq) -> [B, S, H Dv]. The blocks go through one
    loop body (`lax.map`), so the program compiles one block, not S / 512."""
    b, s, h, dq = q.shape
    c = min(Q_CHUNK, s)

    @jax.checkpoint
    def block(args):
        qc, start = args
        sc = mm("bqhd,bkhd->bhqk", qc, k, mode) / jnp.sqrt(jnp.float32(dq))
        mask = jnp.arange(s)[None, :] <= start + jnp.arange(c)[:, None]
        sc = jnp.where(mask, sc, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v, mode)

    n = -(-s // c)
    qp = jnp.pad(q, ((0, 0), (0, n * c - s), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qp.reshape(b, n, c, h, dq), 1, 0)
    out = jax.lax.map(block, (qb, jnp.arange(0, n * c, c)))  # [n, B, c, H, Dv]
    return jnp.moveaxis(out, 0, 1).reshape(b, n * c, -1)[:, :s]


def _mla(m, get, x, mode):
    b, s, _d = x.shape
    h, dn, dr, dv, r = _dims(m)
    theta = m["rope_theta"]
    hn = _norm(m, get("attn_ln", "scale"), x)
    q = mm("bsd,de->bse", hn, get("attn", "wq"), mode).reshape(b, s, h, -1)
    kv_a = mm("bsd,de->bse", hn, get("attn", "wkv_a"), mode)
    c = _norm(m, get("attn", "kv_a_norm", "scale"), kv_a[..., :r])
    kv = mm("bsr,re->bse", c, get("attn", "wkv_b"), mode).reshape(b, s, h, -1)
    k_pe = dense_lm._rope(kv_a[..., None, r:], theta)
    q = jnp.concatenate([q[..., :dn], dense_lm._rope(q[..., dn:], theta)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))],
                        -1)
    o = _attention(q, k, kv[..., dn:], mode)
    return x + mm("bse,ed->bsd", o, get("attn", "wo"), mode)


def dense_layer(m, get, x, mode):
    """The leading dense layer: (x, no sums)."""
    x = _mla(m, get, x, mode)
    hn = _norm(m, get("mlp_ln", "scale"), x)
    return x + moe_lm._swiglu(hn, get("mlp", "w_gate"), get("mlp", "w_up"),
                              get("mlp", "w_down"), mode), {}


def route(m, get, t, mode):
    """t [T, d] -> (gates [T, R]: each token's weight on each expert, zero
    where not routed; ids [T, k]; scores s [T, R])."""
    _e, routed, k, _ns, _first = _experts(m)
    s = jax.nn.sigmoid(mm("td,de->te", t, get("moe", "router"), mode))
    _, ids = jax.lax.top_k(
        s + jax.lax.stop_gradient(get("moe", "score_bias")), k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / w.sum(-1, keepdims=True) * m["moe"]["routed_scaling"]
    gates = jnp.zeros(s.shape, jnp.float32).at[
        jnp.arange(t.shape[0])[:, None], ids].add(w)
    return gates, ids, s


def moe_ffn(m, get, t, mode):
    """F of an expert layer on tokens t [T, d]: (the held experts' weighted
    outputs plus the shared experts [T, d]; ids [T, k]; scores [T, R])."""
    e, _routed, _k, ns, first = _experts(m)
    gates, ids, sc = route(m, get, t, mode)
    ws = [get("moe", n) for n in ("w_gate", "w_up", "w_down")]

    def one(y, i):
        # expert i in one loop body, so the program compiles one expert
        g = jax.lax.dynamic_index_in_dim(gates, first + i, axis=1)
        return y + g * moe_lm._swiglu(t, *(expert(w, i) for w in ws),
                                      mode), None

    y, _ = jax.lax.scan(one, jnp.zeros(t.shape, jnp.float32), jnp.arange(e))
    if ns:
        y = y + moe_lm._swiglu(t, get("moe", "shared", "w_gate"),
                               get("moe", "shared", "w_up"),
                               get("moe", "shared", "w_down"), mode)
    return y, ids, sc


def moe_layer(m, get, x, mode):
    """An expert layer: (x, sums: the rows' summed sequence-wise balance
    terms `balance` and the row count `rows`)."""
    _e, routed, k, _ns, _first = _experts(m)
    x = _mla(m, get, x, mode)
    hn = _norm(m, get("mlp_ln", "scale"), x)
    b, s, d = hn.shape
    y, ids, sc = moe_ffn(m, get, hn.reshape(b * s, d), mode)
    f = jax.nn.one_hot(ids, routed, dtype=jnp.float32).sum(1).reshape(
        b, s, routed).sum(1) * (routed / (k * s))
    p = (sc / sc.sum(-1, keepdims=True)).reshape(b, s, routed).mean(1)
    sums = {"balance": jnp.sum(f * p), "rows": jnp.float32(b)}
    return x + y.reshape(b, s, d), sums


LAYERS = {"first": dense_layer, "blocks": moe_layer}


def aux_loss(m, sums: list, tokens: int):
    """alpha * the mean over rows of each expert layer's balance term (a
    dense layer's sums are empty)."""
    total = jnp.zeros((), jnp.float32)
    for s in sums:
        if s:
            total = total + m["moe"]["aux_weight"] * s["balance"] / s["rows"]
    return total


def flops_per_token(m, seq: int, k_train: int, ratio: float,
                    block_req: int) -> dict:
    """Required FLOPs per token of one sparse training step, as for moe_lm:
    the forward of every layer (in an expert layer the router, the shared
    experts and the held share of the routed experts, k * held / R per
    token) and the head; input gradients through the trainable suffix and
    the head (not into the suffix's own input); weight gradients of the
    selected blocks (an expert leaf's over the held experts each token
    reaches) and of the router. Causal attention counts (S + 1) H (dq + dv)
    per token forward (scores and values over the average visible prefix)
    and twice that backward."""
    d, ff, v = m["d_model"], m["d_ff"], m["vocab_size"]
    e, routed, k, ns, _first = _experts(m)
    h, dn, dr, dv, _r = _dims(m)
    reach = k * e / routed                     # held experts per token
    attn_w = sum(i * o for i, o in _attn_mats(m))
    attn = (seq + 1) * h * (dn + dr + dv)
    mats = {"first": attn_w + 3 * d * m["moe"]["dense_d_ff"],
            "blocks": attn_w + d * routed + 3 * d * ff * (reach + ns)}
    fwd = sum(n * (2 * mats[name] + attn) for name, n in segments(m))
    fwd += 2 * d * v
    first = sum(i * o for i, o in _attn_mats(m)[:2])
    dx = k_train * (2 * mats["blocks"] + 2 * attn) - 2 * first + 2 * d * v
    dw = 2 * d * routed                              # the router, dense
    for leaf in selectable_leaves(m):
        block, _n_blocks, n_sel = sel_spec(leaf[2], ratio, block_req)
        dw += 2 * leaf[1] * n_sel * block * (reach if len(leaf) > 3 else 1)
    dw *= k_train
    return {"forward": fwd, "backward": dx + dw, "total": fwd + dx + dw}
