"""Core layers: norms, RoPE / M-RoPE, GQA attention (dense / diagonal-block
flash / sliding-window / cached decode), MLP variants.

All functions are pure; params are plain dicts of jnp arrays. Matmuls that
participate in dynamic gradient sparse update go through
``repro.core.sparse_update.smm`` (sparse-matmul) so the backward pass skips
unselected output-channel blocks.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sparse_update import smm
from repro.models.common import col_matmul, dense_init, row_matmul
from repro import sharding as SH
from repro.sharding import constrain

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(key, d: int, kind: str, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    raise ValueError(kind)


def apply_norm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def init_group_norm(key, c: int, groups: int, dtype):
    del groups  # static — passed to apply_group_norm
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def apply_group_norm(p, x, groups: int, eps: float = 1e-5):
    """x: [B, H, W, C] (NHWC)."""
    b, h, w, c = x.shape
    g = groups
    xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mu = xf.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(b, h, w, c)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions: broadcastable to [..., S] int32."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., :, None, :]                    # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int) -> tuple[int, int, int]:
    """Pair-counts for (temporal, height, width); qwen2-vl uses 16/24/24 of 64
    pairs for head_dim=128 — i.e. fractions (1/4, 3/8, 3/8)."""
    pairs = head_dim // 2
    t = pairs // 4
    h = (pairs - t) // 2
    w = pairs - t - h
    return t, h, w


def apply_mrope(x, positions_thw, theta: float):
    """M-RoPE (qwen2-vl): positions_thw [3, ..., S]; frequency bands are
    partitioned between the three position components."""
    d = x.shape[-1]
    pairs = d // 2
    t, h, w = mrope_sections(d)
    freqs = rope_frequencies(d, theta)                       # [pairs]
    section = jnp.concatenate([
        jnp.zeros((t,), jnp.int32), jnp.ones((h,), jnp.int32),
        jnp.full((w,), 2, jnp.int32)])
    # pick position component per frequency band
    pos = jnp.take(positions_thw, section, axis=0)           # [pairs, ..., S]
    pos = jnp.moveaxis(pos, 0, -1)                           # [..., S, pairs]
    angles = pos.astype(jnp.float32) * freqs                 # [..., S, pairs]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype):
    if cfg.mla:
        return init_mla(key, cfg, dtype)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d, cfg.num_heads * hd), dtype=dtype),
        "wk": dense_init(kk, (d, cfg.num_kv_heads * hd), dtype=dtype),
        "wv": dense_init(kv, (d, cfg.num_kv_heads * hd), dtype=dtype),
        "wo": dense_init(ko, (cfg.num_heads * hd, d), dtype=dtype),
    }


def _qkv(p, cfg, x, positions, sel=None, delta=None):
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    # head counts come from the projection widths, not cfg: inside a
    # shard_map over the model axis each shard holds a head-block of
    # wq/wk/wv (column-parallel), so the local head count is cfg's divided
    # by the shard count; full_out lets a rider delta land on the owning
    # shard only
    q = col_matmul(x, p["wq"], sel, "wq", delta,
                   full_out=cfg.num_heads * hd).reshape(b, s, -1, hd)
    k = col_matmul(x, p["wk"], sel, "wk", delta,
                   full_out=cfg.num_kv_heads * hd).reshape(b, s, -1, hd)
    v = col_matmul(x, p["wv"], sel, "wv", delta,
                   full_out=cfg.num_kv_heads * hd).reshape(b, s, -1, hd)
    if getattr(cfg, "mrope", False):
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _expand_kv(k, hq: int):
    """GQA expansion via gather (sharding-friendly on the head axis):
    [B,S,Hkv,D] -> [B,S,Hq,D]."""
    hkv = k.shape[2]
    if hkv == hq:
        return k
    return jnp.take(k, jnp.arange(hq) // (hq // hkv), axis=2)


def _sdpa_dense(q, k, v, window: int = 0):
    """Materialized causal attention. q:[B,S,Hq,D] k,v:[B,S,Hkv,D]."""
    b, s, hq, dd = q.shape
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dd)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = kpos <= qpos
    if window:
        mask &= (qpos - kpos) < window
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


def _diag_mask(c: int, diag: int, window: int):
    qpos_in = jnp.arange(c)[:, None]
    kpos_in = jnp.arange(c)[None, :]
    delta = qpos_in - kpos_in + diag * c     # distance q-k; >= 0 is causal
    mask = delta >= 0
    if window:
        mask &= delta < window
    return mask


# The flash loops below are unrolled over the diagonals of blocks, and XLA
# may schedule every diagonal's [b, nb, c, h, c] score blocks at once, and
# share a rematerialized forward's with the backward: memory that grows as
# the square of the diagonals (four fit beside a 2,048-token step; sixteen,
# at 8,192 tokens, take over 5 GB). Past SERIAL_DIAGONALS each diagonal
# starts only once the previous one has finished (an optimization barrier
# over the carried state and the blocks it reads), so one diagonal's blocks
# are live at a time.
SERIAL_DIAGONALS = 4


def _in_turn(n: int, *xs):
    """xs unchanged, or held behind a barrier when the loop over n
    diagonals runs them one at a time."""
    return jax.lax.optimization_barrier(xs) if n > SERIAL_DIAGONALS else xs


def _flash_fwd_impl(q, k, v, window: int, c: int):
    """Diagonal-block causal flash attention forward (pure jnp, online
    softmax). Only on/below-diagonal blocks are computed (no causal-FLOP
    waste); sliding windows statically truncate the diagonal range.
    Returns (out [b,s,h,d], lse [b,n,c,h])."""
    b, s, hq, dd = q.shape
    dv = v.shape[-1]
    n = s // c
    qb = q.reshape(b, n, c, hq, dd)
    kb = k.reshape(b, n, c, hq, dd)
    vb = v.reshape(b, n, c, hq, dv)

    scale = 1.0 / math.sqrt(dd)
    m = jnp.full((b, n, c, hq), -1e30, jnp.float32)    # running max
    l = jnp.zeros((b, n, c, hq), jnp.float32)           # running denom
    o = jnp.zeros((b, n, c, hq, dv), jnp.float32)       # running numer

    max_diag = n if not window else min(n, (window + c - 1) // c + 1)
    for diag in range(max_diag):
        m, l, o, qb, kb, vb = _in_turn(n, m, l, o, qb, kb, vb)
        nb = n - diag                        # blocks on this diagonal
        qs = qb[:, diag:, ...]               # [b, nb, c, hq, dd]
        ks = kb[:, :nb, ...]
        vs = vb[:, :nb, ...]
        sc = jnp.einsum("bnqhd,bnkhd->bnqhk", qs, ks,
                        preferred_element_type=jnp.float32) * scale
        mask = _diag_mask(c, diag, window)
        sc = jnp.where(mask[None, None, :, None, :], sc, -1e30)
        blk_m = sc.max(axis=-1)                                  # [b,nb,c,hq]
        m_old = m[:, diag:, ...]
        m_new = jnp.maximum(m_old, blk_m)
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m_old - m_new)
        l = l.at[:, diag:, ...].set(l[:, diag:, ...] * corr + p.sum(axis=-1))
        pv = jnp.einsum("bnqhk,bnkhd->bnqhd", p.astype(q.dtype), vs,
                        preferred_element_type=jnp.float32)
        o = o.at[:, diag:, ...].set(o[:, diag:, ...] * corr[..., None] + pv)
        m = m.at[:, diag:, ...].set(m_new)
    out = o / jnp.maximum(l[..., None], 1e-30)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out.astype(q.dtype).reshape(b, s, hq, dv), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attn(q, k, v, window: int, c: int):
    return _flash_fwd_impl(q, k, v, window, c)[0]


def _flash_attn_fwd(q, k, v, window, c):
    out, lse = _flash_fwd_impl(q, k, v, window, c)
    return out, (q, k, v, out, lse)


def _flash_attn_bwd(window, c, res, dout):
    """Flash backward: recompute probabilities per diagonal from (q,k,lse)
    — O(S·d) residual memory instead of O(S^2) (the dominant training-memory
    term at 4k+ sequence lengths; see EXPERIMENTS.md §Perf iteration 1)."""
    q, k, v, out, lse = res
    b, s, hq, dd = q.shape
    dvh = v.shape[-1]
    n = s // c
    scale = 1.0 / math.sqrt(dd)
    qb = q.reshape(b, n, c, hq, dd)
    kb = k.reshape(b, n, c, hq, dd)
    vb = v.reshape(b, n, c, hq, dvh)
    dob = dout.reshape(b, n, c, hq, dvh)
    ob = out.reshape(b, n, c, hq, dvh)
    # delta_i = sum_d dout_i * out_i  (the softmax normalization term)
    delta = jnp.einsum("bnqhd,bnqhd->bnqh", dob.astype(jnp.float32),
                       ob.astype(jnp.float32))

    dq = jnp.zeros((b, n, c, hq, dd), jnp.float32)
    dk = jnp.zeros((b, n, c, hq, dd), jnp.float32)
    dv = jnp.zeros((b, n, c, hq, dvh), jnp.float32)
    max_diag = n if not window else min(n, (window + c - 1) // c + 1)
    for diag in range(max_diag):
        dq, dk, dv, qb, kb, vb, dob = _in_turn(n, dq, dk, dv, qb, kb, vb, dob)
        nb = n - diag
        qs = qb[:, diag:, ...]
        ks = kb[:, :nb, ...]
        vs = vb[:, :nb, ...]
        dos = dob[:, diag:, ...]
        sc = jnp.einsum("bnqhd,bnkhd->bnqhk", qs, ks,
                        preferred_element_type=jnp.float32) * scale
        mask = _diag_mask(c, diag, window)
        sc = jnp.where(mask[None, None, :, None, :], sc, -1e30)
        p = jnp.exp(sc - lse[:, diag:, :, :, None])          # normalized probs
        dv = dv.at[:, :nb].add(jnp.einsum(
            "bnqhk,bnqhd->bnkhd", p.astype(q.dtype), dos,
            preferred_element_type=jnp.float32))
        dp = jnp.einsum("bnqhd,bnkhd->bnqhk", dos, vs,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, diag:, :, :, None]) * scale
        ds = ds.astype(q.dtype)
        dq = dq.at[:, diag:].add(jnp.einsum(
            "bnqhk,bnkhd->bnqhd", ds, ks, preferred_element_type=jnp.float32))
        dk = dk.at[:, :nb].add(jnp.einsum(
            "bnqhk,bnqhd->bnkhd", ds, qs, preferred_element_type=jnp.float32))
    rs = lambda t: t.reshape(b, s, hq, t.shape[-1]).astype(q.dtype)
    return rs(dq), rs(dk), rs(dv)


_flash_attn.defvjp(_flash_attn_fwd, _flash_attn_bwd)


def _sdpa_flash(q, k, v, window: int = 0, q_chunk: int = 512,
                kv_chunk: int = 512, naive_vjp: bool = False):
    """Memory-efficient causal attention. naive_vjp=True keeps plain
    autodiff (O(S^2) residuals) — the pre-optimization baseline."""
    b, s, hq, dd = q.shape
    if s <= q_chunk:
        return _sdpa_dense(q, k, v, window)
    assert s % q_chunk == 0 and s % kv_chunk == 0 and q_chunk == kv_chunk, (
        "flash path requires equal, dividing chunks")
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    if naive_vjp:
        return _flash_fwd_impl(q, k, v, window, q_chunk)[0]
    return _flash_attn(q, k, v, window, q_chunk)


def attention(p, cfg, x, positions, *, window: int = 0, sel=None,
              flash_threshold: int = 2048):
    """Full training/prefill attention over a whole sequence."""
    b, s, _ = x.shape
    if cfg.mla:
        q, k, v = _mla_qkv(p, cfg, x, positions, sel)
    else:
        q, k, v = _qkv(p, cfg, x, positions, sel=sel)
    if s > flash_threshold:
        out = _sdpa_flash(q, k, v, window)
    else:
        out = _sdpa_dense(q, k, v, window)
    out = out.reshape(b, s, -1)
    return smm(out, p["wo"], sel, "wo")


def init_mla(key, cfg, dtype):
    """Multi-head latent attention (DeepSeek-V2/V3) without a query LoRA:
    wq [d, H (dn + dr)], wkv_a [d, r + dr], kv_a_norm [r], wkv_b
    [r, H (dn + dv)], wo [H dv, d] (dn, dr, dv: the nope, rope and value head
    sizes; r: kv_lora_rank)."""
    if cfg.q_lora_rank:
        raise ValueError("latent attention with a query LoRA (q_lora_rank) "
                         "is not built")
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d, h * (dn + dr)), dtype=dtype),
        "wkv_a": dense_init(ka, (d, r + dr), dtype=dtype),
        "kv_a_norm": init_norm(ka, r, "rmsnorm", dtype),
        "wkv_b": dense_init(kb, (r, h * (dn + dv)), dtype=dtype),
        "wo": dense_init(ko, (h * dv, d), dtype=dtype),
    }


def _mla_qkv(p, cfg, x, positions, sel):
    """Latent attention in its expanded form: the latent c_kv is decompressed
    to per-head keys and values. q, k: [B, S, H, dn + dr] (nope dims, then
    the rope dims, rotate-half RoPE; k's rope dims are one head shared by
    all heads); v: [B, S, H, dv]. The key and value heads are `num_heads`
    (`num_kv_heads` is not read)."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = smm(x, p["wq"], sel, "wq").reshape(b, s, h, dn + dr)
    with jax.named_scope("latent_kv"):
        kv_a = smm(x, p["wkv_a"], sel, "wkv_a")
        c_kv = apply_norm(p["kv_a_norm"], kv_a[..., :r], cfg.norm_eps)
        kv = smm(c_kv, p["wkv_b"], sel, "wkv_b").reshape(b, s, h, dn + dv)
    k_pe = apply_rope(kv_a[..., None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], positions, cfg.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))], -1)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "heads", None)
    v = constrain(kv[..., dn:], "batch", "seq", "heads", None)
    return q, k, v


def decode_attention(p, cfg, x, positions, cache, *, window: int = 0):
    """Single-token decode against a KV cache.

    cache: {"k","v": [B, S_cache, Hkv, D], "pos": [B] int32 tokens-so-far}
    `pos` is per batch row so decode slots can sit at different depths
    (continuous batching: a freshly refilled slot decodes position
    `prompt_len` while its neighbours are deep into generation).
    For sliding-window layers the cache is a ring buffer of size `window`.
    """
    b, s, _ = x.shape
    assert s == 1
    hd = cfg.resolved_head_dim
    q, k, v = _qkv(p, cfg, x, positions)
    pos = cache["pos"]                    # [B] position index of the new token
    s_cache = cache["k"].shape[1]
    # ring buffer when windowed (s_cache == window), else direct slot
    slot = pos % s_cache if window > 0 else pos
    rows = jnp.arange(b)
    k_cache = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))

    hkv = cfg.num_kv_heads
    g = cfg.num_heads // hkv
    qg = q.reshape(b, hkv, g, hd)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    idx = jnp.arange(s_cache)[None, :]
    if window > 0:
        # slot i currently holds position p_at = pos - ((pos - i) mod W);
        # by construction pos - W < p_at <= pos, so only p_at >= 0 matters.
        p_at = pos[:, None] - jnp.mod(pos[:, None] - idx, s_cache)
        valid = p_at >= 0                                      # [B, S_cache]
    else:
        valid = idx <= pos[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(q.dtype), v_cache,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    out = out.reshape(b, 1, cfg.num_heads * hd)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos + 1}
    return smm(out, p["wo"], None, "wo"), new_cache


# ---------------------------------------------------------------------------
# Chunk-capable serving attention (paged + ring)
#
# Both variants process s >= 1 new tokens per call against an existing cache,
# so the serving engine's single step function covers batched decode (s=1
# over all slots) AND chunked prefill (one slot, page-sized chunks) — long
# admissions never stall in-flight decodes behind a monolithic prefill.
# Scores are taken against [cached keys ++ in-chunk keys] with the cache
# read BEFORE the chunk's rows are written, so in-chunk causality never
# depends on write ordering (a ring buffer may overwrite its own chunk).
# ---------------------------------------------------------------------------

def _grouped_scores(q, k_cat, v_cat, mask, cfg=None):
    """q: [B,S,Hq,D]; k_cat/v_cat: [B,L,Hkv,D]; mask: [B,S,L] -> [B,S,Hq*D].

    Hkv comes from k_cat, not cfg: under head-sharded serving each shard
    sees a local head-block (Hq_loc = g * Hkv_loc keeps the GQA grouping
    aligned, so the monolithic reshape below stays correct per shard)."""
    b, s, hq, hd = q.shape
    hkv = k_cat.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, hd)
    scores = jnp.einsum("bshgd,blhd->bhgsl", qg, k_cat,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgsl,blhd->bshgd", probs.astype(q.dtype), v_cat,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(b, s, hq * hd)


def _grouped_scores_split(q, k_cat, v_cat, mask, tile: int):
    """Flash-decoding form of `_grouped_scores`: the KV length is split
    into `tile`-sized blocks (one page per block in the serve engine), each
    block contributes an (out, lse)-style partial, and partials merge with
    the online-softmax update from `_flash_fwd_impl` — so long contexts
    reduce over pages instead of materializing one [B,Hq,S,L] score tensor.
    Matches the monolithic softmax to float32 roundoff.
    """
    b, s, hq, hd = q.shape
    hkv = k_cat.shape[2]
    g = hq // hkv
    L = k_cat.shape[1]
    nt = -(-L // tile)
    pad = nt * tile - L
    if pad:
        zkv = jnp.zeros((b, pad) + k_cat.shape[2:], k_cat.dtype)
        k_cat = jnp.concatenate([k_cat, zkv], axis=1)
        v_cat = jnp.concatenate([v_cat, zkv], axis=1)
        mask = jnp.concatenate(
            [mask, jnp.zeros((b, s, pad), mask.dtype)], axis=2)

    qg = q.reshape(b, s, hkv, g, hd)
    scale = 1.0 / math.sqrt(hd)
    # tiles to the front so lax.scan walks pages: [NT, B, tile, ...]
    kt = jnp.moveaxis(k_cat.reshape(b, nt, tile, hkv, hd), 1, 0)
    vt = jnp.moveaxis(v_cat.reshape(b, nt, tile, hkv, hd), 1, 0)
    mt = jnp.moveaxis(mask.reshape(b, s, nt, tile), 2, 0)

    m0 = jnp.full((b, hkv, g, s), -1e30, jnp.float32)       # running max
    l0 = jnp.zeros((b, hkv, g, s), jnp.float32)             # running denom
    o0 = jnp.zeros((b, hkv, g, s, hd), jnp.float32)         # running numer

    def merge(carry, blk):
        m, l, o = carry
        k_b, v_b, msk = blk
        sc = jnp.einsum("bshgd,bthd->bhgst", qg, k_b,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(msk[:, None, None, :, :], sc, -1e30)
        blk_m = sc.max(axis=-1)
        m_new = jnp.maximum(m, blk_m)
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgst,bthd->bhgsd", p.astype(q.dtype), v_b,
                        preferred_element_type=jnp.float32)
        o = o * corr[..., None] + pv
        return (m_new, l, o), None

    (m, l, o), _ = jax.lax.scan(merge, (m0, l0, o0), (kt, vt, mt))
    out = o / jnp.maximum(l[..., None], 1e-30)              # [B,Hkv,G,S,D]
    out = jnp.moveaxis(out, 3, 1)                           # [B,S,Hkv,G,D]
    return out.astype(q.dtype).reshape(b, s, hq * hd)


def _serve_positions(cfg, start, s):
    """Token positions for a chunk: [B,S] (or [3,B,S] broadcast for mrope)."""
    pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    if getattr(cfg, "mrope", False):
        pos = jnp.broadcast_to(pos, (3,) + pos.shape)
    return pos


def chunk_ring_attention(p, cfg, x, start, active, cache, *, window: int,
                         length=None, delta=None):
    """Sliding-window attention for a chunk of s tokens per batch row.

    cache: {"k","v": [B, W, H, D]} ring buffers (position p at slot p % W).
    `start` [B] = tokens already cached per row; rows with active=False get
    their cache returned unchanged (the caller row-selects, but the write
    here must still be computed — shapes are fixed). `length` [B] = valid
    tokens per row (None = all s): rows are padded to a fixed chunk shape
    so the final partial prefill chunk does not retrace, and the writes of
    padded positions MUST be dropped — a ring slot written at a padded
    position would masquerade as an earlier (mod-W-aliased) position on the
    next read.
    """
    b, s, _ = x.shape
    if length is None:
        length = jnp.full((b,), s, jnp.int32)
    w_cap = cache["k"].shape[1]
    q, k, v = _qkv(p, cfg, x, _serve_positions(cfg, start, s), delta=delta)

    # Under head-sharded serving the ring cache arrives replicated (it is
    # per-slot engine state, not pool state): slice this shard's head block,
    # attend and write locally, and gather the heads back before returning
    # so the state leaves the shard_map replicated again.
    ax = SH.current_mapped_axis()
    hkv_loc = k.shape[2]
    local_heads = ax is not None and hkv_loc != cache["k"].shape[2]
    ring_k, ring_v = cache["k"], cache["v"]
    if local_heads:
        off = jax.lax.axis_index(ax) * hkv_loc
        ring_k = jax.lax.dynamic_slice_in_dim(ring_k, off, hkv_loc, axis=2)
        ring_v = jax.lax.dynamic_slice_in_dim(ring_v, off, hkv_loc, axis=2)

    j = jnp.arange(s)
    qpos = start[:, None] + j[None, :]                       # [B, S]
    # ring part: slot i holds the latest position == i (mod W) that is
    # <= start-1 (pre-chunk content); negative p_at -> never written
    idx = jnp.arange(w_cap)[None, :]
    last = start[:, None] - 1
    p_at = last - jnp.mod(last - idx, w_cap)                 # [B, W]
    ring_mask = (p_at[:, None, :] >= 0) & \
        (qpos[:, :, None] - p_at[:, None, :] < window)       # [B, S, W]
    # in-chunk part: causal within the chunk, window-limited
    chunk_mask = (j[None, :] <= j[:, None]) & (j[:, None] - j[None, :] < window)
    chunk_mask = jnp.broadcast_to(chunk_mask[None], (b, s, s))

    k_cat = jnp.concatenate([ring_k.astype(k.dtype), k], axis=1)
    v_cat = jnp.concatenate([ring_v.astype(v.dtype), v], axis=1)
    mask = jnp.concatenate([ring_mask, chunk_mask], axis=2)
    out = _grouped_scores(q, k_cat, v_cat, mask, cfg)

    # write the chunk into the ring: position p -> slot p % W; among the
    # valid (non-padded) rows only the last W survive, so earlier rows are
    # dropped via an out-of-bounds slot (duplicate in-bounds scatters have
    # no defined order); padded rows (j >= length) never write
    keep = (j[None, :] < length[:, None]) & \
        (j[None, :] >= length[:, None] - w_cap) & active[:, None]
    slot = jnp.where(keep, jnp.mod(qpos, w_cap), w_cap)      # [B, S]
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s))
    k_cache = ring_k.at[rows, slot].set(
        k.astype(ring_k.dtype), mode="drop")
    v_cache = ring_v.at[rows, slot].set(
        v.astype(ring_v.dtype), mode="drop")
    if local_heads:
        k_cache = SH.all_gather_mapped(k_cache, axis=2)
        v_cache = SH.all_gather_mapped(v_cache, axis=2)
    y = row_matmul(out, p["wo"], None, "wo", delta,
                   full_in=cfg.num_heads * cfg.resolved_head_dim)
    return y, {"k": k_cache, "v": v_cache}


def chunk_paged_attention(p, cfg, x, start, active, pool, page_table, *,
                          page_size: int, length=None, delta=None,
                          flash_decode: bool = False):
    """Full (window-free) attention for a chunk of s tokens per batch row,
    reading and writing K/V through per-row page tables.

    pool: {"k","v": [R, H, D]} physical token rows shared by ALL batch rows
    (R = num_pages * page_size); page_table: [B, MP] int32 physical page per
    logical page, -1 where unallocated. Writes of inactive rows, rows
    whose page is unallocated, and padded rows (`length` [B] = valid tokens
    per row, None = all s) are dropped via out-of-bounds indices — padded
    garbage must never land in a page a later request could share.
    """
    b, s, _ = x.shape
    if length is None:
        length = jnp.full((b,), s, jnp.int32)
    ps = page_size
    r_rows = pool["k"].shape[0]
    mp = page_table.shape[1]
    q, k, v = _qkv(p, cfg, x, _serve_positions(cfg, start, s), delta=delta)

    # gather the cached prefix in logical order: [B, MP*ps] physical rows
    phys = jnp.clip(page_table, 0)[:, :, None] * ps + \
        jnp.arange(ps)[None, None, :]
    phys = phys.reshape(b, mp * ps)
    k_cache = jnp.take(pool["k"], phys, axis=0)              # [B, L, H, D]
    v_cache = jnp.take(pool["v"], phys, axis=0)

    l_idx = jnp.arange(mp * ps)[None, :]                     # logical index
    alloc = jnp.take_along_axis(page_table, l_idx // ps, axis=1) >= 0
    cache_mask = (l_idx < start[:, None]) & alloc            # [B, L]
    cache_mask = jnp.broadcast_to(cache_mask[:, None, :], (b, s, mp * ps))
    j = jnp.arange(s)
    chunk_mask = jnp.broadcast_to((j[None, :] <= j[:, None])[None], (b, s, s))

    k_cat = jnp.concatenate([k_cache.astype(k.dtype), k], axis=1)
    v_cat = jnp.concatenate([v_cache.astype(v.dtype), v], axis=1)
    mask = jnp.concatenate([cache_mask, chunk_mask], axis=2)
    if flash_decode:
        out = _grouped_scores_split(q, k_cat, v_cat, mask, tile=ps)
    else:
        out = _grouped_scores(q, k_cat, v_cat, mask, cfg)

    # write the chunk rows: logical position -> page_table page; unallocated
    # pages / inactive rows land out of bounds and are dropped
    wpos = start[:, None] + j[None, :]                       # [B, S]
    pid = jnp.take_along_axis(page_table, wpos // ps, axis=1)
    dest = jnp.where((pid >= 0) & active[:, None] &
                     (j[None, :] < length[:, None]),
                     pid * ps + wpos % ps, r_rows).reshape(-1)
    k_pool = pool["k"].at[dest].set(
        k.reshape(b * s, *k.shape[2:]).astype(pool["k"].dtype), mode="drop")
    v_pool = pool["v"].at[dest].set(
        v.reshape(b * s, *v.shape[2:]).astype(pool["v"].dtype), mode="drop")
    # under head-sharded serving each shard's wo rows cover only its local
    # heads: row-parallel matmul, one psum reassembles the output (identity
    # outside shard_map); a rider delta is applied on the local d_in slice
    # before the reduction
    y = row_matmul(out, p["wo"], None, "wo", delta,
                   full_in=cfg.num_heads * cfg.resolved_head_dim)
    return y, {"k": k_pool, "v": v_pool}


def init_kv_cache(cfg, batch: int, seq_len: int, *, window: int = 0, dtype=None):
    hd = cfg.resolved_head_dim
    size = min(window, seq_len) if window > 0 else seq_len
    dt = dtype or jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dt),
        "v": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dt),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def ring_snapshot_leaves(cfg, window: int, max_len: int, dtype=None):
    """Per-row (shape, dtype) spec of a ring layer's serve-cache state — the
    snapshot unit a prefix cache stores at a page boundary. The serve ring
    leaf carries no per-row `pos` (position is the engine's slot.pos), so
    the snapshot is the k/v buffers only."""
    hd = cfg.resolved_head_dim
    size = min(window, max_len)
    dt = dtype or jnp.dtype(cfg.dtype)
    return {"k": ((size, cfg.num_kv_heads, hd), dt),
            "v": ((size, cfg.num_kv_heads, hd), dt)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, dtype, d_ff: Optional[int] = None):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    kind = cfg.mlp_kind
    if kind == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {"w_gate": dense_init(k1, (d, ff), dtype=dtype),
                "w_up": dense_init(k2, (d, ff), dtype=dtype),
                "w_down": dense_init(k3, (ff, d), dtype=dtype)}
    if kind in ("gelu", "sq_relu"):
        k1, k2 = jax.random.split(key, 2)
        return {"w_up": dense_init(k1, (d, ff), dtype=dtype),
                "w_down": dense_init(k2, (ff, d), dtype=dtype)}
    raise ValueError(kind)


def apply_mlp(p, cfg, x, sel=None, delta=None, d_ff: Optional[int] = None):
    """gate/up are column-parallel (ff sharded on the model axis at serve
    time), down is row-parallel (one psum). `d_ff` is the FULL hidden width
    when it differs from cfg.d_ff (dense-first MoE segment, shared-expert
    MLP) — the col/row primitives compare it against the local weight shape
    to tell sharded from replicated-fallback leaves."""
    ff = d_ff or cfg.d_ff
    kind = cfg.mlp_kind
    if kind == "swiglu":
        h = jax.nn.silu(
            col_matmul(x, p["w_gate"], sel, "w_gate", delta, full_out=ff)) * \
            col_matmul(x, p["w_up"], sel, "w_up", delta, full_out=ff)
    elif kind == "gelu":
        h = jax.nn.gelu(
            col_matmul(x, p["w_up"], sel, "w_up", delta, full_out=ff))
    elif kind == "sq_relu":
        h = col_matmul(x, p["w_up"], sel, "w_up", delta, full_out=ff)
        h = jax.nn.relu(h)
        h = h * h
    else:
        raise ValueError(kind)
    h = constrain(h, "batch", "seq", "ff")
    return row_matmul(h, p["w_down"], sel, "w_down", delta, full_in=ff)
