"""RWKV-6 "Finch" block: token-shift time-mix with data-dependent decay.

WKV recurrence (per head, head_dim D):
    y_t = r_t · (diag(u) k_t v_tᵀ + S_{t-1})
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
with per-channel decay w_t = exp(-exp(wlog_t)) produced by a low-rank
data-dependent path (the Finch contribution). The recurrence takes the
log-decay lw_t = -exp(wlog_t) <= 0, so a decay that underflows to 0 stays a
finite number.

Implementation: `wkv` scans over chunks of CHUNK tokens with the state
S [B, H, D, D] (f32) as the carry and jax.checkpoint on the chunk body, so
memory is O(chunk) and a backward pass saves one state per chunk. Inside a
chunk the recurrence has a closed form. With b_t the cumulative log-decay
inside the chunk (b_{-1} = 0):
    y_t = (r_t ⊙ e^{b_{t-1}}) · S
        + Σ_{u<t} [Σ_d r_{t,d} k_{u,d} e^{b_{t-1,d} − b_{u,d}}] v_u
        + (Σ_d r_{t,d} u_d k_{t,d}) v_t
    S'  = diag(e^{b_{Q-1}}) S + Σ_u (k_u ⊙ e^{b_{Q-1} − b_u}) v_uᵀ
so the state is read and written once per chunk and the work inside it is
matmuls (`_wkv_chunk`). Every exponent is kept at or below zero: the chunk
is split into sub-chunks of SUB tokens; a key in an earlier sub-chunk is
decayed to the last token before the query's sub-chunk and the query from
there, and pairs inside one sub-chunk are formed elementwise. A one-token
call (decode) takes the stepwise update (`_wkv_step`).

Simplification vs the full Finch block (recorded in DESIGN §8): the five
token-shift interpolations use per-channel learned mu (RWKV-5 style lerp)
rather than the stacked data-dependent lora for all of r/k/v/g; the decay w
keeps its full data-dependent low-rank path (the core of RWKV-6).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse_update import smm
from repro.models.common import dense_init, last_valid, row_matmul
from repro.models.layers import apply_norm, init_norm
from repro import sharding as SH

CHUNK = 32             # tokens per step of the state scan
SUB = 16               # tokens per sub-chunk inside a chunk
LOG_DECAY_MIN = -104.0  # below this e^lw is 0 in f32: a decay of zero
DECAY_LORA = 64


def num_heads(cfg) -> int:
    return cfg.d_model // cfg.rwkv.head_dim


def init_time_mix(key, cfg, dtype):
    d = cfg.d_model
    hd = cfg.rwkv.head_dim
    h = num_heads(cfg)
    ks = jax.random.split(key, 9)
    return {
        "mu": jax.random.uniform(ks[0], (5, d), jnp.float32),  # r,k,v,g,w shifts
        "wr": dense_init(ks[1], (d, d), dtype=dtype),
        "wk": dense_init(ks[2], (d, d), dtype=dtype),
        "wv": dense_init(ks[3], (d, d), dtype=dtype),
        "wg": dense_init(ks[4], (d, d), dtype=dtype),
        "wo": dense_init(ks[5], (d, d), dtype=dtype),
        # data-dependent decay lora: w_t = w0 + tanh(x_w @ A) @ B
        "w0": jnp.full((d,), -6.0, jnp.float32),
        "wA": dense_init(ks[6], (d, DECAY_LORA), dtype=jnp.float32),
        "wB": dense_init(ks[7], (DECAY_LORA, d), dtype=jnp.float32, scale=0.1),
        "u": (jax.random.normal(ks[8], (h, hd), jnp.float32) * 0.1),
        "ln_x": init_norm(jax.random.PRNGKey(0), d, "layernorm", jnp.float32),
    }


def _shift(x, last=None):
    """Token shift: x_{t-1} (zeros or `last` at t=0)."""
    if last is None:
        last = jnp.zeros_like(x[:, :1])
    else:
        last = last[:, None, :] if last.ndim == 2 else last
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def _wkv_step(u, s, r, k, v, lw):
    """One token. s: [B,H,D,D]; r,k,v,lw: [B,H,D] -> (S', y [B,H,D] f32)."""
    r, k, v = (t.astype(jnp.float32) for t in (r, k, v))
    kv = k[..., :, None] * v[..., None, :]                    # [B,H,D,D]
    y = jnp.einsum("bhd,bhde->bhe", r, u[None, :, :, None] * kv + s)
    return jnp.exp(lw)[..., :, None] * s + kv, y


def _excl_cumsum(x, axis, reverse=False):
    """Sum of the elements before each one along `axis` (after it, with
    `reverse`), as a sum and not as a difference of running sums."""
    inc = jax.lax.cumsum(x, axis=axis, reverse=reverse)
    zero = jnp.zeros_like(jax.lax.slice_in_dim(x, 0, 1, axis=axis))
    if reverse:
        return jnp.concatenate(
            [jax.lax.slice_in_dim(inc, 1, None, axis=axis), zero], axis)
    return jnp.concatenate(
        [zero, jax.lax.slice_in_dim(inc, 0, -1, axis=axis)], axis)


def _wkv_chunk(u, s, chunk):
    """One chunk in closed form. s: [B,H,D,D]; chunk: r,k,v,lw [B,Q,H,D]
    -> (S', y [B,Q,H,D] in the dtype of r). Runs in f32; every contraction
    at f32 accuracy."""
    r, k, v, lw = chunk
    dtype = r.dtype
    b, q, h, d = r.shape
    c = min(SUB, q)
    n = q // c
    hi = jax.lax.Precision.HIGHEST
    r, k, v = (t.astype(jnp.float32).swapaxes(1, 2).reshape(b, h, n, c, d)
               for t in (r, k, v))
    lw = jnp.maximum(lw, LOG_DECAY_MIN).swapaxes(1, 2).reshape(b, h, n, c, d)

    # log-decay summed inside each sub-chunk: up to and including token t
    # (a), before it (a_ex), after it (suf); and over whole sub-chunks
    # before sub-chunk i, after it, and strictly between j and i
    a = jnp.cumsum(lw, axis=3)
    a_ex, suf = _excl_cumsum(lw, 3), _excl_cumsum(lw, 3, reverse=True)
    tot = a[:, :, :, -1]                                      # [B,H,n,D]
    before, after = _excl_cumsum(tot, 2), _excl_cumsum(tot, 2, reverse=True)
    lower = np.tril(np.ones((n, n), bool), -1)                # [i, m]: m < i
    between = jnp.sum(jnp.where((lower[:, None] & lower.T[None])[..., None],
                                tot[:, :, None, None], 0.0), axis=4)

    # the state entering the chunk, and the state leaving it
    rq = r * jnp.exp(a_ex + before[:, :, :, None])           # e^{b_{t-1}}
    y = jnp.einsum("bhntd,bhde->bhnte", rq, s, precision=hi)
    kq = k * jnp.exp(suf + after[:, :, :, None])             # e^{b_{Q-1}-b_u}
    s = (jnp.exp(tot.sum(axis=2))[..., None] * s
         + jnp.einsum("bhntd,bhnte->bhde", kq, v, precision=hi))

    # query in sub-chunk i, key in sub-chunk j < i: both decayed to the last
    # token before sub-chunk i
    rs = r * jnp.exp(a_ex)
    ks = k[:, :, None] * jnp.exp(suf[:, :, None] + between[:, :, :, :, None])
    att = jnp.einsum("bhitd,bhijsd->bhitjs", rs, ks, precision=hi)
    # query and key in one sub-chunk: pairwise, the bonus u on the diagonal
    tri = np.tril(np.ones((c, c), bool), -1)[..., None]       # [t, s, 1]
    expo = jnp.where(tri, a_ex[:, :, :, :, None] - a[:, :, :, None], 0.0)
    diag = jnp.sum(r[:, :, :, :, None] * k[:, :, :, None]
                   * jnp.where(tri, jnp.exp(expo), 0.0), axis=-1)
    bonus = jnp.sum(r * u[None, :, None, None] * k, axis=-1)  # [B,H,n,c]
    diag = diag + bonus[..., None] * np.eye(c, dtype=np.float32)
    att = jnp.where(lower[:, None, :, None], att,
                    jnp.where(np.eye(n, dtype=bool)[:, None, :, None],
                              diag[:, :, :, :, None], 0.0))
    y = y.reshape(b, h, q, d) + jnp.einsum(
        "bhts,bhse->bhte", att.reshape(b, h, q, q), v.reshape(b, h, q, d),
        precision=hi)
    return s, y.astype(dtype).swapaxes(1, 2)


def wkv(r, k, v, lw, u, s0):
    """r,k,v: [B,S,H,D]; lw: f32 log-decay <= 0 [B,S,H,D]; u: [H,D];
    s0: [B,H,D,D] f32 -> (y [B,S,H,D] in the dtype of r, s_last). The
    recurrence runs in f32 whatever the dtype of r, k and v. A sequence
    that is not a whole number of chunks is padded with tokens that leave
    the state as it is."""
    b, s, h, d = r.shape
    c = min(SUB, s)
    q = min(CHUNK, -(-s // c) * c)
    pad = -s % q
    nc = (s + pad) // q

    def resh(t):                                  # -> [nc, B, Q, H, D]
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return t.reshape(b, nc, q, h, d).swapaxes(0, 1)

    body = jax.checkpoint(partial(_wkv_chunk, u))
    s_last, ys = jax.lax.scan(body, s0, tuple(map(resh, (r, k, v, lw))))
    y = ys.swapaxes(0, 1).reshape(b, s + pad, h, d)
    return y[:, :s], s_last


def apply_time_mix(p, cfg, x, sel=None, cache=None, length=None):
    """x: [B,S,d]. cache (decode): {"s": [B,H,D,D], "last": [B,d]}.

    length [B] (cached path, None = all s): valid tokens per row. Padded
    rows must not advance the wkv state — their log-decay is forced to 0
    and their key to 0 (S_t = 1·S + 0), and the token-shift "last" is
    taken at the per-row valid end, so the cache comes back exactly as
    after the valid prefix."""
    b, s, d = x.shape
    hd = cfg.rwkv.head_dim

    # Serve-mesh detection: the time-mix mats arrive head-block sharded only
    # when H % shards == 0 (a partial head cannot straddle shards — the wkv
    # scan is head-local); otherwise they stay replicated and this whole
    # path is the single-device one.
    ax = SH.current_mapped_axis()
    d_loc = p["wr"].shape[-1]
    local = ax is not None and d_loc != d
    shard = jax.lax.axis_index(ax) if local else None

    last = cache["last"] if cache is not None else None
    xp = _shift(x, last)
    mu = p["mu"].astype(x.dtype)
    xr, xk, xv, xg, xw = [x + (xp - x) * mu[i] for i in range(5)]

    # column-parallel projections: local head block [B, S, d/n]
    r = smm(xr, p["wr"], sel, "wr").reshape(b, s, -1, hd)
    k = smm(xk, p["wk"], sel, "wk").reshape(b, s, -1, hd)
    v = smm(xv, p["wv"], sel, "wv").reshape(b, s, -1, hd)
    g = smm(xg, p["wg"], sel, "wg")

    # decay lora: wA replicated (tiny), w0/wB sharded with the head block
    wlog = p["w0"] + jnp.tanh(xw.astype(jnp.float32) @ p["wA"]) @ p["wB"]
    lw = -jnp.exp(wlog).reshape(b, s, -1, hd)   # log-decay: w = e^lw in [0,1)

    if length is not None and s > 1:
        valid = (jnp.arange(s)[None, :] < length[:, None])[:, :, None, None]
        k = jnp.where(valid, k, 0.0)          # kv outer product vanishes
        lw = jnp.where(valid, lw, 0.0)        # identity decay: S frozen
    h_eff = r.shape[2]
    if cache is None:
        s0 = jnp.zeros((b, h_eff, hd, hd), jnp.float32)
    elif local:
        # the wkv state enters the shard_map replicated: run the scan on
        # this shard's head block only
        s0 = jax.lax.dynamic_slice_in_dim(cache["s"], shard * h_eff, h_eff,
                                          axis=1)
    else:
        s0 = cache["s"]
    if s == 1:  # decode fast path
        s_new, y = _wkv_step(p["u"], s0, r[:, 0], k[:, 0], v[:, 0],
                             lw[:, 0])
        y = y[:, None]
    else:
        y, s_new = wkv(r, k, v, lw, p["u"], s0)

    if local:
        # ln_x normalizes over the FULL d: gather the head blocks (exact —
        # per-head values are concatenated in shard order)
        y = SH.all_gather_mapped(y, axis=2)
        if cache is not None:
            s_new = SH.all_gather_mapped(s_new, axis=1)
    y = apply_norm(p["ln_x"], y.reshape(b, s, d).astype(x.dtype))
    if local:
        # gate with the local g slice and feed wo row-parallel: one psum
        # reassembles the output
        y_loc = jax.lax.dynamic_slice_in_dim(y, shard * d_loc, d_loc, -1)
        out = jax.lax.psum(smm(y_loc * jax.nn.silu(g), p["wo"], sel, "wo"),
                           ax)
    else:
        y = y * jax.nn.silu(g)
        out = smm(y, p["wo"], sel, "wo")
    new_cache = None if cache is None else {"s": s_new,
                                            "last": last_valid(x, length)}
    return out, new_cache


def init_channel_mix(key, cfg, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "mu": jax.random.uniform(ks[0], (2, d), jnp.float32),  # k,r shifts
        "wk": dense_init(ks[1], (d, ff), dtype=dtype),
        "wv": dense_init(ks[2], (ff, d), dtype=dtype),
        "wr": dense_init(jax.random.fold_in(key, 7), (d, d), dtype=dtype),
    }


def apply_channel_mix(p, cfg, x, sel=None, cache=None, length=None):
    b, s, d = x.shape
    last = cache["last"] if cache is not None else None
    xp = _shift(x, last)
    mu = p["mu"].astype(x.dtype)
    xk = x + (xp - x) * mu[0]
    xr = x + (xp - x) * mu[1]
    # channel-mix is mlp-shaped: wk column-parallel on ff, wv row-parallel
    # (one psum); wr is [d, d] and stays replicated (specs.py _RWKV_CHAN)
    k = jax.nn.relu(smm(xk, p["wk"], sel, "wk"))
    k = k * k
    kv = row_matmul(k, p["wv"], sel, "wv", full_in=cfg.d_ff)
    out = jax.nn.sigmoid(smm(xr, p["wr"], sel, "wr")) * kv
    new_cache = None if cache is None else {"last": last_valid(x, length)}
    return out, new_cache


def init_rwkv_cache(cfg, batch: int, dtype):
    hd = cfg.rwkv.head_dim
    h = num_heads(cfg)
    return {
        "time": {"s": jnp.zeros((batch, h, hd, hd), jnp.float32),
                 "last": jnp.zeros((batch, cfg.d_model), dtype)},
        "chan": {"last": jnp.zeros((batch, cfg.d_model), dtype)},
    }


def rwkv_snapshot_leaves(cfg, dtype):
    """Per-row (shape, dtype) spec of the rwkv6 recurrent state — the wkv
    matrix state S plus the token-shift `last` vectors — as a prefix-cache
    snapshot."""
    hd = cfg.rwkv.head_dim
    h = num_heads(cfg)
    dt = jnp.dtype(dtype)
    return {"time": {"s": ((h, hd, hd), jnp.float32),
                     "last": ((cfg.d_model,), dt)},
            "chan": {"last": ((cfg.d_model,), dt)}}
