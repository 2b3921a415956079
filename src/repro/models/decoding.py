"""Serving paths: prefill (full prompt -> cache + last logits) and
single-token decode against per-layer caches, for every model family.

Caches are pytrees stacked along the segment scan axis so decode is also a
lax.scan over layers (carry = hidden state, xs = (params, cache_in),
ys = cache_out).

Sliding-window attention layers keep ring-buffer caches of size `window`
(gemma local layers cache 1024 slots even at 500k context). SSM layers
(mamba/rwkv) cache O(1) recurrent state, which keeps long_500k runnable
for ssm/hybrid/local archs — see DESIGN §Arch-applicability.

Cache families and prefix reuse
-------------------------------
Every mixer's serve cache plays one of three roles (`_paged_layout`):
`paged` (window-free attention — token rows live in shared page pools),
`ring` (sliding-window attention — per-slot ring buffers), and `state`
(mamba/rwkv — per-slot O(1) recurrent state). ALL THREE participate in
prompt-prefix reuse, each through its family's unit of reuse
(`CACHE_FAMILIES`):

- paged layers share their token pages directly (refcounts + COW in
  `serve/paging.py`) — reuse is position-addressed, any page boundary.
- ring and state layers are NOT position-addressed, so their unit of
  reuse is a *snapshot*: the per-row cache leaves (`snapshot_leaves`)
  copied to host at a page-aligned prefill boundary and restored by
  `cache_insert_row` at admission. A restored snapshot is bit-exact
  because chunked prefill always advances in page-sized steps from
  position 0 — identical prefixes replay identical chunk boundaries.

`cache_extract_row` / `cache_insert_row` are the family-uniform
snapshot/restore ops: they tree-map over whatever leaves a family keeps,
so the prefix cache never inspects family internals. `has_state_layers`
tells the engine whether a config needs snapshots at all;
`snapshot_row_bytes` prices one snapshot for budget accounting.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rwkv6 as R
from repro.models import transformer as T
from repro.models.common import last_valid, vocab_parallel_logits
from repro import sharding as SH
from repro.sharding import constrain


def _cache_dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# cache init
# ---------------------------------------------------------------------------

def _kv_cache_spec(cfg, batch, seq_len, window):
    return L.init_kv_cache(cfg, batch, seq_len, window=window,
                           dtype=_cache_dtype(cfg))


def _step_cache(cfg, kind: str, batch: int, seq_len: int):
    dt = _cache_dtype(cfg)
    if kind == "dense":
        window = T._window_for(cfg, "dense", 0)
        return _kv_cache_spec(cfg, batch, seq_len, window)
    if kind == "moe":
        return _kv_cache_spec(cfg, batch, seq_len, 0)
    if kind == "gemma_super":
        _, l, g = cfg.attn_pattern.split(":")
        period = int(l) + int(g)
        return {f"sub{i}": _kv_cache_spec(cfg, batch, seq_len,
                                          T._window_for(cfg, "gemma_super", i))
                for i in range(period)}
    if kind == "jamba_super":
        period = cfg.attn_every
        attn_pos = period // 2
        out = {}
        for i in range(period):
            if i == attn_pos:
                out[f"sub{i}"] = _kv_cache_spec(cfg, batch, seq_len, 0)
            else:
                out[f"sub{i}"] = M.init_mamba_cache(cfg, batch, dt)
        return out
    if kind == "rwkv":
        return R.init_rwkv_cache(cfg, batch, dt)
    raise ValueError(kind)


def init_cache(cfg, batch: int, seq_len: int):
    """Stacked caches per segment (leading axis = scan steps)."""
    cache = {}
    for seg in T.segment_layout(cfg):
        one = _step_cache(cfg, seg.kind, batch, seq_len)
        cache[seg.name] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (seg.steps,) + a.shape), one)
    return cache


# ---------------------------------------------------------------------------
# cache row ops (continuous batching)
#
# Every cache leaf — dense KV k/v, ring-buffer k/v, per-row pos, mamba
# h/conv state, rwkv s/last state — is shaped (scan_steps, batch, ...), so a
# decode *slot* is batch row `i` of every leaf. The serving engine re-prefills
# a finished slot from the queue by running a batch=1 prefill and splicing the
# resulting row into the live batch cache; both ops are pure tree-maps over
# fixed shapes and stay inside a single jitted step (`row` may be traced).
# ---------------------------------------------------------------------------

def cache_extract_row(cache, row):
    """Slice batch row `row` out of every leaf, keeping a batch dim of 1."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1, axis=1), cache)


def cache_insert_row(cache, row_cache, row):
    """Write a batch=1 cache (e.g. from a batch=1 prefill) into batch row
    `row` of every leaf. Overwrites the row completely — k/v (ring caches
    included: prefill zero-fills unused ring slots), recurrent state, and
    pos — so a dirty slot left by a finished request is fully recycled."""
    def ins(dst, src):
        # a smaller update would silently partial-write the row
        assert src.shape[1] == 1 and src.shape[0] == dst.shape[0] \
            and src.shape[2:] == dst.shape[2:], (src.shape, dst.shape)
        return jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), row, axis=1)
    return jax.tree.map(ins, cache, row_cache)


def cache_reset_row(cache, row):
    """Zero batch row `row` of every leaf (slot back to its init state)."""
    def rst(a):
        zero = jnp.zeros((a.shape[0], 1) + a.shape[2:], a.dtype)
        return jax.lax.dynamic_update_slice_in_dim(a, zero, row, axis=1)
    return jax.tree.map(rst, cache)


# ---------------------------------------------------------------------------
# paged serving caches
#
# The serve engine splits per-layer caches into two trees:
#
# - `state`: per-slot leaves, shaped [scan_steps, B, ...] — ring-buffer k/v
#   for sliding-window layers and O(1) recurrent state for ssm/rwkv layers
#   (a state family is effectively a single resident "page" per slot). The
#   row ops above (insert/extract/reset) apply unchanged.
# - `pools`: physical token-row pools for window-free attention layers,
#   shaped [scan_steps, num_pages * page_size, Hkv, D] and shared by ALL
#   slots; a per-slot page table maps logical page -> physical page in every
#   layer's pool simultaneously (one page id indexes all layers).
#
# `paged_step` consumes both trees for s >= 1 tokens per row, so the same
# jitted function serves batched decode (B=num_slots, S=1) and chunked
# prefill (B=1, S=page-sized chunk).
# ---------------------------------------------------------------------------

def _paged_layout(cfg, kind: str):
    """(sub_name | None, 'paged'|'ring'|'state') for each sublayer mixer."""
    if kind in ("dense", "moe"):
        window = T._window_for(cfg, kind, 0) if kind == "dense" else 0
        return [(None, "ring" if window > 0 else "paged")]
    if kind == "gemma_super":
        _, l, g = cfg.attn_pattern.split(":")
        out = []
        for i in range(int(l) + int(g)):
            window = T._window_for(cfg, "gemma_super", i)
            out.append((f"sub{i}", "ring" if window > 0 else "paged"))
        return out
    if kind == "jamba_super":
        attn_pos = cfg.attn_every // 2
        return [(f"sub{i}", "paged" if i == attn_pos else "state")
                for i in range(cfg.attn_every)]
    if kind == "rwkv":
        return [(None, "state")]
    raise ValueError(kind)


def has_paged_layers(cfg) -> bool:
    return any(role == "paged"
               for seg in T.segment_layout(cfg)
               for _, role in _paged_layout(cfg, seg.kind))


def has_state_layers(cfg) -> bool:
    """True when any mixer keeps non-position-addressed cache (ring or
    recurrent state) — prefix reuse for these configs needs recurrent-state
    snapshots at page boundaries, not just shared pages."""
    return any(role != "paged"
               for seg in T.segment_layout(cfg)
               for _, role in _paged_layout(cfg, seg.kind))


class CacheFamily:
    """One cache role's contract with the prefix-reuse stack: what its
    per-row reuse unit looks like. `snapshot_leaves(cfg, kind, sub, max_len,
    dtype)` returns a nested dict of (shape, dtype) specs — the leaves
    `cache_extract_row` yields for one slot of this family (empty for
    `paged`, whose unit of reuse is the shared page itself). Snapshot and
    restore are family-uniform (`cache_extract_row`/`cache_insert_row`
    tree-map over the live leaves), so this protocol only *prices and
    describes* the blob; it never moves data."""

    def __init__(self, role: str, leaves):
        self.role = role
        self._leaves = leaves

    def snapshot_leaves(self, cfg, kind: str, sub: int, max_len: int, dtype):
        return self._leaves(cfg, kind, sub, max_len, dtype)


CACHE_FAMILIES = {
    "paged": CacheFamily("paged", lambda cfg, kind, sub, max_len, dt: {}),
    "ring": CacheFamily(
        "ring", lambda cfg, kind, sub, max_len, dt:
        L.ring_snapshot_leaves(cfg, T._window_for(cfg, kind, sub), max_len,
                               dtype=dt)),
    "state": CacheFamily(
        "state", lambda cfg, kind, sub, max_len, dt:
        R.rwkv_snapshot_leaves(cfg, dt) if kind == "rwkv"
        else M.mamba_snapshot_leaves(cfg, dt)),
}


def snapshot_row_bytes(cfg, max_len: int) -> int:
    """Host bytes of ONE slot's recurrent-state snapshot (every non-paged
    mixer's leaves across all scan steps) — the budget unit for the prefix
    cache's snapshot LRU."""
    dt = _cache_dtype(cfg)
    total = 0
    for seg in T.segment_layout(cfg):
        for i, (_, role) in enumerate(_paged_layout(cfg, seg.kind)):
            leaves = CACHE_FAMILIES[role].snapshot_leaves(
                cfg, seg.kind, i, max_len, dt)
            for shape, leaf_dt in jax.tree.leaves(
                    leaves, is_leaf=lambda x: isinstance(x, tuple)
                    and len(x) == 2 and isinstance(x[0], tuple)):
                total += seg.steps * int(np.prod(shape)) \
                    * jnp.dtype(leaf_dt).itemsize
    return total


def _serve_leaf(cfg, role: str, batch: int, max_len: int, kind: str,
                sub: int, pool_rows: int):
    dt = _cache_dtype(cfg)
    if role == "ring":
        hd = cfg.resolved_head_dim
        window = T._window_for(cfg, kind, sub)
        size = min(window, max_len)
        state = {"k": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dt),
                 "v": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dt)}
        return state, {}
    if role == "paged":
        hd = cfg.resolved_head_dim
        pool = {"k": jnp.zeros((pool_rows, cfg.num_kv_heads, hd), dt),
                "v": jnp.zeros((pool_rows, cfg.num_kv_heads, hd), dt)}
        return {}, pool
    if kind == "rwkv":
        return R.init_rwkv_cache(cfg, batch, dt), {}
    return M.init_mamba_cache(cfg, batch, dt), {}


def init_serve_cache(cfg, batch: int, max_len: int, num_pages: int,
                     page_size: int):
    """Returns (state, pools): per-slot state tree + shared page pools."""
    pool_rows = num_pages * page_size
    state, pools = {}, {}
    for seg in T.segment_layout(cfg):
        st_one, pl_one = {}, {}
        for i, (sub, role) in enumerate(_paged_layout(cfg, seg.kind)):
            s, p = _serve_leaf(cfg, role, batch, max_len, seg.kind, i,
                               pool_rows)
            if sub is None:
                st_one, pl_one = s, p
            else:           # keep tree structures minimal: no empty subdicts
                if s:
                    st_one[sub] = s
                if p:
                    pl_one[sub] = p
        stack = lambda one: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (seg.steps,) + a.shape), one)
        state[seg.name] = stack(st_one)
        pools[seg.name] = stack(pl_one)
    return state, pools


def copy_pool_rows(pools, src_row, dst_row, n: int):
    """Copy `n` physical token rows src -> dst in EVERY layer's pool (the
    device half of a COW split or prefix-page duplication)."""
    def cp(a):
        rows = jax.lax.dynamic_slice_in_dim(a, src_row, n, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(a, rows, dst_row, axis=1)
    return jax.tree.map(cp, pools)


def read_pool_rows(pools, src_row, n: int):
    """Slice `n` physical token rows out of EVERY layer's pool — the device
    half of spilling an evicted prefix page to the host tier."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, src_row, n, axis=1), pools)


def write_pool_rows(pools, rows, dst_row):
    """Write a `read_pool_rows`-shaped tree back into EVERY layer's pool at
    physical row `dst_row` — the device half of rehydrating a spilled page."""
    return jax.tree.map(
        lambda a, r: jax.lax.dynamic_update_slice_in_dim(
            a, r.astype(a.dtype), dst_row, axis=1), pools, rows)


def _delta_sub(delta, *path):
    """Slice a per-layer delta tree ({"idx": ..., "val": ...}, leaves keyed
    by the same sublayer path as the params) down to one sublayer's
    {leaf -> array} dicts; None when that sublayer carries no delta."""
    if delta is None:
        return None
    idx, val = delta["idx"], delta["val"]
    for name in path:
        if not isinstance(idx, dict) or name not in idx:
            return None
        idx, val = idx[name], val[name]
    if not idx:
        return None
    return {"idx": idx, "val": val}


def _paged_block(cfg, kind: str, p, x, start, active, length, st_c, pl_c,
                 page_table, page_size: int, delta=None,
                 flash_decode: bool = False):
    """One scan step of `paged_step`; mirrors `_decode_block` for s >= 1.

    `delta` carries this layer's per-batch-row compact weight deltas (see
    `repro.core.delta`); covered attention/MLP projections apply them as a
    gather-add at matmul time."""
    def attn(sub_p, h, role, window, st, pl, d=None):
        if role == "ring":
            return L.chunk_ring_attention(sub_p, cfg, h, start, active, st,
                                          window=window, length=length,
                                          delta=d)
        a, pool = L.chunk_paged_attention(sub_p, cfg, h, start, active, pl,
                                          page_table, page_size=page_size,
                                          length=length, delta=d,
                                          flash_decode=flash_decode)
        return a, pool

    if kind in ("dense", "moe"):
        window = T._window_for(cfg, kind, 0) if kind == "dense" else 0
        role = "ring" if window > 0 else "paged"
        h = L.apply_norm(p["attn_ln"], x)
        a, c_out = attn(p["attn"], h, role, window, st_c, pl_c,
                        _delta_sub(delta, "attn"))
        x = x + a
        h = L.apply_norm(p["mlp_ln"], x)
        if kind == "moe":
            y, _ = MOE.apply_moe(p["moe"], cfg, h)
        else:
            y = L.apply_mlp(p["mlp"], cfg, h, delta=_delta_sub(delta, "mlp"))
        x = x + y
        return (x, c_out, {}) if role == "ring" else (x, {}, c_out)
    if kind == "gemma_super":
        new_st, new_pl = {}, {}
        for i, (sub, role) in enumerate(_paged_layout(cfg, kind)):
            sp = p[sub]
            window = T._window_for(cfg, kind, i)
            h = L.apply_norm(sp["attn_ln"], x)
            a, c_out = attn(sp["attn"], h, role, window,
                            st_c.get(sub), pl_c.get(sub),
                            _delta_sub(delta, sub, "attn"))
            if role == "ring":
                new_st[sub] = c_out
            else:
                new_pl[sub] = c_out
            x = x + a
            h = L.apply_norm(sp["mlp_ln"], x)
            x = x + L.apply_mlp(sp["mlp"], cfg, h,
                                delta=_delta_sub(delta, sub, "mlp"))
        return x, new_st, new_pl
    if kind == "jamba_super":
        attn_pos = cfg.attn_every // 2
        new_st, new_pl = {}, {}
        for i in range(cfg.attn_every):
            sub = f"sub{i}"
            sp = p[sub]
            h = L.apply_norm(sp["mixer_ln"], x)
            if i == attn_pos:
                a, new_pl[sub] = attn(sp["attn"], h, "paged", 0, None,
                                      pl_c[sub],
                                      _delta_sub(delta, sub, "attn"))
                x = x + a
            else:
                y, new_st[sub] = M.apply_mamba(sp["mamba"], cfg, h,
                                               cache=st_c[sub], length=length)
                x = x + y
            h = L.apply_norm(sp["ffn_ln"], x)
            if T._moe_at(cfg, i):
                y, _ = MOE.apply_moe(sp["moe"], cfg, h)
            else:
                y = L.apply_mlp(sp["mlp"], cfg, h,
                                delta=_delta_sub(delta, sub, "mlp"))
            x = x + y
        return x, new_st, new_pl
    if kind == "rwkv":
        h = L.apply_norm(p["time_ln"], x)
        y, tc = R.apply_time_mix(p["time"], cfg, h, cache=st_c["time"],
                                 length=length)
        x = x + y
        h = L.apply_norm(p["chan_ln"], x)
        y, cc = R.apply_channel_mix(p["chan"], cfg, h, cache=st_c["chan"],
                                    length=length)
        return x + y, {"time": tc, "chan": cc}, {}
    raise ValueError(kind)


def paged_step(cfg, params, batch, state, pools, page_table, *,
               page_size: int, deltas=None, flash_decode: bool = False):
    """s >= 1 tokens per batch row against the paged serve caches.

    batch: {"tokens" [B,S] | "embeds" [B,S,d], "start" [B], "active" [B],
    "length" [B] (optional, default S)}. `start` is the per-row token count
    already cached (the chunk occupies positions start..start+length); rows
    with active=False keep ALL their state (per-row leaves are row-selected
    here, pool writes are dropped inside the attention). `length` lets the
    engine pad every prefill chunk to one fixed page-sized shape — a single
    trace for all prompt lengths — with padded positions (j >= length)
    contributing nothing: cache/pool writes dropped, recurrent state
    frozen, and the returned logits taken at each row's position length-1.

    `deltas` (optional) is {seg_name: {"idx": ..., "val": ...}} of per-user
    compact weight deltas whose leaves are [scan_steps, B, ...] — they ride
    the layer scan next to the params, and each batch row applies its own
    delta as a gather-add inside the covered matmuls. Zero rows are exact
    no-ops, so one trace serves personalized and plain rows alike; the
    engine passes a fixed structure (or None) so the trace count is
    unchanged vs. non-personalized serving.
    Returns (last-valid-position logits [B, V], state, pools).
    """
    start = batch["start"]
    active = batch["active"]
    length = batch.get("length")
    pair = (params, None)
    x = T.embed_tokens(cfg, pair, batch)

    def merge(new, old):
        return jax.tree.map(
            lambda n, o: jnp.where(
                active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, old)

    new_state, new_pools = {}, {}
    for seg in T.segment_layout(cfg):
        stack = params["segments"][seg.name]
        d_seg = None if deltas is None else deltas.get(seg.name)

        def body(x, xs, d_seg=d_seg):
            if d_seg is None:
                p_l, st_l, pl_l = xs
                d_l = None
            else:
                p_l, st_l, pl_l, d_l = xs
            x = constrain(x, "batch", "seq", "model_d")
            x, st_out, pl_out = _paged_block(
                cfg, seg.kind, p_l, x, start, active, length, st_l, pl_l,
                page_table, page_size, delta=d_l, flash_decode=flash_decode)
            return x, (merge(st_out, st_l), pl_out)

        xs = (stack, state[seg.name], pools[seg.name])
        if d_seg is not None:
            xs = xs + (d_seg,)
        x, (new_state[seg.name], new_pools[seg.name]) = jax.lax.scan(
            body, x, xs)
    x = L.apply_norm(T._pick(params, None, "final_norm"), x)
    # each row's last VALID position (prefill chunks are padded)
    x_last = last_valid(x, length)
    w_head = T.lm_head_weight(cfg, pair)
    # vocab-parallel on the serve mesh: local [B, V/n] einsum + all_gather
    logits = vocab_parallel_logits(x_last, w_head, cfg.vocab_size)
    return logits, new_state, new_pools


# ---------------------------------------------------------------------------
# sharded serving: paged_step through shard_map over the model axis
#
# Page pools shard over KV heads (logical axis "paged_pool" -> model); page
# tables, batch rows, and per-slot recurrent/ring state stay replicated
# ("page_table" -> None; state shards slice their block in and all_gather it
# back out). EVERY weight matmul in the step is tensor-parallel whenever its
# sharded dim divides the mesh: attention (paged AND ring) runs
# head-parallel (wq/wk/wv by output head blocks, wo by input rows, one psum
# after wo), MLPs and MoE expert FFNs split d_ff column/row-parallel with
# one psum after w_down, mamba splits d_inner (in/x/out projections
# row-parallel), rwkv time-mix splits by head block and channel-mix by d_ff,
# and the embedding/LM head are vocab-parallel. The tiny remainder — norms,
# routers, decay loras — is replicated. Detection is SHAPE-BASED at every
# site: `paged_param_specs` only shards dims divisible by the mesh size, and
# model code compares the local leaf shape against the full dim, so an
# indivisible group silently falls back to the replicated single-device path
# (and the replication audit's allowlist matches by construction).
#
# GQA head-block sharding keeps groups aligned: shard i holds q heads
# [i*Hq/n, (i+1)*Hq/n) and kv heads [i*Hkv/n, (i+1)*Hkv/n), Hq/n = g*Hkv/n.
#
# Per-user deltas ride the same step: each delta leaf stays replicated and
# the col/row_matmul sites apply it only on the shard owning the selected
# block (column-parallel) or slice its d_in rows before the psum
# (row-parallel) — bit-identical to the single-device gather-add.
# ---------------------------------------------------------------------------

def validate_pool_sharding(cfg, rules) -> int:
    """Number of model-axis shards the page pools will split into; raises
    with a clear message when the head counts cannot shard that many ways
    (silent mis-sharding would desync pools from their replicated page
    tables)."""
    if rules is None or rules.model_axis is None:
        return 1
    with SH.use_rules(rules):
        n = SH.model_axis_size()     # raises if rules carry no mesh
    if n == 1 or not has_paged_layers(cfg):
        return n
    if cfg.num_kv_heads % n != 0:
        raise ValueError(
            f"cannot shard page pools {n}-way over the model axis: "
            f"num_kv_heads={cfg.num_kv_heads} is not divisible by the "
            f"model-axis size {n} (pool leaves are [rows, Hkv, head_dim])")
    if cfg.num_heads % n != 0:
        raise ValueError(
            f"cannot shard paged attention {n}-way over the model axis: "
            f"num_heads={cfg.num_heads} is not divisible by the "
            f"model-axis size {n}")
    return n


def pool_pspec(rules):
    """PartitionSpec of every page-pool leaf [steps, rows, Hkv, head_dim]
    under `rules` — the "paged_pool" logical rule on the KV-head axis.
    Returned in jax's NORMALIZED form (trailing Nones stripped, size-1 mesh
    axes dropped): sharding equality — and therefore the jitted step's
    dispatch cache key — compares normalized specs, so pinning pools to any
    other spelling would make the first call key a duplicate entry."""
    from jax.sharding import PartitionSpec as P
    ax = rules.rules.get("paged_pool")
    if ax is not None and rules.mesh is not None \
            and rules.mesh.shape.get(ax, 1) == 1:
        ax = None
    return P() if ax is None else P(None, None, ax)


def paged_param_specs(cfg, params, rules):
    """PartitionSpec tree for serve params: every matmul weight shards over
    the model axis when its sharded dim divides the mesh size (attention by
    head block, MLP/MoE/rwkv-channel by d_ff, mamba by d_inner, rwkv
    time-mix by head block, embed/LM head by vocab); norms, routers, and any
    group failing its divisibility check stay replicated — model code
    detects the fallback from the leaf shapes. Segment leaves carry a
    leading scan-steps axis; embed/lm_head do not."""
    from jax.sharding import PartitionSpec as P
    axis = rules.model_axis
    n = rules.mesh.shape[axis] if (rules.mesh is not None and axis) else 1
    specs = jax.tree.map(lambda _: P(), params)

    def set_group(ts, name, spec):
        # overwrite only the named leaves; nested dicts (ln_x, shared)
        # keep their already-replicated structure
        if spec is None or name not in ts:
            return
        for k, v in spec.items():
            if k in ts[name]:
                ts[name][k] = v

    heads_ok = cfg.num_heads % n == 0 and cfg.num_kv_heads % n == 0
    attn_spec = {"wq": P(None, None, axis), "wk": P(None, None, axis),
                 "wv": P(None, None, axis), "wo": P(None, axis, None)}

    def mlp_spec(p_mlp):
        if p_mlp["w_up"].shape[-1] % n:
            return None
        return {"w_gate": P(None, None, axis), "w_up": P(None, None, axis),
                "w_down": P(None, axis, None)}

    mamba_ok = M.d_inner(cfg) % n == 0 and cfg.d_model % n == 0 \
        if cfg.ssm is not None else False
    mamba_spec = {"in_proj": P(None, axis, None), "conv_w": P(None, None, axis),
                  "conv_b": P(None, axis), "x_proj": P(None, axis, None),
                  "dt_proj": P(None, None, axis), "dt_bias": P(None, axis),
                  "A_log": P(None, axis, None), "D": P(None, axis),
                  "out_proj": P(None, axis, None)}
    rwkv_ok = cfg.rwkv is not None and R.num_heads(cfg) % n == 0
    time_spec = {"wr": P(None, None, axis), "wk": P(None, None, axis),
                 "wv": P(None, None, axis), "wg": P(None, None, axis),
                 "wo": P(None, axis, None), "w0": P(None, axis),
                 "wB": P(None, None, axis), "u": P(None, axis, None)}
    chan_spec = {"wk": P(None, None, axis), "wv": P(None, axis, None)} \
        if cfg.d_ff % n == 0 else None

    for seg in T.segment_layout(cfg):
        seg_p = params["segments"][seg.name]
        seg_s = specs["segments"][seg.name]
        for sub, role in _paged_layout(cfg, seg.kind):
            tp = seg_p if sub is None else seg_p[sub]
            ts = seg_s if sub is None else seg_s[sub]
            if "attn" in tp and (role == "paged" or heads_ok):
                # paged layers are validated divisible up front
                set_group(ts, "attn", attn_spec)
            if "mamba" in tp and mamba_ok:
                set_group(ts, "mamba", mamba_spec)
            if "time" in tp and rwkv_ok:
                set_group(ts, "time", time_spec)
            if "chan" in tp:
                set_group(ts, "chan", chan_spec)
            if "mlp" in tp:
                set_group(ts, "mlp", mlp_spec(tp["mlp"]))
            if "moe" in tp:
                if cfg.d_ff % n == 0:
                    set_group(ts, "moe", {
                        "w_gate": P(None, None, None, axis),
                        "w_up": P(None, None, None, axis),
                        "w_down": P(None, None, axis, None)})
                if "shared" in tp["moe"]:
                    sh = mlp_spec(tp["moe"]["shared"])
                    if sh is not None:
                        set_group(ts["moe"], "shared", sh)
    if cfg.vocab_size % n == 0:
        if "embed" in specs:
            specs["embed"]["tok"] = P(axis, None)
        if "lm_head" in specs:
            specs["lm_head"]["w"] = P(None, axis)
    return specs


def sharded_param_shapes(cfg, params, rules):
    """(forbidden, replicated) full per-matmul shapes for the replication
    audit. `forbidden` holds the FULL (unsharded) shape of every
    spec-sharded leaf — a dot_general consuming such a shape inside the
    sharded step means the leaf arrived replicated and the per-shard FLOP
    saving silently reverted. Segment leaves drop their leading scan-steps
    axis (the scan body consumes per-step slices). Two collision classes
    are subtracted into the `replicated` allowlist: full shapes that ALSO
    belong to a policy-replicated leaf (e.g. rwkv channel-mix wr [d, d]
    colliding with a sharded time-mix wr), and full shapes coinciding with
    some leaf's POST-SHARD local shape (smoke configs set d_ff = 2 d, so
    the n=2 local w_gate [d, d] is a legitimate matmul that must not match
    a forbidden full wq [d, d])."""
    specs = paged_param_specs(cfg, params, rules)
    axis = rules.model_axis
    n = rules.mesh.shape[axis] if (rules.mesh is not None and axis) else 1
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: x is None)
    forbidden, replicated = set(), set()
    for (path, leaf), spec in zip(flat_p, flat_s):
        keys = [getattr(k, "key", None) for k in path]
        scan = bool(keys) and keys[0] == "segments"
        shape = tuple(leaf.shape)
        local = tuple(d // n if (i < len(spec) and spec[i] is not None)
                      else d for i, d in enumerate(shape))
        if scan:
            shape, local = shape[1:], local[1:]
        if len(shape) < 2:
            continue      # vectors never feed a dot_general contraction
        if any(a is not None for a in spec):
            forbidden.add(shape)
            replicated.add(local)
        else:
            replicated.add(shape)
    return forbidden - replicated, replicated


def make_sharded_paged_step(cfg, rules, params, *, page_size: int,
                            flash_decode: bool = True):
    """Build a jitted `paged_step` that runs through shard_map over
    `rules.model_axis`. Signature matches the single-device step
    (`(params, batch, state, pools, page_table, deltas)`), per-user deltas
    included: delta leaves cross the shard_map replicated and each
    col/row_matmul site applies its shard's share (see the contract comment
    above). The deltas shard_map is built lazily, keyed by the deltas tree
    structure — the engine passes one fixed structure (or always None), so
    the jit trace count stays at one per batch shape, exactly as on a
    single device. `params` is only used for its tree structure/shapes
    (in_specs are a full pytree over the param leaves)."""
    from jax.sharding import PartitionSpec as P

    mesh, axis = rules.mesh, rules.model_axis
    validate_pool_sharding(cfg, rules)
    param_specs = paged_param_specs(cfg, params, rules)
    io_specs = dict(out_specs=(P(), P(), pool_pspec(rules)), check_vma=False)

    def body(p, batch, state, pools, pt, deltas=None):
        # inside shard_map arrays are per-shard locals: GSPMD constraints
        # (use_rules) do not apply, and row-parallel partials psum over
        # `axis`
        with SH.use_rules(None), SH.mapped_model_axis(axis):
            return paged_step(cfg, p, batch, state, pools, pt,
                              page_size=page_size, deltas=deltas,
                              flash_decode=flash_decode)

    base = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, P(), P(), pool_pspec(rules), P()),
        **io_specs))
    delta_steps: dict[Any, Any] = {}

    def call(p, batch, state, pools, pt, deltas=None):
        if deltas is None:
            return base(p, batch, state, pools, pt)
        key = jax.tree.structure(deltas)
        step = delta_steps.get(key)
        if step is None:
            step = jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(param_specs, P(), P(), pool_pspec(rules), P(),
                          jax.tree.map(lambda _: P(), deltas)),
                **io_specs))
            delta_steps[key] = step
        return step(p, batch, state, pools, pt, deltas)

    def cache_size():
        sizes = [getattr(s, "_cache_size", lambda: -1)()
                 for s in [base] + list(delta_steps.values())]
        return -1 if any(s < 0 for s in sizes) else sum(sizes)

    call._cache_size = cache_size
    return call


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def _decode_block(cfg, kind: str, p, x, positions, cache):
    if kind in ("dense", "moe"):
        h = L.apply_norm(p["attn_ln"], x)
        window = T._window_for(cfg, kind, 0) if kind == "dense" else 0
        a, cache = L.decode_attention(p["attn"], cfg, h, positions, cache,
                                      window=window)
        x = x + a
        h = L.apply_norm(p["mlp_ln"], x)
        if kind == "moe":
            y, _ = MOE.apply_moe(p["moe"], cfg, h)
        else:
            y = L.apply_mlp(p["mlp"], cfg, h)
        return x + y, cache
    if kind == "gemma_super":
        _, l, g = cfg.attn_pattern.split(":")
        period = int(l) + int(g)
        new_cache = {}
        for i in range(period):
            sub = p[f"sub{i}"]
            window = T._window_for(cfg, "gemma_super", i)
            h = L.apply_norm(sub["attn_ln"], x)
            a, new_cache[f"sub{i}"] = L.decode_attention(
                sub["attn"], cfg, h, positions, cache[f"sub{i}"], window=window)
            x = x + a
            h = L.apply_norm(sub["mlp_ln"], x)
            x = x + L.apply_mlp(sub["mlp"], cfg, h)
        return x, new_cache
    if kind == "jamba_super":
        period = cfg.attn_every
        attn_pos = period // 2
        new_cache = {}
        for i in range(period):
            sub = p[f"sub{i}"]
            h = L.apply_norm(sub["mixer_ln"], x)
            if i == attn_pos:
                a, new_cache[f"sub{i}"] = L.decode_attention(
                    sub["attn"], cfg, h, positions, cache[f"sub{i}"])
                x = x + a
            else:
                y, new_cache[f"sub{i}"] = M.apply_mamba(
                    sub["mamba"], cfg, h, cache=cache[f"sub{i}"])
                x = x + y
            h = L.apply_norm(sub["ffn_ln"], x)
            if T._moe_at(cfg, i):
                y, _ = MOE.apply_moe(sub["moe"], cfg, h)
            else:
                y = L.apply_mlp(sub["mlp"], cfg, h)
            x = x + y
        return x, new_cache
    if kind == "rwkv":
        h = L.apply_norm(p["time_ln"], x)
        y, tc = R.apply_time_mix(p["time"], cfg, h, cache=cache["time"])
        x = x + y
        h = L.apply_norm(p["chan_ln"], x)
        y, cc = R.apply_channel_mix(p["chan"], cfg, h, cache=cache["chan"])
        return x + y, {"time": tc, "chan": cc}
    raise ValueError(kind)


def decode_step(cfg, params, batch, cache):
    """One token for the whole batch.

    batch: {"tokens" [B,1] | "embeds" [B,1,d], "positions" [B,1] or [3,B,1]}
    Returns (logits [B, V], new_cache).
    """
    pair = (params, None)
    x = T.embed_tokens(cfg, pair, batch)
    positions = batch.get("positions")
    if positions is None:
        raise ValueError("decode_step requires explicit positions")

    new_cache = {}
    for seg in T.segment_layout(cfg):
        stack = params["segments"][seg.name]

        def body(x, xs):
            p_l, c_l = xs
            x = constrain(x, "batch", "seq", "model_d")
            x, c_out = _decode_block(cfg, seg.kind, p_l, x, positions, c_l)
            return x, c_out

        x, new_cache[seg.name] = jax.lax.scan(
            body, x, (stack, cache[seg.name]))
    x = L.apply_norm(T._pick(params, None, "final_norm"), x)
    w_head = T.lm_head_weight(cfg, pair)
    logits = jnp.einsum("bsd,dv->bsv", x, w_head,
                        preferred_element_type=jnp.float32)
    return logits[:, -1], new_cache


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def prefill(cfg, params, batch, pad_to: int = 0):
    """Run the full prompt, returning (last-token logits [B, V], cache).

    Attention layers: compute K/V for the whole prompt and write them into
    the cache (ring-layout for windowed layers). SSM layers: run the
    recurrence and keep the final state.
    """
    pair = (params, None)
    x = T.embed_tokens(cfg, pair, batch)
    b, s = x.shape[0], x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    pad_to = max(pad_to, s)
    cache = {}
    for seg in T.segment_layout(cfg):
        stack = params["segments"][seg.name]

        def body(x, p_l):
            x = constrain(x, "batch", "seq", "model_d")
            x, c_out = _prefill_block(cfg, seg.kind, p_l, x, positions, pad_to)
            return x, c_out

        x, cache[seg.name] = jax.lax.scan(body, x, stack)
    x = L.apply_norm(T._pick(params, None, "final_norm"), x)
    w_head = T.lm_head_weight(cfg, pair)
    logits = jnp.einsum("bd,dv->bv", x[:, -1], w_head,
                        preferred_element_type=jnp.float32)
    return logits, cache


def _ring_pack(k, window: int):
    """Pack the last `window` positions of k [B,S,H,D] into a ring buffer of
    exactly `window` slots (position p lives at slot p % window)."""
    b, s, h, d = k.shape
    out = jnp.zeros((b, window, h, d), k.dtype)
    n = min(s, window)
    tail = k[:, s - n:]
    slots = jnp.arange(s - n, s) % window
    return out.at[:, slots].set(tail)


def _pad_cache(k, pad_to: int):
    b, s, h, d = k.shape
    if pad_to <= s:
        return k
    return jnp.pad(k, ((0, 0), (0, pad_to - s), (0, 0), (0, 0)))


def _prefill_attn(cfg, p, x, positions, window, pad_to):
    b, s, _ = x.shape
    q, k, v = L._qkv(p, cfg, x, positions)
    if s > 2048:
        out = L._sdpa_flash(q, k, v, window)
    else:
        out = L._sdpa_dense(q, k, v, window)
    out = out.reshape(b, s, -1)
    out = jnp.matmul(out, p["wo"])
    if window > 0:
        # cap the ring at the cache capacity: when window >= pad_to the ring
        # never wraps, and init_kv_cache sizes the cache the same way, so
        # prefill rows stay insertable into an init_cache'd batch cache
        w = min(window, pad_to)
        kc = _ring_pack(k, w).astype(_cache_dtype(cfg))
        vc = _ring_pack(v, w).astype(_cache_dtype(cfg))
    else:
        kc = _pad_cache(k, pad_to).astype(_cache_dtype(cfg))
        vc = _pad_cache(v, pad_to).astype(_cache_dtype(cfg))
    cache = {"k": kc, "v": vc, "pos": jnp.full((b,), s, jnp.int32)}
    return out, cache


def _prefill_block(cfg, kind: str, p, x, positions, pad_to):
    if kind in ("dense", "moe"):
        window = T._window_for(cfg, kind, 0) if kind == "dense" else 0
        h = L.apply_norm(p["attn_ln"], x)
        a, cache = _prefill_attn(cfg, p["attn"], h, positions, window, pad_to)
        x = x + a
        h = L.apply_norm(p["mlp_ln"], x)
        if kind == "moe":
            y, _ = MOE.apply_moe(p["moe"], cfg, h)
        else:
            y = L.apply_mlp(p["mlp"], cfg, h)
        return x + y, cache
    if kind == "gemma_super":
        _, l, g = cfg.attn_pattern.split(":")
        period = int(l) + int(g)
        caches = {}
        for i in range(period):
            sub = p[f"sub{i}"]
            window = T._window_for(cfg, "gemma_super", i)
            h = L.apply_norm(sub["attn_ln"], x)
            a, caches[f"sub{i}"] = _prefill_attn(cfg, sub["attn"], h,
                                                 positions, window, pad_to)
            x = x + a
            h = L.apply_norm(sub["mlp_ln"], x)
            x = x + L.apply_mlp(sub["mlp"], cfg, h)
        return x, caches
    if kind == "jamba_super":
        period = cfg.attn_every
        attn_pos = period // 2
        caches = {}
        for i in range(period):
            sub = p[f"sub{i}"]
            h = L.apply_norm(sub["mixer_ln"], x)
            if i == attn_pos:
                a, caches[f"sub{i}"] = _prefill_attn(cfg, sub["attn"], h,
                                                     positions, 0, pad_to)
                x = x + a
            else:
                y, state = _mamba_prefill_state(sub["mamba"], cfg, h)
                caches[f"sub{i}"] = state
                x = x + y
            h = L.apply_norm(sub["ffn_ln"], x)
            if T._moe_at(cfg, i):
                y, _ = MOE.apply_moe(sub["moe"], cfg, h)
            else:
                y = L.apply_mlp(sub["mlp"], cfg, h)
            x = x + y
        return x, caches
    if kind == "rwkv":
        h = L.apply_norm(p["time_ln"], x)
        y, ts = _rwkv_prefill_time(p["time"], cfg, h)
        x = x + y
        h = L.apply_norm(p["chan_ln"], x)
        y, _ = R.apply_channel_mix(p["chan"], cfg, h)
        cc = {"last": h[:, -1]}
        return x + y, {"time": ts, "chan": cc}
    raise ValueError(kind)


def _mamba_prefill_state(p, cfg, x):
    """apply_mamba returning the final recurrent state as a cache."""
    b, s, _ = x.shape
    dt = _cache_dtype(cfg)
    out, _ = M.apply_mamba(p, cfg, x)
    # final conv history = last (d_conv-1) post-in_proj activations
    xz = jnp.matmul(x, p["in_proj"])
    x_in = xz[..., : M.d_inner(cfg)]
    conv = x_in[:, -(cfg.ssm.d_conv - 1):]
    pad = cfg.ssm.d_conv - 1 - conv.shape[1]
    if pad > 0:   # prompt shorter than the history: oldest slots stay zero
        conv = jnp.pad(conv, ((0, 0), (pad, 0), (0, 0)))
    # final ssm state: recompute the scan's last carry
    h_last = _mamba_last_state(p, cfg, x)
    return out, {"h": h_last, "conv": conv.astype(dt)}


def _mamba_last_state(p, cfg, x):
    b = x.shape[0]
    xz = jnp.matmul(x, p["in_proj"])
    x_in = xz[..., : M.d_inner(cfg)]
    x_c = jax.nn.silu(M._causal_depthwise_conv(x_in, p["conv_w"], p["conv_b"]))
    dbl = jnp.matmul(x_c, p["x_proj"])
    dr = M.dt_rank(cfg)
    ns = cfg.ssm.d_state
    dtv, b_ssm, c_ssm = jnp.split(dbl, [dr, dr + ns], axis=-1)
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) @ p["dt_proj"].astype(jnp.float32)
                          + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    h0 = jnp.zeros((b, M.d_inner(cfg), ns), jnp.float32)
    _, h_last = M.selective_scan(a, dtv, x_c.astype(jnp.float32),
                                 b_ssm.astype(jnp.float32),
                                 c_ssm.astype(jnp.float32), h0)
    return h_last


def _rwkv_prefill_time(p, cfg, x):
    y, _ = R.apply_time_mix(p, cfg, x)
    # final state via a dedicated wkv pass
    b, s, d = x.shape
    hd = cfg.rwkv.head_dim
    h = R.num_heads(cfg)
    xp = R._shift(x)
    mu = p["mu"].astype(x.dtype)
    xr, xk, xv, xg, xw = [x + (xp - x) * mu[i] for i in range(5)]
    r = jnp.matmul(xr, p["wr"]).reshape(b, s, h, hd).astype(jnp.float32)
    k = jnp.matmul(xk, p["wk"]).reshape(b, s, h, hd).astype(jnp.float32)
    v = jnp.matmul(xv, p["wv"]).reshape(b, s, h, hd).astype(jnp.float32)
    wlog = p["w0"] + jnp.tanh(xw.astype(jnp.float32) @ p["wA"]) @ p["wB"]
    w = jnp.exp(-jnp.exp(wlog)).reshape(b, s, h, hd)
    s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    _, s_last = R.wkv(r, k, v, w, p["u"], s0)
    return y, {"s": s_last, "last": x[:, -1]}
