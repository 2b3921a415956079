"""Decoder-only LM supporting all assigned families:

dense (llama3/command-r/nemotron/musicgen/qwen2-vl), local:global (gemma3),
MoE (deepseek/llama4), hybrid mamba+attn+MoE (jamba), RWKV-6 (rwkv6).

Layout: layers are grouped into SEGMENTS, each a lax.scan over stacked
params (HLO size O(1) in depth). Heterogeneous periods (gemma 5:1, jamba
1:7) scan over *super-blocks* and unroll the period inside the body.

Training params arrive as a (frozen, trainable) pair of same-structure trees
(split along the stacked-layer axis by the sparse-update plan); the frozen
prefix is never differentiated, so XLA saves no residuals for it — the
paper's activation-memory saving.

`sel` carries dynamic channel-block selection indices (see core.sparse_update).
"""
from __future__ import annotations

import dataclasses
import zlib
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rwkv6 as R
from repro.models.common import (dense_init, embed_init,
                                 vocab_parallel_gather)
from repro.sharding import constrain

CE_CHUNK = 1024
# a block's loss terms and counters, carried through the stacks as one
# vector: the MoE balance and z terms and the slots on held experts add up
# over layers, the largest held expert's rows is a max
AUX = ("load_balance", "router_z", "rows_held", "rows_max")


def _no_aux():
    return jnp.zeros((len(AUX),), jnp.float32)


def _aux_vec(aux: dict):
    return jnp.stack([jnp.asarray(aux[k], jnp.float32) for k in AUX])


def _aux_add(a, b):
    return jnp.concatenate([a[:3] + b[:3], jnp.maximum(a[3:], b[3:])])


class SegmentDef(NamedTuple):
    name: str
    steps: int          # scan length
    kind: str           # dense | moe | gemma_super | jamba_super | rwkv
    layers_per_step: int


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------

def segment_layout(cfg: ModelConfig) -> list[SegmentDef]:
    if cfg.family == "ssm":
        return [SegmentDef("blocks", cfg.num_layers, "rwkv", 1)]
    if cfg.family == "hybrid":
        assert cfg.num_layers % cfg.attn_every == 0
        return [SegmentDef("blocks", cfg.num_layers // cfg.attn_every,
                           "jamba_super", cfg.attn_every)]
    if cfg.attn_pattern.startswith("local_global"):
        _, l, g = cfg.attn_pattern.split(":")
        period = int(l) + int(g)
        n_super = cfg.num_layers // period
        tail = cfg.num_layers - n_super * period
        segs = [SegmentDef("blocks", n_super, "gemma_super", period)]
        if tail:
            segs.append(SegmentDef("tail", tail, "dense", 1))
        return segs
    if cfg.moe is not None and cfg.moe.layout == "all_but_first":
        return [SegmentDef("first", 1, "dense", 1),
                SegmentDef("blocks", cfg.num_layers - 1, "moe", 1)]
    if cfg.moe is not None:
        return [SegmentDef("blocks", cfg.num_layers, "moe", 1)]
    return [SegmentDef("blocks", cfg.num_layers, "dense", 1)]


def _moe_at(cfg, layer_in_period: int) -> bool:
    """For jamba: is the FFN at this in-block index MoE?"""
    if cfg.moe is None:
        return False
    if cfg.moe.layout == "every_2":
        return layer_in_period % 2 == 1
    return True


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_dense_block(key, cfg, dtype, d_ff=None):
    k1, k2 = jax.random.split(key)
    return {
        "attn_ln": L.init_norm(key, cfg.d_model, cfg.norm_kind, dtype),
        "attn": L.init_attention(k1, cfg, dtype),
        "mlp_ln": L.init_norm(key, cfg.d_model, cfg.norm_kind, dtype),
        "mlp": L.init_mlp(k2, cfg, dtype, d_ff=d_ff),
    }


def _init_moe_block(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "attn_ln": L.init_norm(key, cfg.d_model, cfg.norm_kind, dtype),
        "attn": L.init_attention(k1, cfg, dtype),
        "mlp_ln": L.init_norm(key, cfg.d_model, cfg.norm_kind, dtype),
        "moe": MOE.init_moe(k2, cfg, dtype),
    }


def _init_jamba_super(key, cfg, dtype):
    """One super-block: `attn_every` sublayers; index attn_every//2 is
    attention, the rest mamba; FFN alternates dense/MoE."""
    out = {}
    period = cfg.attn_every
    attn_pos = period // 2
    ks = jax.random.split(key, period * 2)
    for i in range(period):
        mixer_key, ffn_key = ks[2 * i], ks[2 * i + 1]
        sub = {"mixer_ln": L.init_norm(mixer_key, cfg.d_model, cfg.norm_kind, dtype),
               "ffn_ln": L.init_norm(ffn_key, cfg.d_model, cfg.norm_kind, dtype)}
        if i == attn_pos:
            sub["attn"] = L.init_attention(mixer_key, cfg, dtype)
        else:
            sub["mamba"] = M.init_mamba(mixer_key, cfg, dtype)
        if _moe_at(cfg, i):
            sub["moe"] = MOE.init_moe(ffn_key, cfg, dtype)
        else:
            sub["mlp"] = L.init_mlp(ffn_key, cfg, dtype)
        out[f"sub{i}"] = sub
    return out


def _init_gemma_super(key, cfg, dtype, period: int):
    ks = jax.random.split(key, period)
    return {f"sub{i}": _init_dense_block(ks[i], cfg, dtype) for i in range(period)}


def _init_rwkv_block(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "time_ln": L.init_norm(k1, cfg.d_model, "layernorm", dtype),
        "time": R.init_time_mix(k1, cfg, dtype),
        "chan_ln": L.init_norm(k2, cfg.d_model, "layernorm", dtype),
        "chan": R.init_channel_mix(k2, cfg, dtype),
    }


def _dense_ff_first(cfg) -> int:
    """The leading dense layer's width (deepseek-style layout)."""
    return cfg.moe.dense_d_ff or 8 * cfg.d_ff


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    segs = segment_layout(cfg)
    kseg, kemb, khead = jax.random.split(key, 3)
    params: dict[str, Any] = {"segments": {}}

    if not cfg.embed_inputs or cfg.tie_embeddings:
        params["embed"] = {"tok": embed_init(kemb, (cfg.vocab_size, cfg.d_model),
                                             dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense_init(khead, (cfg.d_model, cfg.vocab_size),
                                             dtype=dtype)}
    if cfg.family == "ssm":
        params["ln0"] = L.init_norm(kemb, cfg.d_model, "layernorm", dtype)

    for seg in segs:
        keys = jax.random.split(
            jax.random.fold_in(kseg, zlib.crc32(seg.name.encode()) % 2**31),
            seg.steps)
        if seg.kind == "dense":
            d_ff = _dense_ff_first(cfg) if seg.name == "first" else None
            blocks = [_init_dense_block(k, cfg, dtype, d_ff=d_ff) for k in keys]
        elif seg.kind == "moe":
            blocks = [_init_moe_block(k, cfg, dtype) for k in keys]
        elif seg.kind == "gemma_super":
            blocks = [_init_gemma_super(k, cfg, dtype, seg.layers_per_step)
                      for k in keys]
        elif seg.kind == "jamba_super":
            blocks = [_init_jamba_super(k, cfg, dtype) for k in keys]
        elif seg.kind == "rwkv":
            blocks = [_init_rwkv_block(k, cfg, dtype) for k in keys]
        else:
            raise ValueError(seg.kind)
        params["segments"][seg.name] = jax.tree.map(
            lambda *xs: jnp.stack(xs, 0), *blocks)

    params["final_norm"] = L.init_norm(kseg, cfg.d_model,
                                       "layernorm" if cfg.family == "ssm"
                                       else cfg.norm_kind, dtype)
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _window_for(cfg, kind: str, sub: int) -> int:
    if kind == "gemma_super":
        _, l, _g = cfg.attn_pattern.split(":")
        return cfg.sliding_window if sub < int(l) else 0
    if kind == "dense" and cfg.attn_pattern.startswith("local_global"):
        return cfg.sliding_window   # gemma tail layers are local
    return 0


def _sub_sel(sel, name):
    """Subset a selection tuple — (idx, spec) or (idx, spec, wsel) — to one
    child subtree. All components share the idx tree's structure."""
    if sel is None:
        return None
    idx = sel[0]
    if idx is None or name not in idx:
        return None
    return tuple(comp[name] for comp in sel)


def _apply_dense_block(cfg, p, x, positions, sel, window: int):
    h = L.apply_norm(p["attn_ln"], x, cfg.norm_eps)
    with jax.named_scope("token_mix"):
        x = x + L.attention(p["attn"], cfg, h, positions, window=window,
                            sel=_sub_sel(sel, "attn"))
    h = L.apply_norm(p["mlp_ln"], x, cfg.norm_eps)
    with jax.named_scope("channel_mix"):
        x = x + L.apply_mlp(p["mlp"], cfg, h, sel=_sub_sel(sel, "mlp"))
    return x, _no_aux()


def _apply_moe_block(cfg, p, x, positions, sel):
    h = L.apply_norm(p["attn_ln"], x, cfg.norm_eps)
    with jax.named_scope("token_mix"):
        x = x + L.attention(p["attn"], cfg, h, positions,
                            sel=_sub_sel(sel, "attn"))
    h = L.apply_norm(p["mlp_ln"], x, cfg.norm_eps)
    with jax.named_scope("channel_mix"):
        y, aux = MOE.apply_moe(p["moe"], cfg, h, sel=_sub_sel(sel, "moe"))
        x = x + y
    return x, _aux_vec(aux)


def _apply_jamba_super(cfg, p, x, positions, sel):
    period = cfg.attn_every
    attn_pos = period // 2
    aux = _no_aux()
    for i in range(period):
        sub = p[f"sub{i}"]
        ssel = _sub_sel(sel, f"sub{i}")
        h = L.apply_norm(sub["mixer_ln"], x)
        with jax.named_scope("token_mix"):
            if i == attn_pos:
                x = x + L.attention(sub["attn"], cfg, h, positions,
                                    sel=_sub_sel(ssel, "attn"))
            else:
                y, _ = M.apply_mamba(sub["mamba"], cfg, h,
                                     sel=_sub_sel(ssel, "mamba"))
                x = x + y
        h = L.apply_norm(sub["ffn_ln"], x)
        with jax.named_scope("channel_mix"):
            if _moe_at(cfg, i):
                y, a = MOE.apply_moe(sub["moe"], cfg, h,
                                     sel=_sub_sel(ssel, "moe"))
                aux = _aux_add(aux, _aux_vec(a))
            else:
                y = L.apply_mlp(sub["mlp"], cfg, h, sel=_sub_sel(ssel, "mlp"))
            x = x + y
    return x, aux


def _apply_gemma_super(cfg, p, x, positions, sel, period: int):
    for i in range(period):
        sub = p[f"sub{i}"]
        window = _window_for(cfg, "gemma_super", i)
        x, _ = _apply_dense_block(cfg, sub, x, positions,
                                  _sub_sel(sel, f"sub{i}"), window)
    return x, _no_aux()


def _apply_rwkv_block(cfg, p, x, positions, sel):
    h = L.apply_norm(p["time_ln"], x)
    with jax.named_scope("token_mix"):
        y, _ = R.apply_time_mix(p["time"], cfg, h, sel=_sub_sel(sel, "time"))
        x = x + y
    h = L.apply_norm(p["chan_ln"], x)
    with jax.named_scope("channel_mix"):
        y, _ = R.apply_channel_mix(p["chan"], cfg, h,
                                   sel=_sub_sel(sel, "chan"))
        x = x + y
    return x, _no_aux()


def _apply_step(cfg, kind: str, p, x, positions, sel):
    if kind == "dense":
        window = _window_for(cfg, "dense", 0)
        return _apply_dense_block(cfg, p, x, positions, sel, window)
    if kind == "moe":
        return _apply_moe_block(cfg, p, x, positions, sel)
    if kind == "gemma_super":
        _, l, g = cfg.attn_pattern.split(":")
        return _apply_gemma_super(cfg, p, x, positions, sel, int(l) + int(g))
    if kind == "jamba_super":
        return _apply_jamba_super(cfg, p, x, positions, sel)
    if kind == "rwkv":
        return _apply_rwkv_block(cfg, p, x, positions, sel)
    raise ValueError(kind)


def _run_segment(cfg, kind: str, stack, x, positions, sel_idx, sel_spec,
                 remat: bool = True, sel_wsel=None):
    """Scan a segment. sel_idx: stacked [steps, ...] idx tree or None.
    sel_wsel: stacked compact selected-block tree (compact-gradient path)."""
    if stack is None:
        return x, _no_aux()

    # only MoE blocks have loss terms and counters to carry
    carries = kind in ("moe", "jamba_super")

    def body(carry, xs):
        x, aux = carry if carries else (carry, None)
        p_l, idx_l, wsel_l = xs
        if idx_l is None:
            sel = None
        elif wsel_l is None:
            sel = (idx_l, sel_spec)
        else:
            sel = (idx_l, sel_spec, wsel_l)
        x = constrain(x, "batch", "seq", "model_d")
        x, a = _apply_step(cfg, kind, p_l, x, positions, sel)
        return ((x, _aux_add(aux, a)) if carries else x), None

    fn = jax.checkpoint(body) if remat else body
    steps = jax.tree.leaves(stack)[0].shape[0]
    xs = (stack, sel_idx, sel_wsel)
    out, _ = jax.lax.scan(fn, (x, _no_aux()) if carries else x, xs,
                          length=steps)
    return out if carries else (out, _no_aux())


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def _pick(a, b, *path):
    """Fetch a subtree preferring the trainable tree."""
    for tree in (b, a):
        if tree is None:
            continue
        node = tree
        ok = True
        for key in path:
            if node is None or key not in node:
                ok = False
                break
            node = node[key]
        if ok and node is not None:
            return node
    return None


@jax.named_scope("embed")
def embed_tokens(cfg, params_pair, batch):
    frozen, trainable = params_pair
    if cfg.embed_inputs:
        x = batch["embeds"]
    else:
        emb = _pick(frozen, trainable, "embed", "tok")
        # vocab-parallel on the serve mesh (local gather + psum); plain
        # jnp.take otherwise
        x = vocab_parallel_gather(emb, batch["tokens"], cfg.vocab_size)
    if cfg.family == "ssm":
        x = L.apply_norm(_pick(frozen, trainable, "ln0"), x)
    return x


def forward(cfg, params_pair, batch, sel=None, remat: bool = True):
    """params_pair = (frozen_tree, trainable_tree); either may be None.
    batch: {"tokens" [B,S] | "embeds" [B,S,d], optional "positions"}.
    Returns (hidden [B,S,d], aux [2])."""
    frozen, trainable = params_pair
    x = embed_tokens(cfg, params_pair, batch)
    b, s = x.shape[0], x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    aux = _no_aux()
    for seg in segment_layout(cfg):
        f_stack = _pick(frozen, None, "segments", seg.name)
        t_stack = _pick(trainable, None, "segments", seg.name)
        sel_idx = sel_spec = sel_wsel = None
        if sel is not None and seg.name in sel[0]:
            sel_idx, sel_spec = sel[0][seg.name], sel[1][seg.name]
            if len(sel) > 2 and sel[2] is not None:
                sel_wsel = sel[2].get(seg.name)
        with jax.named_scope("frozen_layers"):
            x, a1 = _run_segment(cfg, seg.kind, f_stack, x, positions,
                                 None, None, remat)
        with jax.named_scope("trainable_layers"):
            x, a2 = _run_segment(cfg, seg.kind, t_stack, x, positions,
                                 sel_idx, sel_spec, remat, sel_wsel=sel_wsel)
        aux = _aux_add(_aux_add(aux, a1), a2)
    with jax.named_scope("head_loss"):   # the final norm feeds only the head
        x = L.apply_norm(_pick(frozen, trainable, "final_norm"), x,
                         cfg.norm_eps)
    return x, aux


def lm_head_weight(cfg, params_pair):
    frozen, trainable = params_pair
    if cfg.tie_embeddings:
        return _pick(frozen, trainable, "embed", "tok").T
    return _pick(frozen, trainable, "lm_head", "w")


def chunked_cross_entropy(hidden, w_head, labels, chunk: int = CE_CHUNK):
    """Per-token CE without materializing [B,S,V] logits: scan over sequence
    chunks with rematerialization. Returns (sum_loss, token_count)."""
    b, s, d = hidden.shape
    c = min(chunk, s)
    assert s % c == 0
    nc = s // c
    hs = hidden.reshape(b, nc, c, d).swapaxes(0, 1)
    ls = labels.reshape(b, nc, c).swapaxes(0, 1)

    @jax.checkpoint
    def body(acc, xs):
        h, y = xs
        logits = jnp.einsum("bcd,dv->bcv", h, w_head,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    return total, b * s


def loss_fn(cfg, params_pair, batch, sel=None, remat: bool = True):
    """(loss, metrics): the mean cross-entropy plus, for an MoE model, the
    router terms at `cfg.moe`'s weights; the metrics add the MoE counters
    `moe_rows_held` (routing slots on held experts, over layers) and
    `moe_rows_max` (the largest held expert's rows in any layer)."""
    hidden, aux = forward(cfg, params_pair, batch, sel=sel, remat=remat)
    with jax.named_scope("head_loss"):
        w_head = lm_head_weight(cfg, params_pair)
        total, count = chunked_cross_entropy(hidden, w_head, batch["labels"])
        ce = total / count
    loss = ce
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_weight * aux[0] + cfg.moe.z_weight * aux[1]
    return loss, {"ce": ce, "load_balance": aux[0], "router_z": aux[1],
                  "moe_rows_held": aux[2], "moe_rows_max": aux[3]}
