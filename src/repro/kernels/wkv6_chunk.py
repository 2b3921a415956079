"""Pallas TPU kernel: chunked RWKV-6 WKV recurrence (one head-block).

The pure-JAX model (models/rwkv6.py) runs the recurrence as a sequential
lax.scan — exact, but latency-bound on TPU (one tiny [D,D] update per
step). This kernel processes CHUNK timesteps per grid step with the
classic two-part decomposition, keeping the state in VMEM scratch across
the sequential grid dimension:

    intra-chunk:  y_t += r_t . (decay(t,u) k_u v_u^T) for u <= t in chunk
                  (dense [C,C] masked matmuls on the MXU)
    inter-chunk:  y_t += (r_t * prod_decay(<=t)) . S;  S <- decayed S + chunk kv

Shapes (per (batch, head) grid cell):
    r, k, v, w: [T, D]  (w = per-channel decay in (0,1)); u: [D]
    out:        [T, D]
Grid: (B*H, T/C) with the time dimension "arbitrary" (sequential), state
S [D, D] in VMEM scratch.

Numerics: decays are accumulated in log space within a chunk (w in (0,1)
=> logs <= 0; C=32/64 keeps exp() in fp32 range), matching the oracle to
~1e-5 fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *, chunk: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)          # [C, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)        # [1, D]
    s = s_ref[...]                            # [D, D]

    logw = jnp.log(jnp.maximum(w, 1e-12))     # [C, D], <= 0
    cum = jnp.cumsum(logw, axis=0)            # prod of decays up to & incl. t

    # inter-chunk: y_t = (r_t * exp(cum_{t-1})) @ S ; cum_{t-1} = cum_t - logw_t
    r_dec = r * jnp.exp(cum - logw)
    y = jax.lax.dot_general(r_dec, s, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # intra-chunk: att[t,u] = sum_d r[t,d] k[u,d] exp(cum_{t-1,d} - cum_{u,d})
    # for u < t; diagonal uses the bonus u instead of decay.
    rd = r * jnp.exp(cum - logw)              # exp(cum_{t-1})
    ku = k * jnp.exp(-cum)                    # exp(-cum_u)
    att = jax.lax.dot_general(rd, ku, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [C, C]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    u_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(u_idx < t_idx, att, 0.0)
    diag = jnp.sum(r * (u * k), axis=1)       # bonus term at u == t
    y += jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y += diag[:, None] * v

    # state update: S <- diag(prod w) S + sum_u (k_u * exp(cum_C - cum_u)) v_u^T
    k_tail = k * jnp.exp(cum[-1:] - cum)
    s_new = jnp.exp(cum[-1])[:, None] * s + jax.lax.dot_general(
        k_tail, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = s_new
    o_ref[0] = y.astype(o_ref.dtype)


def wkv6_chunk_kernel(r, k, v, w, u, *, chunk: int = 32,
                      interpret: bool = False):
    """r,k,v,w: [BH, T, D] (already merged batch*heads); u: [D].
    Returns y [BH, T, D] (fp32)."""
    bh, t, d = r.shape
    chunk = min(chunk, t)
    assert t % chunk == 0
    grid = (bh, t // chunk)
    u2 = u.reshape(1, d)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, d), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="wkv6_chunk",
    )(r, k, v, w, u2)
