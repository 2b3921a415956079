"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles
(interpret mode executes the kernel body exactly as staged for TPU).

The fused single-launch kernels (PR 3) are swept across n_shards in
{1, 2, 4}, stacked K in {1, 3}, odd n_sel, and bf16/f32 params; the fused
optimizer is bitwise vs the un-fused oracle for SGD and allclose for
momentum/AdamW.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing import given, settings, st

from repro.kernels import ref
from repro.kernels.batched_dw import batched_dw_kernel
from repro.kernels.block_act_prune import block_act_prune_kernel
from repro.kernels.fused_block_opt import fused_block_opt_kernel
from repro.kernels.masked_dw import (block_sparse_dw_kernel,
                                     block_sparse_dw_pipelined_kernel)
from repro.kernels.scatter_blocks import block_scatter_update_kernel


def _sel_idx(rng, lead_shape, n_blocks, n_sel):
    """Random no-duplicate selection of shape [*lead_shape, n_sel]."""
    flat = [rng.choice(n_blocks, n_sel, replace=False)
            for _ in range(int(np.prod(lead_shape)))]
    return jnp.asarray(np.stack(flat).reshape(lead_shape + (n_sel,)),
                       jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("m,k,nb,block,n_sel,tm,tk", [
    (64, 32, 4, 16, 3, 32, 16),       # odd n_sel
    (128, 64, 3, 32, 2, 64, 64),
    (256, 128, 2, 128, 1, 128, 128),  # MXU-aligned full-config block
    (32, 16, 6, 8, 5, 32, 16),        # odd n_sel
])
def test_block_sparse_dw_sweep(dtype, n_shards, m, k, nb, block, n_sel, tm, tk):
    rng = np.random.default_rng(m * 7 + nb * n_shards)
    n = n_shards * nb * block
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    dy = jnp.asarray(rng.normal(size=(m, n)), dtype)
    idx = _sel_idx(rng, (n_shards,), nb, n_sel)
    out = block_sparse_dw_kernel(x, dy, idx, block=block, tm=tm, tk=tk,
                                 interpret=True)
    assert out.shape == (k, n_shards, n_sel, block)
    want = ref.block_sparse_dw_ref(x, dy, idx, block)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


@settings(max_examples=15, deadline=None)
@given(
    m_t=st.integers(1, 4), k_t=st.integers(1, 4),
    s=st.sampled_from([1, 2, 4]), nb=st.integers(2, 6),
    blk=st.sampled_from([8, 16]), seed=st.integers(0, 2**31 - 1),
)
def test_block_sparse_dw_property(m_t, k_t, s, nb, blk, seed):
    rng = np.random.default_rng(seed)
    m, k = 32 * m_t, 16 * k_t
    n = s * nb * blk
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    n_sel = int(rng.integers(1, nb + 1))
    idx = _sel_idx(rng, (s,), nb, n_sel)
    out = block_sparse_dw_kernel(x, dy, idx, block=blk, tm=32, tk=16,
                                 interpret=True)
    want = ref.block_sparse_dw_ref(x, dy, idx, blk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("variant", [block_sparse_dw_pipelined_kernel],
                         ids=["pipelined"])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("m,k,nb,block,n_sel,tm,tk", [
    (64, 32, 4, 16, 3, 32, 16),       # odd n_sel
    (128, 64, 3, 32, 2, 64, 64),
    (32, 16, 6, 8, 5, 32, 16),        # odd n_sel
])
def test_block_sparse_dw_pipelined_sweep(variant, n_shards, m, k, nb, block,
                                         n_sel, tm, tk):
    """The emit_pipeline double-buffered variant must match the grid
    kernel's oracle exactly as the grid kernel does."""
    rng = np.random.default_rng(m * 5 + nb * n_shards)
    n = n_shards * nb * block
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    idx = _sel_idx(rng, (n_shards,), nb, n_sel)
    out = variant(x, dy, idx, block=block, tm=tm, tk=tk, interpret=True)
    want = ref.block_sparse_dw_ref(x, dy, idx, block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def _group_sizes(rng, layout: str, n_experts: int, m: int):
    """Rows per expert over m rows: "uneven" leaves some experts empty and
    the last rows in no group; "skewed" gives every row to the last
    expert."""
    if layout == "skewed":
        return jnp.asarray([0] * (n_experts - 1) + [m], jnp.int32)
    cuts = np.sort(rng.integers(0, m - m // 4, n_experts - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [m - m // 4]]))
    sizes[0] = 0
    return jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("layout", ["uneven", "skewed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_experts", [2, 4])
@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("c,k,nb,block,n_sel,tm,tk", [
    (32, 32, 4, 16, 3, 32, 16),       # odd n_sel
    (16, 16, 6, 8, 5, 16, 16),        # odd n_sel
    (64, 32, 3, 32, 2, 32, 32),
])
def test_batched_dw_sweep(layout, dtype, n_experts, n_shards, c, k, nb,
                          block, n_sel, tm, tk):
    """Expert-batched compact dW over rows grouped by expert (one launch
    over experts x shards x selected blocks, a visit per row tile of each
    expert) vs the per-expert jnp einsum oracle, with empty experts, tiles
    shared by two experts and rows past the last group."""
    rng = np.random.default_rng(c * 3 + nb * n_shards + n_experts)
    n = n_shards * nb * block
    m = n_experts * c
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    dy = jnp.asarray(rng.normal(size=(m, n)), dtype)
    sizes = _group_sizes(rng, layout, n_experts, m)
    idx = _sel_idx(rng, (n_shards,), nb, n_sel)
    out = batched_dw_kernel(x, dy, sizes, idx, block=block, tm=tm, tk=tk,
                            interpret=True)
    assert out.shape == (n_experts, k, n_shards, n_sel, block)
    want = ref.batched_dw_ref(x, dy, sizes, idx, block)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


def test_batched_dw_deselected_expert_blocks_frozen():
    """End-to-end freeze guarantee for the expert leaf: with the batched-dW
    kernel in the backward and the fused optimizer applied on the stacked
    expert leaf, the DESELECTED blocks of every expert — weights and
    optimizer state — come back bitwise untouched."""
    from repro.core.sparse_update import SelSpec, gmm, use_kernels
    rng = np.random.default_rng(7)
    e, c, k, s, nb, blk, n_sel = 3, 16, 16, 2, 4, 8, 1
    n = s * nb * blk
    spec = SelSpec(block=blk, n_shards=s, n_sel=n_sel, n_blocks=nb)
    x = jnp.asarray(rng.normal(size=(e * c, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, k, n)), jnp.float32)
    sizes = jnp.full((e,), c, jnp.int32)
    idx = _sel_idx(rng, (s,), nb, n_sel)
    sel = ({"w": idx}, {"w": spec})
    with use_kernels(True):
        dw = jax.grad(lambda w: (gmm(x, w, sizes, sel, "w") ** 2).sum())(w)
    sel_mask = np.zeros((e, k, s, nb, blk), bool)
    for si in range(s):
        sel_mask[:, :, si, np.asarray(idx)[si], :] = True
    sel_mask = sel_mask.reshape(e, k, n)
    dw_np = np.asarray(dw)
    assert (dw_np[~sel_mask] == 0.0).all(), \
        "deselected expert blocks received gradient"
    assert np.abs(dw_np[sel_mask]).max() > 0

    # the fused optimizer on the stacked expert leaf ([K, E, d, N] flattened
    # lead) leaves the deselected blocks of params AND state bitwise frozen
    k_steps = 2
    w_leaf = jnp.asarray(rng.normal(size=(k_steps, e, k, n)), jnp.float32)
    mu = jnp.asarray(rng.normal(size=(k_steps, e, k, n)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(k_steps, e, k, s, n_sel, blk)),
                    jnp.float32)
    idx2 = _sel_idx(rng, (k_steps, s), nb, n_sel)
    w3 = w_leaf.reshape(k_steps, e * k, n)
    mu3 = mu.reshape(k_steps, e * k, n)
    g5 = g.reshape(k_steps, e * k, s, n_sel, blk)
    w2, mu2, _ = fused_block_opt_kernel(
        w3, g5, idx2, jnp.float32(0.1), jnp.float32(1.0), mu3,
        kind="momentum", momentum=0.9, tr=16, interpret=True)
    mask2 = np.zeros((k_steps, e * k, s, nb, blk), bool)
    for kk in range(k_steps):
        for si in range(s):
            mask2[kk, :, si, np.asarray(idx2)[kk, si], :] = True
    mask2 = mask2.reshape(k_steps, e * k, n)
    for before, after in ((w3, w2), (mu3, mu2)):
        b, a = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(a[~mask2], b[~mask2])
        assert np.abs(a[mask2] - b[mask2]).max() > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k_steps", [1, 3])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("r,nb,blk,n_sel,tr", [
    (32, 8, 8, 3, 32),        # odd n_sel
    (64, 4, 16, 2, 32),
    (128, 2, 128, 1, 128),    # MXU-aligned full-config block
    (48, 6, 8, 6, 16),        # full selection: every block overwritten
])
def test_block_scatter_update_sweep(dtype, k_steps, n_shards, r, nb, blk,
                                    n_sel, tr):
    rng = np.random.default_rng(r * 3 + nb + k_steps * n_shards)
    n = n_shards * nb * blk
    w = jnp.asarray(rng.normal(size=(k_steps, r, n)), dtype)
    upd = jnp.asarray(rng.normal(size=(k_steps, r, n_shards, n_sel, blk)),
                      dtype)
    idx = _sel_idx(rng, (k_steps, n_shards), nb, n_sel)
    out = block_scatter_update_kernel(w, upd, idx, tr=tr, interpret=True)
    want = ref.block_scatter_update_ref(w, upd, idx, blk)
    # pure write routing — must be exact in any dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))


@given(
    k_steps=st.sampled_from([1, 3]), s=st.sampled_from([1, 2, 4]),
    r_t=st.integers(1, 4), nb=st.integers(2, 8),
    blk=st.sampled_from([8, 16]), seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=15, deadline=None)
def test_block_scatter_update_property(k_steps, s, r_t, nb, blk, seed):
    rng = np.random.default_rng(seed)
    r = 16 * r_t
    n = s * nb * blk
    w = jnp.asarray(rng.normal(size=(k_steps, r, n)), jnp.float32)
    n_sel = int(rng.integers(1, nb + 1))
    idx = _sel_idx(rng, (k_steps, s), nb, n_sel)
    upd = jnp.asarray(rng.normal(size=(k_steps, r, s, n_sel, blk)),
                      jnp.float32)
    out = block_scatter_update_kernel(w, upd, idx, tr=16, interpret=True)
    want = ref.block_scatter_update_ref(w, upd, idx, blk)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["sgd", "momentum", "adamw"])
@pytest.mark.parametrize("k_steps,n_shards,nb,n_sel", [
    (1, 1, 4, 3),             # odd n_sel
    (3, 2, 4, 2),
    (1, 4, 3, 1),
    (3, 1, 6, 5),             # odd n_sel
])
def test_fused_block_opt_parity(dtype, kind, k_steps, n_shards, nb, n_sel):
    """Fused gather+rule+writeback kernel vs the un-fused oracle: SGD is
    bitwise; momentum/AdamW allclose (fp32 state updated in the same pass,
    deselected blocks untouched)."""
    rng = np.random.default_rng(k_steps * 13 + n_shards * 5 + nb)
    r, blk = 48, 8
    n = n_shards * nb * blk
    w = jnp.asarray(rng.normal(size=(k_steps, r, n)), dtype)
    g = jnp.asarray(rng.normal(size=(k_steps, r, n_shards, n_sel, blk)),
                    dtype)
    idx = _sel_idx(rng, (k_steps, n_shards), nb, n_sel)
    mu = nu = None
    if kind in ("momentum", "adamw"):
        mu = jnp.asarray(rng.normal(size=(k_steps, r, n)), jnp.float32)
    if kind == "adamw":
        nu = jnp.abs(jnp.asarray(rng.normal(size=(k_steps, r, n)),
                                 jnp.float32))
    lr, t = jnp.float32(0.05), jnp.float32(3.0)
    hp = dict(kind=kind, momentum=0.9, beta1=0.9, beta2=0.999, eps=1e-8,
              weight_decay=0.01)
    got = fused_block_opt_kernel(w, g, idx, lr, t, mu, nu, tr=16,
                                 interpret=True, **hp)
    # jit the oracle: bitwise means compiled-vs-compiled — XLA contracts
    # `p - lr*g` into an FMA in both, while an eager oracle rounds twice
    # and differs by 1 ulp on ~5% of elements
    import functools
    want = jax.jit(functools.partial(ref.fused_block_opt_ref, **hp))(
        w, g, idx, lr, t, mu, nu)
    for a, b in zip(got, want):
        assert (a is None) == (b is None)
        if a is None:
            continue
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if kind == "sgd" and dtype == jnp.float32:
            np.testing.assert_array_equal(a32, b32)
        elif kind == "sgd":
            # bf16 param cast may land on the adjacent value (1 ulp) when
            # the fp32 intermediate sits on an FMA rounding boundary
            np.testing.assert_allclose(a32, b32, rtol=1e-2, atol=1e-7)
        else:
            np.testing.assert_allclose(a32, b32, rtol=1e-6, atol=1e-6)


def test_fused_block_opt_freezes_deselected():
    """Deselected blocks — weights AND optimizer state — come back bitwise
    untouched (the in-place aliasing writes only selected blocks)."""
    rng = np.random.default_rng(0)
    k_steps, r, s, nb, blk, n_sel = 2, 32, 2, 4, 8, 1
    n = s * nb * blk
    w = jnp.asarray(rng.normal(size=(k_steps, r, n)), jnp.float32)
    mu = jnp.asarray(rng.normal(size=(k_steps, r, n)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(k_steps, r, s, n_sel, blk)), jnp.float32)
    idx = _sel_idx(rng, (k_steps, s), nb, n_sel)
    w2, mu2, _ = fused_block_opt_kernel(w, g, idx, jnp.float32(0.1),
                                        jnp.float32(1.0), mu, kind="momentum",
                                        momentum=0.9, tr=16, interpret=True)
    sel_mask = np.zeros((k_steps, r, s, nb, blk), bool)
    for kk in range(k_steps):
        for si in range(s):
            sel_mask[kk, :, si, np.asarray(idx)[kk, si], :] = True
    sel_mask = sel_mask.reshape(k_steps, r, n)
    for before, after in ((w, w2), (mu, mu2)):
        b, a = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(a[~sel_mask], b[~sel_mask])
        assert np.abs(a[sel_mask] - b[sel_mask]).max() > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("r,c,tr,tc,blk,thr", [
    (64, 64, 32, 32, 2, 0.15),
    (128, 256, 64, 128, 2, 0.15),
    (32, 128, 32, 64, 4, 0.3),
    (256, 512, 256, 512, 2, 0.05),
])
def test_block_act_prune_sweep(dtype, r, c, tr, tc, blk, thr):
    rng = np.random.default_rng(r + c)
    x = jnp.asarray(rng.normal(size=(r, c)) * 0.3, dtype)
    out = block_act_prune_kernel(x, threshold=thr, block=blk, tr=tr, tc=tc,
                                 interpret=True)
    want = ref.block_act_prune_ref(x, thr, blk)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))


def test_kernel_integrates_with_smm_grad():
    """kernels-enabled smm backward == jnp smm backward == masked dense."""
    from repro.core.sparse_update import SelSpec, smm, use_kernels
    rng = np.random.default_rng(0)
    k, n = 32, 64
    x = jnp.asarray(rng.normal(size=(4, 16, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    spec = SelSpec(block=16, n_shards=1, n_sel=2, n_blocks=4)
    idx = jnp.asarray([[0, 3]], jnp.int32)
    sel = ({"w": idx}, {"w": spec})
    g_jnp = jax.grad(lambda w: (smm(x, w, sel, "w") ** 2).sum())(w)
    with use_kernels(True):
        g_kern = jax.grad(lambda w: (smm(x, w, sel, "w") ** 2).sum())(w)
    np.testing.assert_allclose(np.asarray(g_kern), np.asarray(g_jnp),
                               rtol=1e-4, atol=1e-4)


def test_compact_dw_full_selection_view_path():
    """The jnp fallback's full-selection branch (einsum on a reshaped view,
    reorder on the output) matches the gather-first path exactly."""
    from repro.core.sparse_update import SelSpec, _gather_blocks, compact_dw
    rng = np.random.default_rng(4)
    m, k, s, nb, blk = 64, 32, 2, 4, 8
    spec = SelSpec(block=blk, n_shards=s, n_sel=nb, n_blocks=nb)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(m, s * nb * blk)), jnp.float32)
    idx = _sel_idx(rng, (s,), nb, nb)        # full selection, permuted order
    got = compact_dw(x, dy, idx, spec)
    want = jnp.einsum("mk,msnb->ksnb", x, _gather_blocks(dy, idx, spec),
                      preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_block_act_prune_nd():
    from repro.kernels import ops
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 8, 64)) * 0.2,
                    jnp.float32)
    out = ops.block_act_prune(x, threshold=0.15, block=2)
    want = ref.block_act_prune_ref(x, 0.15, 2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("bh,t,d", [(3, 128, 16), (2, 64, 32), (1, 256, 8)])
def test_wkv6_chunk_kernel(chunk, bh, t, d):
    """Chunked WKV6 kernel == sequential recurrence oracle."""
    from repro.kernels.wkv6_chunk import wkv6_chunk_kernel
    rng = np.random.default_rng(bh * t + d)
    r = jnp.asarray(rng.normal(size=(bh, t, d)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(bh, t, d)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(bh, t, d)) * 0.5, jnp.float32)
    w = jnp.exp(-jnp.exp(jnp.asarray(rng.normal(size=(bh, t, d)) - 1.0,
                                     jnp.float32)))
    u = jnp.asarray(rng.normal(size=(d,)) * 0.3, jnp.float32)
    out = wkv6_chunk_kernel(r, k, v, w, u, chunk=min(chunk, t),
                            interpret=True)
    want = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("lanes", [1, 128])
def test_ops_pad_a_block_of_part_lanes(monkeypatch, lanes):
    """A channel block of 96 (a 576-wide latent projection's) through every
    compact-path kernel entry, unpadded and padded to 128 lanes as when
    compiling for a TPU: the same dW, expert dW, writeback and AdamW
    update as the jnp path."""
    from repro.configs.base import OptimizerConfig
    from repro.core.sparse_update import (SelSpec, compact_dw,
                                          compact_dw_batched,
                                          scatter_param_blocks)
    from repro.kernels import ops
    monkeypatch.setattr(ops, "lanes", lambda: lanes)
    rng = np.random.default_rng(11)
    m, k, blk, nb, n_sel = 64, 128, 96, 6, 2
    spec = SelSpec(block=blk, n_shards=1, n_sel=n_sel, n_blocks=nb)
    idx = jnp.asarray([[4, 1]], jnp.int32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(m, nb * blk)), jnp.float32)
    sizes = jnp.asarray([20, 0, 30], jnp.int32)
    np.testing.assert_allclose(ops.block_sparse_dw(x, dy, idx, spec),
                               compact_dw(x, dy, idx, spec),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        ops.block_sparse_dw_batched(x, dy, sizes, idx, spec),
        compact_dw_batched(x, dy, sizes, idx, spec), rtol=1e-5, atol=1e-4)

    w = jnp.asarray(rng.normal(size=(2, k, nb * blk)), jnp.float32)
    kidx = jnp.asarray([[[4, 1]], [[0, 5]]], jnp.int32)
    vals = jnp.asarray(rng.normal(size=(2, k, 1, n_sel, blk)), jnp.float32)
    np.testing.assert_array_equal(
        ops.block_scatter_update(w, vals, kidx, spec),
        scatter_param_blocks(w, vals, kidx, spec))

    oc = OptimizerConfig(kind="adamw", learning_rate=1e-2, weight_decay=0.1)
    mu = jnp.asarray(rng.normal(size=w.shape) * 0.1, jnp.float32)
    nu = jnp.asarray(rng.uniform(size=w.shape) * 0.1, jnp.float32)
    got = ops.fused_block_optimizer(oc, w, vals, kidx, spec, mu, nu,
                                    jnp.float32(1e-2), jnp.float32(3))
    want = ref.fused_block_opt_ref(w, vals, kidx, jnp.float32(1e-2),
                                   jnp.float32(3), mu, nu, kind="adamw",
                                   momentum=oc.momentum, beta1=oc.beta1,
                                   beta2=oc.beta2, eps=oc.eps,
                                   weight_decay=oc.weight_decay)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
