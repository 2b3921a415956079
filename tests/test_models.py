"""Per-arch smoke tests (reduced configs, CPU): forward + one train step,
shape and finiteness checks, decode==forward consistency, attention
equivalences. The FULL configs are exercised only by the dry-run."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCH_IDS, OptimizerConfig, ShapeConfig,
                           SparseUpdateConfig, TrainConfig, get_smoke_config)
from repro.models import decoding as D
from repro.models import rwkv6 as R6
from repro.models import transformer as T


def _batch(cfg, b=2, s=32, key=None):
    key = jax.random.PRNGKey(0) if key is None else key
    batch = {}
    if cfg.embed_inputs:
        batch["embeds"] = jax.random.normal(key, (b, s, cfg.d_model),
                                            jnp.dtype(cfg.dtype))
    else:
        batch["tokens"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    if cfg.mrope:
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        batch["positions"] = jnp.stack([pos, pos, pos])
    batch["labels"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    hidden, aux = T.forward(cfg, (params, None), batch)
    assert hidden.shape == (2, 32, cfg.d_model)
    assert bool(jnp.isfinite(hidden).all())
    loss, metrics = T.loss_fn(cfg, (params, None), batch)
    assert bool(jnp.isfinite(loss))
    # random-init CE should be near ln(V)
    assert abs(float(metrics["ce"]) - np.log(cfg.vocab_size)) < 1.5


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_sparse_train_step(arch):
    """One DGSU train step per arch: loss finite, frozen params untouched,
    only selected channel blocks of trainable params change."""
    from repro.train import make_train_state, make_train_step
    cfg = get_smoke_config(arch)
    shape = ShapeConfig("t", 32, 2, "train")
    tc = TrainConfig(
        model=cfg, shape=shape,
        sparse=SparseUpdateConfig(update_ratio=0.5, num_update_layers=1,
                                  channel_block=8, phase_fixed_early=100),
        optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1))
    state, plan = make_train_state(tc, jax.random.PRNGKey(0))
    step_fn = make_train_step(tc, plan)
    batch = _batch(cfg, b=2, s=32)
    new_state, metrics = jax.jit(step_fn)(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(new_state["step"]) == 1
    # frozen tree bit-identical
    same = jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()),
        state["params_frozen"], new_state["params_frozen"]))
    assert same, "frozen params changed"
    # trainable: some change, and change only within selected blocks for a
    # known selectable leaf
    changed = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                           state["params_trainable"],
                           new_state["params_trainable"])
    assert max(jax.tree.leaves(changed)) > 0, "no parameter moved"


def test_train_decreases_loss_dense_vs_sparse():
    """Paper Table II ordering on the synthetic LM task: full > dynamic
    sparse > frozen (training at all beats nothing)."""
    from repro.data import lm_batches
    from repro.train import make_train_state, make_train_step
    cfg = get_smoke_config("llama3-8b")
    shape = ShapeConfig("t", 16, 16, "train")
    results = {}
    for name, sparse in [
        ("dense", SparseUpdateConfig(enabled=False)),
        ("sparse", SparseUpdateConfig(update_ratio=0.5, num_update_layers=2,
                                      channel_block=16, phase_fixed_early=5,
                                      phase_dynamic=25)),
    ]:
        tc = TrainConfig(model=cfg, shape=shape, sparse=sparse,
                         optimizer=OptimizerConfig(kind="adamw",
                                                   learning_rate=3e-3))
        state, plan = make_train_state(tc, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(tc, plan))
        losses = []
        for i, b in zip(range(60), lm_batches(16, 16, cfg.vocab_size, seed=3)):
            state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()})
            losses.append(float(m["loss"]))
        results[name] = (float(np.mean(losses[:5])), float(np.mean(losses[-10:])))
    for name, (first, last) in results.items():
        assert last < first - 0.02, f"{name} did not reduce loss: {first}->{last}"
    # dense should fit the task at least as well as sparse
    assert results["dense"][1] <= results["sparse"][1] + 0.05


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma3-4b", "rwkv6-3b",
                                  "jamba-1.5-large-398b", "deepseek-moe-16b",
                                  "qwen2-vl-7b"])
def test_prefill_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 24
    key = jax.random.PRNGKey(1)
    batch = _batch(cfg, b, s, key)
    hidden, _ = T.forward(cfg, (params, None), batch)
    w = T.lm_head_weight(cfg, (params, None))
    ref = jnp.einsum("bsd,dv->bsv", hidden, w)

    s0 = s - 4
    pf_batch = {k: (v[:, :s0] if k != "positions" else v[..., :s0])
                for k, v in batch.items() if k != "labels"}
    logits, cache = D.prefill(cfg, params, pf_batch, pad_to=s)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, s0 - 1]),
                               rtol=5e-2, atol=5e-3)
    for t in range(s0, s):
        db = {"positions": jnp.full((b, 1), t, jnp.int32)}
        if cfg.embed_inputs:
            db["embeds"] = batch["embeds"][:, t:t + 1]
        else:
            db["tokens"] = batch["tokens"][:, t:t + 1]
        if cfg.mrope:
            db["positions"] = jnp.broadcast_to(db["positions"], (3, b, 1))
        logits, cache = D.decode_step(cfg, params, db, cache)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, t]),
                                   rtol=5e-2, atol=5e-3)


def test_flash_equals_dense_attention():
    from repro.models.layers import _sdpa_dense, _sdpa_flash
    key = jax.random.PRNGKey(0)
    b, s, hq, hkv, d = 2, 512, 4, 2, 16
    q = jax.random.normal(key, (b, s, hq, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d))
    for w in (0, 100):
        dn = _sdpa_dense(q, k, v, w)
        fl = _sdpa_flash(q, k, v, w, q_chunk=128, kv_chunk=128)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(dn),
                                   rtol=1e-4, atol=1e-5)
        # gradients too (custom flash VJP)
        gf = jax.grad(lambda q, k, v: (_sdpa_flash(q, k, v, w, 128, 128) ** 2
                                       ).sum(), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda q, k, v: (_sdpa_dense(q, k, v, w) ** 2
                                       ).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, bb in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-3, atol=1e-4)


def test_sliding_window_restricts_reach():
    """A token beyond the window must not influence attention output."""
    from repro.models.layers import _sdpa_dense
    key = jax.random.PRNGKey(0)
    b, s, h, d = 1, 64, 2, 8
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))
    out1 = _sdpa_dense(q, k, v, window=8)
    k2 = k.at[:, 0].set(100.0)
    v2 = v.at[:, 0].set(-100.0)
    out2 = _sdpa_dense(q, k2, v2, window=8)
    # position 0 is outside the window of positions >= 8
    np.testing.assert_allclose(np.asarray(out1[:, 8:]),
                               np.asarray(out2[:, 8:]), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(out1[:, 0] - out2[:, 0]).max()) > 1.0


def test_moe_aux_losses_and_balance():
    from repro.models import moe as MOE
    cfg = get_smoke_config("deepseek-moe-16b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    moe_p = jax.tree.map(lambda a: a[0], params["segments"]["blocks"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
    y, aux = MOE.apply_moe(moe_p, cfg, x)
    assert y.shape == x.shape
    assert float(aux["load_balance"]) >= 1.0 - 1e-3  # >= 1 by Cauchy-Schwarz
    assert bool(jnp.isfinite(y).all())


@pytest.mark.parametrize("chunk", [8, 24, 1024])
def test_held_experts_live_rows_match_dense(monkeypatch, chunk):
    """The dropless dispatch's loops over the live rows (chunks of `chunk`
    rows, the last one part-filled) against every held expert applied to
    every token under its gate: the output and the gradients of the
    tokens, the gates and the expert weights."""
    from repro.models import moe as MOE
    monkeypatch.setattr(MOE, "CHUNK", chunk)
    t, d, ff, e_h, routed, k, first = 40, 16, 24, 4, 12, 3, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (t, d))
    ids = jax.vmap(lambda kk: jax.random.permutation(kk, routed)[:k])(
        jax.random.split(ks[1], t))
    w = jax.random.uniform(ks[2], (t, k), minval=0.1, maxval=1.0)
    wp = {n: jax.random.normal(kk, s) * 0.3 for n, kk, s in (
        ("w_gate", ks[3], (e_h, d, ff)), ("w_up", ks[4], (e_h, d, ff)),
        ("w_down", ks[5], (e_h, ff, d)))}

    def program(x, w, wp):
        y, sizes = MOE.held_experts(wp, x, ids, w, first, None)
        return y, sizes

    def dense(x, w, wp):
        y = jnp.zeros((t, d))
        for i in range(e_h):
            gate = jnp.sum(jnp.where(ids == first + i, w, 0.0), axis=1)
            h = jax.nn.silu(x @ wp["w_gate"][i]) * (x @ wp["w_up"][i])
            y = y + gate[:, None] * (h @ wp["w_down"][i])
        return y

    y, sizes = program(x, w, wp)
    held = (ids >= first) & (ids < first + e_h)
    assert int(sizes.sum()) == int(held.sum())
    np.testing.assert_allclose(y, dense(x, w, wp), rtol=1e-4, atol=1e-5)
    probe = jax.random.normal(jax.random.PRNGKey(9), (t, d))
    loss = lambda f: lambda *a: jnp.sum(probe * (f(*a)[0] if f is program
                                                 else f(*a)))
    got = jax.grad(loss(program), argnums=(0, 1, 2))(x, w, wp)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(x, w, wp)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_mamba_chunked_scan_matches_stepwise():
    """Chunked selective scan == naive per-step recurrence."""
    from repro.models import mamba as M
    cfg = get_smoke_config("jamba-1.5-large-398b")
    p = M.init_mamba(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, cfg.d_model)) * 0.5
    out_chunked, _ = M.apply_mamba(p, cfg, x)
    # stepwise via decode cache
    cache = M.init_mamba_cache(cfg, 2, jnp.float32)
    outs = []
    for t in range(128):
        o, cache = M.apply_mamba(p, cfg, x[:, t:t + 1], cache=cache)
        outs.append(o)
    out_step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_chunked), np.asarray(out_step),
                               rtol=2e-3, atol=2e-4)


def test_rwkv_chunked_matches_stepwise():
    from repro.models import rwkv6 as R
    cfg = get_smoke_config("rwkv6-3b")
    p = R.init_time_mix(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model)) * 0.5
    out_full, _ = R.apply_time_mix(p, cfg, x)
    cache = {"s": jnp.zeros((2, R.num_heads(cfg), cfg.rwkv.head_dim,
                             cfg.rwkv.head_dim), jnp.float32),
             "last": jnp.zeros((2, cfg.d_model), jnp.float32)}
    outs = []
    for t in range(64):
        o, cache = R.apply_time_mix(p, cfg, x[:, t:t + 1], cache=cache)
        outs.append(o)
    out_step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(out_step),
                               rtol=2e-3, atol=2e-4)


def _wkv_stepwise(r, k, v, lw, u, s0):
    """Plain token-by-token wkv: S_t = diag(e^lw_t) S + k_t v_tᵀ."""
    def step(s, rkvl):
        rt, kt, vt, lt = rkvl
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhd,bhde->bhe", rt, u[None, :, :, None] * kv + s,
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.exp(lt)[..., :, None] * s + kv, y

    s_last, ys = jax.lax.scan(step, s0, tuple(t.swapaxes(0, 1)
                                             for t in (r, k, v, lw)))
    return ys.swapaxes(0, 1), s_last


def _wkv_inputs(seq, decay, b=2, h=3, d=16):
    """r, k, v, log-decay lw [B,S,H,D], u [H,D] and a nonzero s0."""
    ks = jax.random.split(jax.random.PRNGKey(seq), 7)
    r, k, v = (jax.random.normal(ks[i], (b, seq, h, d)) for i in range(3))
    shape = (b, seq, h, d)
    if decay == "weak":       # the model's init: wlog = -6 ± 0.5
        lw = -jnp.exp(-6.0 + jax.random.uniform(ks[3], shape, minval=-0.5,
                                                maxval=0.5))
    elif decay == "medium":   # log w ≈ -1
        lw = -jnp.exp(0.3 * jax.random.normal(ks[3], shape))
    else:                     # w down to 1e-5; w = 0 in channels 0 and 1
        lw = -jnp.exp(jax.random.uniform(ks[3], shape, minval=-6.0,
                                         maxval=np.log(11.5)))
        lw = lw.at[..., 0].set(-200.0).at[..., 1].set(-jnp.exp(100.0))
    u = 0.5 * jax.random.normal(ks[4], (h, d))
    s0 = jax.random.normal(ks[5], (b, h, d, d))
    return r, k, v, lw, u, s0


DECAYS = ("weak", "medium", "strong")
WKV_LENGTHS = (8, R6.CHUNK, 3 * R6.CHUNK, R6.CHUNK + 5)


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("seq", WKV_LENGTHS)
def test_wkv_chunk_form_matches_stepwise(seq, decay):
    r, k, v, lw, u, s0 = _wkv_inputs(seq, decay)
    y, s_last = jax.jit(R6.wkv)(r, k, v, lw, u, s0)
    y_ref, s_ref = jax.jit(_wkv_stepwise)(r, k, v, lw, u, s0)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(s_last).all())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_last), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("decay", DECAYS)
def test_wkv_bf16_inputs_run_in_f32(decay):
    """bf16 r, k, v (the projections' dtype) give the f32 recurrence: the
    state as from their f32 copies, and y rounded once to bf16."""
    r, k, v, lw, u, s0 = _wkv_inputs(2 * R6.CHUNK, decay)
    rb, kb, vb = (t.astype(jnp.bfloat16) for t in (r, k, v))
    y, s_last = jax.jit(R6.wkv)(rb, kb, vb, lw, u, s0)
    y_ref, s_ref = jax.jit(_wkv_stepwise)(
        *(t.astype(jnp.float32) for t in (rb, kb, vb)), lw, u, s0)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(s_last), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y.astype(jnp.float32)),
                               np.asarray(y_ref.astype(jnp.bfloat16)
                                          .astype(jnp.float32)),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("decay", DECAYS)
def test_wkv_padded_tail_leaves_state(decay):
    """Tokens with log-decay 0 and key 0 (serve prefill padding) after a
    valid prefix leave the state at the prefix's."""
    n = R6.CHUNK + 5
    r, k, v, lw, u, s0 = _wkv_inputs(2 * R6.CHUNK, decay)
    valid = (jnp.arange(2 * R6.CHUNK) < n)[None, :, None, None]
    y, s_last = jax.jit(R6.wkv)(r, jnp.where(valid, k, 0.0), v,
                                jnp.where(valid, lw, 0.0), u, s0)
    y_ref, s_ref = jax.jit(_wkv_stepwise)(r[:, :n], k[:, :n], v[:, :n],
                                          lw[:, :n], u, s0)
    np.testing.assert_allclose(np.asarray(y[:, :n]), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_last), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("decay", DECAYS)
def test_wkv_chunk_form_gradients_match_stepwise(decay):
    """d/d(r, k, v, lw, u, s0) of a random projection of (y, s_last): the
    backward the trainable suffix runs."""
    args = _wkv_inputs(3 * R6.CHUNK, decay, b=1, h=2, d=8)
    ky, ks = jax.random.split(jax.random.PRNGKey(7))
    py = jax.random.normal(ky, args[0].shape)
    ps = jax.random.normal(ks, args[5].shape)

    def loss(fn):
        def f(*a):
            y, s_last = fn(*a)
            return jnp.sum(y * py) + jnp.sum(s_last * ps)
        return jax.jit(jax.grad(f, argnums=tuple(range(6))))

    got = loss(R6.wkv)(*args)
    want = loss(_wkv_stepwise)(*args)
    for name, g, w in zip(("r", "k", "v", "lw", "u", "s0"), got, want):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4, err_msg=name)


def test_mobilenet_smoke():
    from repro.configs.mobilenetv2_cifar import smoke_config
    from repro.models import mobilenet_v2 as MN
    cfg = smoke_config()
    params = MN.init_params(cfg, jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1),
                             (2, cfg.img_size, cfg.img_size, 3))
    logits = MN.forward(cfg, (params, None), imgs)
    assert logits.shape == (2, cfg.num_classes)
    assert bool(jnp.isfinite(logits).all())


def test_latent_attention_model_refuses_serving():
    """An MLA model trains but has no KV cache: the decode paths and the
    engine say so, and the dry-run skips its prefill and decode shapes."""
    from repro.configs import cell_is_skipped
    from repro.models import decoding as D
    from repro.serve.engine import ServeEngine
    cfg = get_smoke_config("moonlight-16b-a3b")
    for build in (lambda: D.init_cache(cfg, 1, 32),
                  lambda: D.init_serve_cache(cfg, 1, 32, 4, 8),
                  lambda: ServeEngine(cfg, None, num_slots=1, max_len=16)):
        with pytest.raises(NotImplementedError, match="latent attention"):
            build()
    for shape in ("prefill_32k", "decode_32k"):
        assert "latent attention" in cell_is_skipped("moonlight-16b-a3b",
                                                     shape)
    assert cell_is_skipped("moonlight-16b-a3b", "train_4k") is None


@pytest.mark.parametrize("arch,smoke,width", [
    ("deepseek-moe-16b", False, 10_944), ("moonlight-16b-a3b", False, 11_264),
    ("deepseek-moe-16b", True, 8 * 32)])
def test_dense_first_layer_width(arch, smoke, width):
    """The leading dense layer's width is the config's `dense_d_ff` where
    the model publishes one, else 8 d_ff."""
    from repro.configs import get_config
    from repro.models.registry import abstract_params
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    segs = abstract_params(cfg)["segments"]
    assert segs["first"]["mlp"]["w_gate"].shape[-1] == width
