"""Compact-gradient training path: equivalence against the dense-scatter
path (SGD / momentum / AdamW, dense + MoE archs, Pallas kernel routing),
the no-full-gradient-scatter HLO guarantee, and checkpoint round-tripping
of the (unchanged, full-shape) train state."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (OptimizerConfig, ShapeConfig, SparseUpdateConfig,
                           TrainConfig, get_smoke_config)
from repro.train import make_train_state, make_train_step


def _tc(arch="llama3-8b", kind="sgd", **opt_kw):
    cfg = get_smoke_config(arch)
    shape = ShapeConfig("t", 16, 4, "train")
    return TrainConfig(
        model=cfg, shape=shape,
        sparse=SparseUpdateConfig(update_ratio=0.5, num_update_layers=2,
                                  channel_block=8),
        optimizer=OptimizerConfig(kind=kind, learning_rate=0.05, **opt_kw))


def _batch(cfg, seed=3):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(seed), (4, 16),
                                         0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(seed + 1),
                                         (4, 16), 0, cfg.vocab_size)}


def _run(tc, plan, state, batch, compact, steps=3):
    step = jax.jit(make_train_step(tc, plan=plan, compact_grads=compact))
    for _ in range(steps):
        state, metrics = step(state, batch)
    return state, metrics


def _max_diff(a_tree, b_tree):
    return max(float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32)).max())
               for a, b in zip(jax.tree.leaves(a_tree),
                               jax.tree.leaves(b_tree)))


@pytest.mark.parametrize("kind,opt_kw,tol", [
    ("sgd", {}, 0.0),                       # bitwise (see sparse_update doc)
    ("momentum", {"momentum": 0.9}, 1e-6),
    ("adamw", {}, 1e-6),
])
def test_compact_matches_dense_scatter(kind, opt_kw, tol):
    tc = _tc(kind=kind, **opt_kw)
    state, plan = make_train_state(tc, jax.random.PRNGKey(0))
    batch = _batch(tc.model)
    sd, md = _run(tc, plan, state, batch, compact=False)
    sc, mc = _run(tc, plan, state, batch, compact=True)
    assert float(md["loss"]) == pytest.approx(float(mc["loss"]), abs=1e-5)
    diff = _max_diff(sd["params_trainable"], sc["params_trainable"])
    assert diff <= tol, diff
    # optimizer state also matches (stale state frozen == zero in fixed phase)
    if sd["opt"]:
        assert _max_diff(sd["opt"], sc["opt"]) <= tol


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "rwkv6-3b"])
def test_compact_matches_dense_other_archs(arch):
    tc = _tc(arch=arch, kind="momentum", momentum=0.9)
    state, plan = make_train_state(tc, jax.random.PRNGKey(0))
    batch = _batch(tc.model)
    sd, _ = _run(tc, plan, state, batch, compact=False, steps=2)
    sc, _ = _run(tc, plan, state, batch, compact=True, steps=2)
    assert _max_diff(sd["params_trainable"], sc["params_trainable"]) <= 1e-6


def test_compact_hlo_has_no_full_gradient_scatter():
    """The acceptance check: the jitted compact step's lowering contains no
    scatter into a zero-initialized blocked-weight buffer; the dense-scatter
    step contains one per selectable weight."""
    from repro.core.sparse_update import SelSpec
    from repro.launch.hlo_analysis import weight_gradient_scatters
    tc = _tc(kind="momentum", momentum=0.9)
    state, plan = make_train_state(tc, jax.random.PRNGKey(0))
    batch = _batch(tc.model)
    specs = [l for seg in plan.spec.values()
             for l in jax.tree_util.tree_leaves(
                 seg, is_leaf=lambda x: isinstance(x, SelSpec))]
    texts = {}
    for compact in (False, True):
        step = make_train_step(tc, plan, compact_grads=compact)
        texts[compact] = jax.jit(step).lower(state, batch).as_text()
    assert len(weight_gradient_scatters(texts[False], specs)) > 0, \
        "detector lost track of the dense path's gradient scatters"
    offenders = weight_gradient_scatters(texts[True], specs)
    assert offenders == [], offenders


@pytest.mark.parametrize("kind,opt_kw,tol", [
    ("sgd", {}, 1e-5),
    ("momentum", {"momentum": 0.9}, 1e-5),
    # adamw's g/(sqrt(g^2)+eps) normalizer amplifies the dW kernel's
    # accumulation-order differences by O(lr) for near-zero gradient
    # elements — the update direction is sign-like there, so the
    # end-to-end tolerance is looser (the optimizer kernel itself is
    # allclose 1e-6 vs its oracle; see test_kernels)
    ("adamw", {}, 1e-2),
])
def test_compact_with_pallas_kernels(kind, opt_kw, tol):
    """use_kernels routes compact dW + the fused gather/rule/writeback
    optimizer kernel through Pallas (interpret mode on CPU) and stays
    allclose to the jnp compact path — params AND optimizer state."""
    from repro.core.sparse_update import use_kernels
    tc = _tc(kind=kind, **opt_kw)
    state, plan = make_train_state(tc, jax.random.PRNGKey(0))
    batch = _batch(tc.model)
    s_jnp, _ = _run(tc, plan, state, batch, compact=True, steps=1)
    # interpret-mode pallas_call doesn't jit-cache well; run un-jitted
    step = make_train_step(tc, plan, compact_grads=True)
    with use_kernels(True):
        s_k, _ = step(state, batch)
    assert _max_diff(s_jnp["params_trainable"],
                     s_k["params_trainable"]) <= tol
    if s_jnp["opt"]:
        assert _max_diff(s_jnp["opt"], s_k["opt"]) <= tol


def _selectable_leaves(plan):
    from repro.core.sparse_update import SelSpec
    return [l for seg, steps in plan.seg_trainable.items() if steps
            for l in jax.tree_util.tree_leaves(
                plan.spec[seg], is_leaf=lambda x: isinstance(x, SelSpec))]


def test_compact_kernel_launch_count():
    """The fused acceptance check: the lowered compact train step contains a
    CONSTANT number of Pallas launch sites per selectable weight leaf — one
    fused dW (inside the backward scan) plus one fused optimizer update —
    independent of the trainable-layer count K (the PR 1 path grew as
    O(K x n_shards) from its per-shard / per-(K, shard) Python loops)."""
    from repro.core.sparse_update import use_kernels
    from repro.launch.hlo_analysis import kernel_launch_count
    counts, leaves = {}, {}
    for k_layers in (1, 3):
        cfg = get_smoke_config("llama3-8b")
        tc = TrainConfig(
            model=cfg, shape=ShapeConfig("t", 16, 4, "train"),
            sparse=SparseUpdateConfig(update_ratio=0.5,
                                      num_update_layers=k_layers,
                                      channel_block=8),
            optimizer=OptimizerConfig(kind="momentum", momentum=0.9,
                                      learning_rate=0.05))
        state, plan = make_train_state(tc, jax.random.PRNGKey(0))
        step = make_train_step(tc, plan, compact_grads=True)
        with use_kernels(True):
            jaxpr = jax.make_jaxpr(step)(state, _batch(cfg))
        counts[k_layers] = kernel_launch_count(jaxpr)
        leaves[k_layers] = len(_selectable_leaves(plan))
    assert counts[1] == counts[3], counts
    assert counts[3] == 2 * leaves[3], (counts, leaves)


def test_kernel_launch_count_text_mode():
    """Text mode counts Pallas/Mosaic custom-calls in compiled TPU HLO."""
    from repro.launch.hlo_analysis import kernel_launch_count
    hlo = """
      %fusion = f32[8,128] fusion(f32[8,128] %p0)
      %cc.1 = f32[8,128] custom-call(f32[8,128] %p1), custom_call_target="tpu_custom_call"
      %cc.2 = (f32[8,128], f32[8]) custom-call(%p2), custom_call_target="Mosaic"
      %other = f32[4] custom-call(%p3), custom_call_target="Sharding"
    """
    assert kernel_launch_count(hlo) == 2
    assert kernel_launch_count("no kernels here") == 0


def test_compact_dynamic_phase_trains():
    """Dynamic reselection (fresh selection every step) under the compact
    path: selection changes, selected blocks move, loss stays finite."""
    cfg = get_smoke_config("llama3-8b")
    shape = ShapeConfig("t", 16, 4, "train")
    tc = TrainConfig(
        model=cfg, shape=shape,
        sparse=SparseUpdateConfig(update_ratio=0.3, num_update_layers=2,
                                  channel_block=8, phase_fixed_early=0,
                                  phase_dynamic=100),
        optimizer=OptimizerConfig(kind="sgd", learning_rate=0.05))
    state, plan = make_train_state(tc, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(tc, plan, compact_grads=True))
    batch = _batch(cfg)
    s = state
    for _ in range(3):
        prev = s
        s, m = step(s, batch)
        assert np.isfinite(float(m["loss"]))
    changed = _max_diff(prev["params_trainable"], s["params_trainable"])
    assert changed > 0.0
    sel_changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(prev["sel_idx"]),
                        jax.tree.leaves(s["sel_idx"])))
    assert sel_changed, "dynamic phase must re-randomize the selection"


def test_compact_state_checkpoint_roundtrip(tmp_path):
    """The compact step leaves the train-state layout unchanged (full-shape
    fp32 state, same tree); save -> restore -> continue is bit-identical to
    an uninterrupted run."""
    from repro.checkpoint import CheckpointManager
    tc = _tc(kind="momentum", momentum=0.9)
    state, plan = make_train_state(tc, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(tc, plan, compact_grads=True))
    batch = _batch(tc.model)

    s, _ = step(state, batch)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(1, s)
    s_cont, _ = step(s, batch)                      # uninterrupted

    restored, meta = mgr.restore(1, target=s)
    assert meta["step"] == 1
    s_res, _ = step(restored, batch)                # resumed
    assert _max_diff(s_cont["params_trainable"],
                     s_res["params_trainable"]) == 0.0
    if s_cont["opt"]:
        assert _max_diff(s_cont["opt"], s_res["opt"]) == 0.0


# ---------------------------------------------------------------------------
# MoE batched (expert) compact backward: parity vs dense per-expert einsum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "kernels"])
@pytest.mark.parametrize("n_experts", [2, 4])
@pytest.mark.parametrize("n_sel", [1, 3])
def test_smm_batched_compact_matches_per_expert_dense(n_experts, n_sel,
                                                      kernels):
    """`_smm_batched_compact` (the MoE expert path) must emit per-expert
    compact dW identical to the dense per-expert einsum gathered at the
    selection — including odd n_sel — with zero cotangent on the
    (gradient-stopped) full weight. Under `use_kernels` the backward is the
    single-launch Pallas `batched_dw` kernel and must stay allclose (1e-6)
    to the same oracle."""
    from repro.core.sparse_update import (SelSpec, _smm_batched_compact,
                                          use_kernels)
    spec = SelSpec(block=8, n_shards=2, n_sel=n_sel, n_blocks=4)
    e, c, k = n_experts, 12, 16
    n = spec.n_shards * spec.n_blocks * spec.block
    kx, kw, kc, ki = jax.random.split(jax.random.PRNGKey(42), 4)
    x = jax.random.normal(kx, (e, c, k), jnp.float32)
    w = jax.random.normal(kw, (e, k, n), jnp.float32)
    cot = jax.random.normal(kc, (e, c, n), jnp.float32)
    idx = jnp.sort(jnp.stack([
        jax.random.permutation(jax.random.fold_in(ki, s),
                               spec.n_blocks)[:n_sel]
        for s in range(spec.n_shards)]), axis=1).astype(jnp.int32)
    w_sel = jnp.zeros((e, k, spec.n_shards, n_sel, spec.block), jnp.float32)

    out = _smm_batched_compact(x, w, w_sel, idx, spec)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.einsum("eck,ekn->ecn", x, w)),
                               rtol=1e-5, atol=1e-5)

    def loss(x, w, w_sel):
        return jnp.vdot(_smm_batched_compact(x, w, w_sel, idx, spec), cot)

    with use_kernels(kernels):
        dx, dw, dw_sel = jax.grad(loss, argnums=(0, 1, 2))(x, w, w_sel)
    assert np.all(np.asarray(dw) == 0.0)      # full weight: gradient stopped

    for ei in range(e):                       # dense per-expert oracle
        dw_dense = jnp.einsum("ck,cn->kn", x[ei], cot[ei],
                              preferred_element_type=jnp.float32)
        dwb = dw_dense.reshape(k, spec.n_shards, spec.n_blocks, spec.block)
        expect = jnp.take_along_axis(dwb, idx[None, :, :, None], axis=2)
        np.testing.assert_allclose(np.asarray(dw_sel[ei]),
                                   np.asarray(expect), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(dx), np.asarray(jnp.einsum("ecn,ekn->eck", cot, w)),
        rtol=1e-5, atol=1e-5)


def _moe_tc(n_experts: int, k_layers: int, num_layers: int = 5):
    import dataclasses
    cfg0 = get_smoke_config("deepseek-moe-16b")
    cfg = dataclasses.replace(
        cfg0, num_layers=num_layers,
        moe=dataclasses.replace(cfg0.moe, num_experts=n_experts, top_k=2))
    return TrainConfig(
        model=cfg, shape=ShapeConfig("t", 16, 4, "train"),
        sparse=SparseUpdateConfig(update_ratio=0.5,
                                  num_update_layers=k_layers,
                                  channel_block=8),
        optimizer=OptimizerConfig(kind="momentum", momentum=0.9,
                                  learning_rate=0.05))


def test_moe_compact_with_pallas_kernels():
    """The MoE arch under use_kernels: the expert leaves' backward runs the
    batched-dW kernel and the fused optimizer updates the stacked expert
    leaf — params AND optimizer state stay allclose to the jnp compact
    path."""
    from repro.core.sparse_update import use_kernels
    tc = _moe_tc(n_experts=4, k_layers=2, num_layers=3)
    state, plan = make_train_state(tc, jax.random.PRNGKey(0))
    batch = _batch(tc.model)
    step = make_train_step(tc, plan, compact_grads=True)
    s_jnp, m_jnp = step(state, batch)
    with use_kernels(True):
        s_k, m_k = step(state, batch)
    assert float(m_jnp["loss"]) == pytest.approx(float(m_k["loss"]), abs=1e-5)
    assert _max_diff(s_jnp["params_trainable"],
                     s_k["params_trainable"]) <= 1e-5
    if s_jnp["opt"]:
        assert _max_diff(s_jnp["opt"], s_k["opt"]) <= 1e-5


def test_moe_compact_kernel_launch_count():
    """The MoE acceptance check: the lowered compact train step has a
    CONSTANT number of Pallas launch sites per expert-sharded leaf — one
    batched dW in the backward scan plus one fused optimizer — asserted
    EQUAL across (n_experts, K) in {(2, 1), (4, 3)} (num_layers is held at
    5 so both K values stay inside the same MoE segment and the selectable
    leaf set is identical)."""
    from repro.core.sparse_update import use_kernels
    from repro.launch.hlo_analysis import (kernel_launch_breakdown,
                                           kernel_launch_count)
    counts, leaves, breakdowns = {}, {}, {}
    for n_experts, k_layers in ((2, 1), (4, 3)):
        tc = _moe_tc(n_experts, k_layers)
        state, plan = make_train_state(tc, jax.random.PRNGKey(0))
        step = make_train_step(tc, plan, compact_grads=True)
        with use_kernels(True):
            jaxpr = jax.make_jaxpr(step)(state, _batch(tc.model))
        key = (n_experts, k_layers)
        counts[key] = kernel_launch_count(jaxpr)
        leaves[key] = len(_selectable_leaves(plan))
        breakdowns[key] = kernel_launch_breakdown(jaxpr)
    (k1, k2) = counts
    assert counts[k1] == counts[k2], counts
    assert leaves[k1] == leaves[k2], leaves
    assert counts[k2] == 2 * leaves[k2], (counts, leaves)
    # per-kernel budget: exactly one batched-dW site per expert leaf
    # (w_gate/w_up/w_down), independent of n_experts and K
    for key, bd in breakdowns.items():
        assert bd.get("batched_dw", 0) == 3, (key, bd)
    assert breakdowns[k1] == breakdowns[k2], breakdowns
