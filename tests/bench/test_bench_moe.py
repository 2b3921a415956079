"""The train harness with a configuration whose model has sub-configs, two
segments and stacked expert weights under the sparse update: the tiny MoE
cell of bench_tiny, checked piece by piece on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.jobs import train as J
from bench.reference import common as C
from bench.reference import moe_lm
from bench.reference import train_ref as TR
from bench_tiny import TINY_MOE, tiny

CELL = sorted(TINY_MOE)[0]


def test_program_config_builds_each_sub_config_from_all_its_keys():
    t = tiny(CELL)
    cfg = J.program_config(t["entry"])
    moe = t["entry"]["model"]["moe"]
    assert type(cfg.moe).__name__ == "MoEConfig"
    assert {k: getattr(cfg.moe, k) for k in moe} == moe
    ssm = J.program_config({"name": "s", "model": dict(
        t["entry"]["model"], moe=None, ssm={"d_state": 8, "expand": 3})})
    assert (ssm.ssm.d_state, ssm.ssm.expand, ssm.ssm.d_conv) == (8, 3, 4)
    rwkv = J.program_config({"name": "r", "model": dict(
        t["entry"]["model"], moe=None, rwkv_head_dim=16)})
    assert rwkv.rwkv.head_dim == 16


def test_segments_and_the_trainable_suffix():
    m = tiny(CELL)["entry"]["model"]
    assert TR.segments(moe_lm, m) == [("first", 1), ("blocks", 2)]
    assert TR.suffix_segment(moe_lm, m, 2) == ("blocks", 0)
    assert TR.segment_layer(moe_lm, "first") is moe_lm.dense_layer
    with pytest.raises(SystemExit, match="last segment 'blocks' holds 2"):
        TR.suffix_segment(moe_lm, m, 3)
    from bench.reference import dense_lm
    dm = {"num_layers": 8}
    assert TR.segments(dense_lm, dm) == [("blocks", 8)]
    assert TR.suffix_segment(dense_lm, dm, 2) == ("blocks", 6)


def test_reference_weights_take_the_programs_layout():
    from repro.models import transformer as T
    t = tiny(CELL)
    cfg = J.program_config(t["entry"])
    want = jax.eval_shape(lambda k: T.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(TR.make_params(moe_lm, t["entry"]["model"]),
                         jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(got)]


def test_expert_blocks_equal_the_per_expert_result():
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (3, 8, 64))              # [E, in, out]
    idx = jnp.array([2, 0], jnp.int32)
    blk = 16
    got = C.gather_blocks(w, idx, blk)
    assert got.shape == (3, 8, 2, blk)
    for e in range(3):
        np.testing.assert_array_equal(got[e], C.gather_blocks(w[e], idx, blk))
    vals = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 2, blk))
    put = C.set_blocks(w, idx, vals, blk)
    for e in range(3):
        np.testing.assert_array_equal(
            put[e], C.set_blocks(w[e], idx, vals[e], blk))
    np.testing.assert_array_equal(put[:, :, 16:32], w[:, :, 16:32])


def test_expert_selection_is_the_programs():
    """The reference's block draw for every leaf of the last segment, an
    expert leaf's among them, is the program's in-graph draw for the same
    key and step: one index set per layer for all experts."""
    from repro.core.selection import build_plan, random_selection
    t = tiny(CELL)
    m, mix = t["entry"]["model"], t["mix"]
    tc = J.train_config(J.program_config(t["entry"]), mix)
    plan = build_plan(tc.model, tc.sparse, mix["batch"] * mix["seq"])
    assert plan.seg_trainable == {"blocks": 2, "first": 0}
    key, step = jax.random.PRNGKey(2**31 - 9), 5
    prog = random_selection(plan, jax.random.fold_in(key, step))
    assert prog["first"] is None
    leaves = moe_lm.selectable_leaves(m)
    assert any(C.leaf_experts(leaf) == 8 for leaf in leaves)
    ref = C.draw_selection(key, step, "blocks", leaves, 2,
                           mix["update_ratio"], mix["channel_block"])
    for leaf in leaves:
        got = TR._get(prog["blocks"], leaf[0])          # [k, shards, n_sel]
        np.testing.assert_array_equal(got[:, 0], ref[leaf[0]])


def test_kernel_calls_book_expert_leaves_as_batched_dw():
    t = tiny(CELL)
    m, mix = t["entry"]["model"], t["mix"]
    calls = J.kernel_calls(moe_lm, m, mix)
    batched = [c for k, c in calls if k == "batched_dw"]
    # 3 expert leaves x 2 trainable layers; T = 128 tokens, top-2 of 8 at a
    # capacity factor of 4: 128 * 2 * 4 / 8 + 1 = 129 rows per expert
    assert len(batched) == 6
    assert {(c["e"], c["c"], c["cols"]) for c in batched} == {(8, 129, 16)}
    assert sorted({c["k"] for c in batched}) == [32, 64]
    assert len([k for k, _c in calls if k == "masked_dw"]) == 7 * 2
    opt = {c["rows"] for k, c in calls if k == "fused_block_opt"}
    assert 2 * 8 * 64 in opt and 2 * 8 * 32 in opt and 2 * 64 in opt
    assert all(c["itemsize"] == 4 for _k, c in calls)


def test_reference_moe_layer_is_dropless_and_renormalised():
    """Each token's output is its top-k experts' weighted by the renormalised
    top-k probabilities, plus the shared experts: one token alone gives
    the same output as inside the row."""
    t = tiny(CELL)
    m = t["entry"]["model"]
    params = jax.jit(TR.make_params(moe_lm, m))(jax.random.PRNGKey(4))
    blocks = params["segments"]["blocks"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, m["d_model"]))
    layer = lambda x: TR.layer_at(moe_lm.moe_layer, m, blocks, 0, x, "f32")
    y, sums = layer(x)
    assert float(sums["count"].sum()) == 8 * 2
    assert float(sums["prob"].sum()) == pytest.approx(8.0, rel=1e-5)
    h = moe_lm._attn(m, lambda *p: TR._get(blocks, p)[0], x, "f32")
    hn = C.rmsnorm({"scale": blocks["mlp_ln"]["scale"][0]}, h)[0]
    moe = blocks["moe"]
    probs = jax.nn.softmax(hn @ moe["router"][0], axis=-1)
    for tok in (0, 5):
        top = np.argsort(-np.asarray(probs[tok]))[:2]
        wts = probs[tok, top] / probs[tok, top].sum()
        want = h[0, tok] + moe_lm._swiglu(
            hn[tok], *(moe["shared"][n][0] for n in ("w_gate", "w_up",
                                                      "w_down")), "f32")
        for w, e in zip(wts, top):
            want = want + w * moe_lm._swiglu(
                hn[tok], *(moe[n][0, e] for n in ("w_gate", "w_up",
                                                   "w_down")), "f32")
        np.testing.assert_allclose(y[0, tok], want, rtol=2e-5, atol=2e-5)
