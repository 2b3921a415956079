"""The program's DeepSeek-V3 block (latent attention, sigmoid routing with a
correction bias, one chip's share of the experts, dropless dispatch)
against the plain reference bench/reference/deepseek_v3.py, on the CPU at
the moonlight cell's tiny size (bench_tiny: d_model 64, 8 heads at the
published head sizes, 8 of 64 experts held, top-6, 2 shared), seeded
random weights; and the check that decides the cell's `correct` refusing
a reference that leaves out the bias or the routed scaling."""
import copy
import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from bench.jobs import train as J
from bench.reference import deepseek_v3 as DS
from bench.reference import train_ref as TR
from bench_tiny import tiny

CELL = "moonlight-16b-a3b-ep8.train-sparse"


def _setup(model=None, seed=0):
    """(reference model dict, program config, the blocks segment's layer 0
    weights) of the tiny cell, weights made as the benchmark makes them."""
    entry = copy.deepcopy(tiny(CELL)["entry"])
    if model:
        entry["model"]["moe"].update(model)
    m = entry["model"]
    params = jax.jit(TR.make_params(DS, m))(jax.random.PRNGKey(seed))
    layer = jax.tree.map(lambda a: a[0], params["segments"]["blocks"])
    return m, J.program_config(entry), layer


def _get(layer):
    return lambda *p: TR._get(layer, p)


def _tokens(m, t, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, m["d_model"]))


@pytest.mark.parametrize("seq", [64, 2560], ids=["dense", "flash"])
def test_mla_attention_matches_reference(seq):
    """Latent attention, output and input gradient; at 2,560 tokens (above
    the flash threshold of 2,048) through the flash path, whose value head
    (128) differs from the query-key head (192)."""
    from repro.models import layers as L
    m, cfg, layer = _setup()
    x = 0.5 * _tokens(m, seq)[None]
    cot = _tokens(m, seq, seed=2)[None]
    pos = jnp.arange(seq)[None]

    def prog(x):
        h = L.apply_norm(layer["attn_ln"], x, cfg.norm_eps)
        return L.attention(layer["attn"], cfg, h, pos)

    def ref(x):
        return DS._mla(m, _get(layer), x, "f32") - x

    dx = jax.grad(lambda x: jnp.vdot(prog(x), cot))(x)
    dx_ref = jax.grad(lambda x: jnp.vdot(ref(x), cot))(x)
    np.testing.assert_allclose(prog(x), ref(x), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(dx, dx_ref, rtol=2e-4, atol=2e-4)


def _gates(ids, weights, routed):
    t = ids.shape[0]
    return jnp.zeros((t, routed)).at[jnp.arange(t)[:, None], ids].add(weights)


def test_sigmoid_routing_with_bias_matches_reference():
    """top-k of sigmoid scores plus a bias large enough to change the
    choice, weighted by the unbiased scores, renormalised and scaled."""
    from repro.models import moe as MOE
    m, cfg, layer = _setup()
    routed = m["moe"]["num_routed_experts"]
    layer["moe"]["score_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(7), (routed,))
    t = _tokens(m, 256)
    ids, w, _probs, logits = MOE.route(layer["moe"], cfg.moe, t)
    gates, ids_ref, _s = DS.route(m, _get(layer), t, "f32")
    unbiased = jax.lax.top_k(jax.nn.sigmoid(logits), m["moe"]["top_k"])[1]
    moved = np.mean(np.sort(np.asarray(unbiased), -1)
                    != np.sort(np.asarray(ids), -1))
    assert moved > 0.05, "the bias should change which experts are picked"
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(ids_ref, -1))
    np.testing.assert_allclose(_gates(ids, w, routed), gates, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), m["moe"]["routed_scaling"],
                               rtol=1e-5)


def _program_ffn(cfg, layer, t):
    """The program's expert layer on tokens t [T, d]: (output, aux)."""
    from repro.models import moe as MOE
    y, aux = MOE.apply_moe(layer["moe"], cfg, t[None])
    return y[0], aux


def test_chip_shares_sum_to_the_uncut_layer():
    """The eight shares of 8 experts each, whose partial outputs the
    program computes, add up (the shared experts counted once) to the
    reference's layer that holds all 64 experts."""
    import dataclasses
    m, cfg, layer = _setup({"num_experts": 64})
    t = _tokens(m, 128)
    shared = DS.moe_lm._swiglu(
        t, *(layer["moe"]["shared"][n] for n in ("w_gate", "w_up",
                                                 "w_down")), "f32")
    whole, _ids, _s = DS.moe_ffn(m, _get(layer), t, "f32")
    total = shared
    for share in range(8):
        sub = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, num_experts=8, first_expert=8 * share))
        p = dict(layer, moe=dict(layer["moe"], **{
            n: layer["moe"][n][8 * share:8 * share + 8]
            for n in ("w_gate", "w_up", "w_down")}))
        y, aux = _program_ffn(sub, p, t)
        total = total + (y - shared)
        assert 0 < float(aux["rows_held"]) < 128 * 6
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-4)


def test_dropless_under_skew():
    """A bias that sends every token's six slots to the eight held experts:
    the dispatch fills its whole row bound and drops nothing."""
    m, cfg, layer = _setup()
    routed, k = m["moe"]["num_routed_experts"], m["moe"]["top_k"]
    layer["moe"]["score_bias"] = jnp.where(jnp.arange(routed) < 8, 10.0, 0.0)
    t = _tokens(m, 96)
    y, aux = _program_ffn(cfg, layer, t)
    y_ref, ids, _s = DS.moe_ffn(m, _get(layer), t, "f32")
    assert bool(jnp.all(ids < 8))
    assert float(aux["rows_held"]) == 96 * k
    assert float(aux["rows_max"]) <= 96
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)


# -- the comparison that decides `correct` -----------------------------------

@pytest.fixture(scope="module")
def program_run():
    """The tiny cell's program through its checked steps (the compact
    sparse-update train step) and its readings against the sound
    reference."""
    t = tiny(CELL)
    job = J.Job({"load_reference": lambda n: importlib.import_module(
        f"bench.reference.{n}")}, t["entry"], t["mix"])
    assert job.tc.compact_grads
    job.build(2**31 + 123)
    prog, _ = job.checked_steps()
    job.free()
    ref = job.reference()
    return job, prog, ref, t["limits"]


def _judge(job, prog, ref, limits, w0):
    nums = check.readings(job.program_readings(prog, w0), ref)
    return check.judge(nums, limits)


def test_train_step_through_the_compact_path_is_correct(program_run):
    job, prog, ref, limits = program_run
    ok, checks = _judge(job, prog, ref, limits, ref["w0"])
    assert ok, checks
    assert all(c["value"] < 1e-4 for c in checks.values()), checks


def _ignores_bias(m):
    mod = types.SimpleNamespace(**vars(DS))

    def moe_layer(m, get, x, mode):
        def no_bias(*p):
            v = get(*p)
            return jnp.zeros_like(v) if p == ("moe", "score_bias") else v
        return DS.moe_layer(m, no_bias, x, mode)

    mod.LAYERS = dict(DS.LAYERS, blocks=moe_layer)
    return mod, m


def _no_routed_scaling(m):
    m = copy.deepcopy(m)
    m["moe"]["routed_scaling"] = 1.0
    return DS, m


@pytest.mark.parametrize("mutate", [_ignores_bias, _no_routed_scaling],
                         ids=["ignores_bias", "no_routed_scaling"])
def test_reference_missing_a_routing_term_is_not_correct(program_run, mutate):
    """A reference that ignores the correction bias, or drops the routed
    scaling, reads outside the cell's limits against the program."""
    job, prog, ref, limits = program_run
    mod, m = mutate(job.m)
    r = TR.Reference(mod, m, job.mix, "f32")
    params = jax.jit(TR.make_params(DS, job.m))(job.wkey)
    bad = r.run(params, job.batches, J.state_rng(job.skey),
                job.mix["checked_steps"])
    ok, checks = _judge(job, prog, bad, limits, ref["w0"])
    assert not ok, checks


def test_flops_per_token_by_hand():
    m = {"d_model": 64, "d_ff": 32, "vocab_size": 256, "num_layers": 3,
         "num_heads": 2, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
         "v_head_dim": 8, "kv_lora_rank": 16,
         "moe": {"num_experts": 4, "num_routed_experts": 8, "top_k": 2,
                 "num_shared_experts": 1, "dense_d_ff": 96,
                 "layout": "all_but_first"}}
    got = DS.flops_per_token(m, seq=32, k_train=2, ratio=0.2, block_req=16)
    # attention weights: wq 64*24, wkv_a 64*20, wkv_b 16*32, wo 16*64
    # = 4352; core 33 * 2 heads * (12 + 8) = 1320 per token; each token
    # reaches 2 * 4/8 = 1 held expert
    # dense layer 4352 + 3*64*96 = 22784; expert layer 4352 + router 64*8
    # + 3*64*32*(1 routed + 1 shared) = 17152
    # forward: (2*22784 + 1320) + 2*(2*17152 + 1320) + head 2*64*256
    # = 150904; input grads: 2*(2*17152 + 2*1320) - 2*(64*24 + 64*20)
    # + 32768 = 101024; weight grads per layer: router 2*64*8, wq one block
    # of 12 (2*64*12), wkv_a one of 10 (2*64*10), wkv_b and wo one of 16
    # (2*16*16 each), routed and shared gate/up one of 16 (2*64*16 each,
    # routed times 1 expert reached), down one of 16 (2*32*16 each)
    # = 15104; two layers 30208
    assert got == {"forward": 150904, "backward": 101024 + 30208,
                   "total": 282136}


def test_roofline_reader_finds_its_cell_by_the_run_s_shapes():
    """expert_ffn_roofline.train takes the cell whose FLOPs per token and
    kernel calls are the run's: the moonlight cell at its size, and no
    cell for the same cell cut to the tiny size."""
    import importlib.util
    import os
    from bench import run as R
    spec = importlib.util.spec_from_file_location("reader", os.path.join(
        R.ROOT, "bench", "metrics", "expert_ffn_roofline.train.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    def ctx(entry, mix):
        m = entry["model"]
        fl = DS.flops_per_token(m, mix["seq"], mix["update_layers"],
                                mix["update_ratio"], mix["channel_block"])
        return {"flops_per_token": fl["total"],
                "kernel_calls": J.kernel_calls(DS, m, mix)}

    bench = R.load_json("BENCHMARK.json")
    _cell, _conf, entry, mix = R.cell_files(bench, CELL)
    found = reader._cell(ctx(entry, mix))
    assert found is not None and found[0] == entry["model"]
    t = tiny(CELL)
    assert reader._cell(ctx(t["entry"], t["mix"])) is None
