"""Sharded paged serving: `paged_step` through shard_map over the model axis,
with every layer tensor-parallel (col/row-parallel linears, vocab-parallel
embed + logits) and per-user deltas riding the sharded step.

Fast tier-1 tests pin the flash-decoding split softmax to the monolithic
softmax (1e-6), single-device engine parity with flash_decode forced on,
the full shard_map plumbing on a one-shard mesh (personalized requests
included), and the rejection paths (indivisible KV heads, rules without a
mesh). The replication audit (multi-device lane) proves the sharded step
performs ZERO full-size matmuls on policy-sharded leaves. The slow
subprocess test forces 8 host CPU devices and proves 2-/4-way sharded
decode token-identical to the single-device engine — and, for llama3, to
the contiguous batch=1 oracle — for all four cache families plus a
deepseek-style MoE, including chunked prefill crossing page boundaries, a
radix prefix hit whose rehydration lands on the sharded pool, a
personalized (delta) request mix with online train waves, and the
launcher's sharded parameter init.
"""
import dataclasses
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sharding as SH
from repro.configs import get_smoke_config
from repro.launch.mesh import make_serve_mesh
from repro.models import decoding as D
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import ServeEngine
from repro.serve.engine import make_shared_prefix_requests
from repro.sharding import default_rules

PAGE = 4


def _tokens(stats):
    return {r.rid: list(r.tokens) for r in stats.results.values()}


# ---------------------------------------------------------------------------
# flash-decoding split softmax == monolithic softmax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,hq,hkv,hd,L_kv,tile", [
    (2, 1, 4, 2, 8, 13, 4),     # batched decode row, L not a tile multiple
    (1, 4, 8, 4, 8, 16, 4),     # prefill chunk, exact tiling
    (3, 4, 4, 4, 16, 7, 8),     # MHA, single ragged tile
    (2, 1, 8, 2, 8, 21, 4),     # deep GQA grouping
])
def test_flash_decode_matches_monolithic_softmax(b, s, hq, hkv, hd, L_kv,
                                                 tile):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, L_kv, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, L_kv, hkv, hd)), jnp.float32)
    mask = jnp.asarray(rng.random((b, s, L_kv)) > 0.4)
    mask = mask.at[:, :, 0].set(True)      # every query has >= 1 valid key
    mono = L._grouped_scores(q, k, v, mask)
    split = L._grouped_scores_split(q, k, v, mask, tile)
    assert float(jnp.abs(mono - split).max()) < 1e-6


def test_flash_decode_engine_token_parity():
    """flash_decode=True on the single-device engine must serve the same
    tokens as the default monolithic softmax."""
    cfg = get_smoke_config("llama3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    reqs = lambda: make_shared_prefix_requests(cfg, 4, 8, 11, 4, seed=3)
    ref = ServeEngine(cfg, params, num_slots=2, max_len=16, page_size=PAGE)
    fd = ServeEngine(cfg, params, num_slots=2, max_len=16, page_size=PAGE,
                     flash_decode=True)
    assert _tokens(ref.run(reqs())) == _tokens(fd.run(reqs()))
    # the DEFAULT single-device path stays bit-identical: one trace for
    # chunked prefill + one for batched decode
    assert ref._step._cache_size() == 2


# ---------------------------------------------------------------------------
# shard_map plumbing on a one-shard mesh (runs on a single CPU device)
# ---------------------------------------------------------------------------

def test_sharded_engine_one_shard_parity():
    cfg = get_smoke_config("llama3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    reqs = lambda: make_shared_prefix_requests(cfg, 4, 8, 11, 4, seed=3)
    ref = ServeEngine(cfg, params, num_slots=2, max_len=16, page_size=PAGE)
    rules = default_rules(make_serve_mesh(1))
    sh = ServeEngine(cfg, params, num_slots=2, max_len=16, page_size=PAGE,
                     rules=rules)
    s_ref, s_sh = ref.run(reqs()), sh.run(reqs())
    assert _tokens(s_ref) == _tokens(s_sh)
    assert s_sh.mesh_shards == 1
    assert s_sh.pool_shard_bytes > 0


# ---------------------------------------------------------------------------
# rejection paths (satellite 3)
# ---------------------------------------------------------------------------

def test_model_axis_size_without_mesh_raises():
    rules = SH.AxisRules({"heads": "model"}, mesh=None, model_axis="model")
    with SH.use_rules(rules):
        with pytest.raises(ValueError, match="no mesh"):
            SH.model_axis_size()
    # no rules installed, or rules without a model axis: still 1
    assert SH.model_axis_size() == 1
    with SH.use_rules(SH.AxisRules({}, mesh=None, model_axis=None)):
        assert SH.model_axis_size() == 1


def _fake_rules(n):
    mesh = types.SimpleNamespace(shape={"model": n},
                                 axis_names=("data", "model"))
    return SH.AxisRules({"paged_pool": "model"}, mesh=mesh,
                        model_axis="model")


def test_pool_sharding_rejects_indivisible_kv_heads():
    cfg = get_smoke_config("llama3-8b")         # smoke: Hq=4, Hkv=2
    with pytest.raises(ValueError, match="num_kv_heads"):
        D.validate_pool_sharding(cfg, _fake_rules(3))
    # the engine rejects at construction, before any device work
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="num_kv_heads"):
        ServeEngine(cfg, params, num_slots=1, max_len=8, page_size=PAGE,
                    rules=_fake_rules(3))
    # Hkv divides but Hq does not
    with pytest.raises(ValueError, match="num_heads"):
        D.validate_pool_sharding(
            dataclasses.replace(cfg, num_heads=6, num_kv_heads=4),
            _fake_rules(4))
    # divisible head counts validate to the mesh width
    assert D.validate_pool_sharding(cfg, _fake_rules(2)) == 2
    # a state-only arch has no pools to shard: any width passes through
    assert D.validate_pool_sharding(get_smoke_config("rwkv6-3b"),
                                    _fake_rules(3)) == 3


def test_engine_rejects_rules_without_mesh():
    cfg = get_smoke_config("llama3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    bad = SH.AxisRules({"heads": "model"}, mesh=None, model_axis="model")
    with pytest.raises(ValueError, match="mesh"):
        ServeEngine(cfg, params, num_slots=1, max_len=8, page_size=PAGE,
                    rules=bad)


def _p13n():
    from repro.configs import OptimizerConfig, SparseUpdateConfig
    from repro.serve import PersonalizationConfig
    return PersonalizationConfig(
        sparse=SparseUpdateConfig(update_ratio=0.5, num_update_layers=2,
                                  channel_block=8),
        optimizer=OptimizerConfig(kind="sgd", learning_rate=0.05),
        train_tokens=8)


def test_sharded_engine_personalized_one_shard_parity():
    """The mesh x personalization exclusion is lifted: a sharded engine
    serves a mixed plain/personalized workload token-identical to the
    single-device personalized engine, with the same 2 jitted-step traces
    (prefill shape + decode shape — deltas ride one fixed structure)."""
    cfg = get_smoke_config("llama3-8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    def reqs():
        rs = make_shared_prefix_requests(cfg, 4, 8, 11, 4, seed=3)
        for r in rs[::2]:
            r.user = 7      # same user twice: a train wave lands mid-run
        return rs

    ref = ServeEngine(cfg, params, num_slots=2, max_len=16, page_size=PAGE,
                      personalization=_p13n())
    sh = ServeEngine(cfg, params, num_slots=2, max_len=16, page_size=PAGE,
                     rules=default_rules(make_serve_mesh(1)),
                     personalization=_p13n())
    s_ref, s_sh = ref.run(reqs()), sh.run(reqs())
    assert _tokens(s_ref) == _tokens(s_sh)
    assert s_ref.train_waves == s_sh.train_waves > 0
    assert sh._step._cache_size() == 2
    assert ref._step._cache_size() == 2


# ---------------------------------------------------------------------------
# replication audit: zero full-size matmuls on policy-sharded leaves
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs a >= 2-device mesh (multi-device CI lane)")
def test_replication_audit_sharded_step():
    """Every matmul the sharding policy covers (MLP, embed/LM head,
    attention) must consume its LOCAL shard inside the sharded step — the
    single-device step over the same shapes trips the audit, proving the
    detector sees full-size matmuls when they exist."""
    from repro.launch.hlo_analysis import replicated_matmul_leaves
    # d_ff = 96 (not the smoke default 2 * d_model): keeps MLP full shapes
    # from colliding with attention locals, so the forbidden set stays rich
    cfg = dataclasses.replace(get_smoke_config("llama3-8b"), d_ff=96)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rules = default_rules(make_serve_mesh(2))
    step = D.make_sharded_paged_step(cfg, rules, params, page_size=PAGE)
    state, pools = D.init_serve_cache(cfg, 2, 16, 8, PAGE)
    pt = jnp.zeros((2, 4), jnp.int32)
    batch = {"tokens": jnp.zeros((2, 1), jnp.int32),
             "start": jnp.zeros((2,), jnp.int32),
             "active": jnp.ones((2,), bool),
             "length": jnp.ones((2,), jnp.int32)}
    forbidden, allowed = D.sharded_param_shapes(cfg, params, rules)
    # the policy must actually shard the MLP (d_ff divides the mesh here)
    assert (cfg.d_model, cfg.d_ff) in forbidden
    args = (params, batch, state, pools, pt)
    hits = replicated_matmul_leaves(lambda *a: step(*a), args, forbidden)
    assert hits == [], f"full-size matmuls on sharded leaves: {hits}"
    # sensitivity: the replicated (single-device) step over the same full
    # params shows the forbidden shapes the audit exists to catch
    ref_hits = replicated_matmul_leaves(
        lambda p, b, st, pl, t: D.paged_step(cfg, p, b, st, pl, t,
                                             page_size=PAGE),
        args, forbidden)
    assert ref_hits, "audit failed to flag a fully-replicated step"


# ---------------------------------------------------------------------------
# forced 8-device host mesh: 2-/4-way sharded == single-device, all families
# ---------------------------------------------------------------------------

_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs import get_smoke_config
from repro.launch.mesh import make_serve_mesh
from repro.models import decoding as D
from repro.models import transformer as T
from repro.serve import ServeEngine
from repro.serve.engine import make_shared_prefix_requests
from repro.sharding import default_rules

PAGE = 4
PREFIX = 8       # two full pages: snapshots + shared pages on the boundary
PROMPT = 11      # 2 full pages + a 3-row partial: chunks cross boundaries
GEN = 4
MAXLEN = 16

def toks(stats):
    return {r.rid: list(r.tokens) for r in stats.results.values()}

def acct(stats):
    return (stats.pages_peak, stats.cow_splits, stats.prefix_hit_tokens,
            stats.prefill_chunks)

def run_twice(engine, cfg):
    # run 2 re-matches run 1's prefixes: the tree is fresh but the spill
    # tier is warm, so hits REHYDRATE spilled pages into the (sharded) pool
    reqs = lambda: make_shared_prefix_requests(
        cfg, 3, PREFIX, PROMPT, GEN, seed=3)
    return engine.run(reqs()), engine.run(reqs())

def oracle(cfg, params, prompt, gen):
    logits, cache = D.prefill(cfg, params,
                              {"tokens": jnp.asarray(prompt)[None]},
                              pad_to=MAXLEN)
    out = [int(jnp.argmax(logits, -1)[0])]
    for t in range(len(prompt), len(prompt) + gen - 1):
        db = {"tokens": jnp.asarray([[out[-1]]], jnp.int32),
              "positions": jnp.full((1, 1), t, jnp.int32)}
        logits, cache = D.decode_step(cfg, params, db, cache)
        out.append(int(jnp.argmax(logits, -1)[0]))
    return out

assert jax.device_count() >= 8, jax.device_count()
all_ok = True
for arch in ("llama3-8b", "gemma3-4b", "rwkv6-3b", "jamba-1.5-large-398b",
             "deepseek-moe-16b"):
    cfg = get_smoke_config(arch)
    if cfg.num_heads:
        # smoke configs keep Hkv=2; a 4-way mesh needs Hkv % 4 == 0
        cfg = dataclasses.replace(cfg, num_heads=8, num_kv_heads=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ref = ServeEngine(cfg, params, num_slots=2, max_len=MAXLEN,
                      page_size=PAGE, num_pages=16)
    r1, r2 = run_twice(ref, cfg)
    ok_trace = ref._step._cache_size() == 2
    # state/hybrid archs truncate prefix matches to page boundaries, so
    # they share only full pages (no COW); positivity is a llama3 claim,
    # cross-shard equality is in acct() for everyone
    ok_cow = arch != "llama3-8b" or r1.cow_splits > 0
    ok_snap = (not ref._need_state) or r1.snapshot_hits > 0
    ok_oracle = True
    if arch == "llama3-8b":
        reqs = make_shared_prefix_requests(cfg, 3, PREFIX, PROMPT, GEN,
                                           seed=3)
        ok_oracle = all(
            r1.results[q.rid].tokens == oracle(cfg, params,
                                               np.asarray(q.tokens), GEN)
            for q in reqs)
    for n in (2, 4):
        rules = default_rules(make_serve_mesh(n))
        eng = ServeEngine(cfg, params, num_slots=2, max_len=MAXLEN,
                          page_size=PAGE, num_pages=16, rules=rules)
        s1, s2 = run_twice(eng, cfg)
        ok_par = toks(s1) == toks(r1) and toks(s2) == toks(r2)
        ok_acct = acct(s1) == acct(r1) and acct(s2) == acct(r2)
        ok_rehy = (not eng.prefix_sharing) or s2.rehydrates > 0
        eng._pool.check()
        # radix tree keeps resident pages across runs; residency must be
        # device-layout independent, i.e. identical to the 1-device engine
        ok_pool = eng._pool.in_use == ref._pool.in_use
        ok_shard = s1.mesh_shards == n and (
            not eng.has_pages or s1.pool_shard_bytes > 0)
        ok = (ok_par and ok_acct and ok_rehy and ok_pool and ok_shard
              and ok_trace and ok_cow and ok_snap and ok_oracle)
        all_ok = all_ok and ok
        print("RESULT", arch, n, int(ok_par), int(ok_acct), int(ok_rehy),
              int(ok_pool), int(ok_shard), int(ok_trace), int(ok_cow),
              int(ok_snap), int(ok_oracle), flush=True)

# --- personalized (delta) request mix on the sharded step -------------------
# decode_delta_spec targets attention/MLP projections, so the mix runs on
# llama3; waves train on the replicated host params, making the resulting
# deltas — and therefore the served tokens — mesh-width independent.
from repro.configs import OptimizerConfig, SparseUpdateConfig
from repro.serve import PersonalizationConfig

def p13n():
    return PersonalizationConfig(
        sparse=SparseUpdateConfig(update_ratio=0.5, num_update_layers=2,
                                  channel_block=8),
        optimizer=OptimizerConfig(kind="sgd", learning_rate=0.05),
        train_tokens=8)

def preqs(cfg):
    rs = make_shared_prefix_requests(cfg, 4, PREFIX, PROMPT, GEN, seed=5)
    for r in rs[::2]:
        r.user = 7          # repeat user: a train wave fires mid-run, so
    return rs               # later requests decode through a live delta

cfg = get_smoke_config("llama3-8b")
cfg = dataclasses.replace(cfg, num_heads=8, num_kv_heads=4)
params = T.init_params(cfg, jax.random.PRNGKey(0))
pref = ServeEngine(cfg, params, num_slots=2, max_len=MAXLEN, page_size=PAGE,
                   num_pages=16, personalization=p13n())
p1 = pref.run(preqs(cfg))
assert p1.train_waves > 0, p1.train_waves
for n in (2, 4):
    peng = ServeEngine(cfg, params, num_slots=2, max_len=MAXLEN,
                       page_size=PAGE, num_pages=16,
                       rules=default_rules(make_serve_mesh(n)),
                       personalization=p13n())
    s1 = peng.run(preqs(cfg))
    ok_par = toks(s1) == toks(p1)
    ok_wave = s1.train_waves == p1.train_waves
    ok_trace = peng._step._cache_size() == 2
    ok = ok_par and ok_wave and ok_trace
    all_ok = all_ok and ok
    print("PRESULT", n, int(ok_par), int(ok_wave), int(ok_trace), flush=True)

# --- launcher init: parameters built straight into their shardings ----------
# `build_engine --mesh-model 4` must leave no policy-sharded leaf whole on
# device 0, and a mesh engine without personalization keeps no other copy.
import argparse
from repro.launch.serve import add_serve_args, build_engine
args = add_serve_args(argparse.ArgumentParser()).parse_args(
    ["--arch", "llama3-8b", "--smoke", "--mesh-model", "4"])
_, eng = build_engine(args, cfg)
specs = D.paged_param_specs(cfg, eng.params, eng.rules)
full = {leaf.shape for leaf, spec in zip(
            jax.tree.leaves(eng.params),
            jax.tree.leaves(specs, is_leaf=lambda x: x is None))
        if any(a is not None for a in spec)}
dev0 = jax.devices()[0]
held = [a for a in jax.tree.leaves(vars(eng)) if isinstance(a, jax.Array)]
whole_on_dev0 = [a.shape for a in held if a.shape in full and any(
    s.device == dev0 and s.data.shape == a.shape
    for s in a.addressable_shards)]
n_sharded = len(full)
ok_init = n_sharded > 0 and not whole_on_dev0
all_ok = all_ok and ok_init
print("SRESULT", n_sharded, int(ok_init), whole_on_dev0, flush=True)
print("ALLOK", int(all_ok), flush=True)
"""

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


@pytest.mark.slow
def test_sharded_parity_forced_multidevice():
    """8 forced host CPU devices: 2-/4-way sharded decode token-identical
    to the single-device engine for every cache family plus a deepseek-style
    MoE, with page accounting device-layout independent, run-2 prefix hits
    rehydrating onto the sharded pool, a personalized request mix whose
    train waves and served tokens are mesh-width independent, and
    `build_engine --mesh-model 4` holding no policy-sharded parameter leaf
    whole on device 0."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT, SRC],
                          capture_output=True, text=True, timeout=1500,
                          env=env)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    lines = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")]
    assert len(lines) == 10, proc.stdout      # 5 archs x 2 mesh widths
    plines = [l for l in proc.stdout.splitlines() if l.startswith("PRESULT")]
    assert len(plines) == 2, proc.stdout      # personalized mix, n in (2, 4)
    slines = [l for l in proc.stdout.splitlines() if l.startswith("SRESULT")]
    assert len(slines) == 1, proc.stdout      # sharded launcher init
    assert "ALLOK 1" in proc.stdout, proc.stdout
