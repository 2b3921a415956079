"""Distribution-layer tests: logical specs, HLO collective parser, and a
multi-device (8 fake CPU devices, subprocess) integration test proving the
sharded MoE/train-step match the single-device reference."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_logical_specs_cover_all_leaves():
    from repro.configs import ARCH_IDS, get_smoke_config
    from repro.models.registry import abstract_params
    from repro.models.specs import param_logical_specs
    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        tree = abstract_params(cfg)
        specs = param_logical_specs(cfg)
        t_leaves = jax.tree_util.tree_leaves_with_path(tree)
        for path, leaf in t_leaves:
            node = specs
            for p in path:
                node = node[str(p.key)]
            assert isinstance(node, tuple) and len(node) == leaf.ndim, \
                (arch, path, node, leaf.shape)


def test_resolve_pspec_divisibility_fallback():
    from types import SimpleNamespace
    from repro.launch.specs import resolve_pspec
    from repro.launch.mesh import make_debug_mesh
    from repro.sharding import AxisRules, default_rules
    P = jax.sharding.PartitionSpec

    rules = default_rules(make_debug_mesh(1, 1))
    # 1x1 mesh: every dim divisible, axes applied as-is
    assert resolve_pspec((10, 7), ("batch", "ff"), rules) == \
        P(("data",), ("model",))
    # simulated 2x4 mesh: 7 % 4 != 0 -> the ff dim falls back to None
    big = AxisRules(dict(rules.rules),
                    mesh=SimpleNamespace(shape={"data": 2, "model": 4}),
                    batch_axes=("data",), model_axis="model")
    assert resolve_pspec((10, 7), ("batch", "ff"), big) == P(("data",), None)
    assert resolve_pspec((10, 8), ("batch", "ff"), big) == \
        P(("data",), ("model",))
    # unknown / None logical names resolve to None without error
    assert resolve_pspec((10, 7), (None, "nope"), big) == P(None, None)


def test_seq_sharded_rules_long_context_decode():
    """long_500k (batch=1): `seq_sharded_rules` moves the batch axes onto
    the KV-cache sequence dim, and `rules_for` extends that with the model
    axis for flash-decoding — 256-way sequence sharding on a full pod."""
    from repro.configs import ShapeConfig, get_smoke_config
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.specs import rules_for
    from repro.sharding import seq_sharded_rules

    mesh = make_debug_mesh(1, 1)
    r = seq_sharded_rules(mesh)
    assert r.rules["batch"] is None            # batch=1: nothing to shard
    assert tuple(r.rules["cache_seq"] or ()) == tuple(r.batch_axes)

    cfg = get_smoke_config("llama3-8b")
    long = rules_for(mesh, cfg, ShapeConfig("long_500k", 64, 1, "decode"))
    assert long.rules["batch"] is None
    assert long.rules["kv_heads"] is None      # GQA gather stays local
    assert tuple(long.rules["cache_seq"]) == tuple(r.batch_axes) + ("model",)
    # every other decode shape keeps batch-parallel defaults: sequence
    # shards over the model axis only
    short = rules_for(mesh, cfg, ShapeConfig("decode_32k", 64, 4, "decode"))
    assert tuple(short.rules["batch"] or ()) == tuple(r.batch_axes)
    assert tuple(short.rules["cache_seq"]) == ("model",)


def test_hlo_collective_parser_synthetic():
    from repro.launch.hlo_analysis import collective_bytes
    hlo = textwrap.dedent("""\
    HloModule test

    %body (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
      %p = (s32[], f32[8,4]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[8,4]{1,0} get-tuple-element(%p), index=1
      %ag = f32[8,4]{1,0} all-reduce(%x), channel_id=1, to_apply=%add
      ROOT %t = (s32[], f32[8,4]) tuple(%i, %ag)
    }

    %cond (p: (s32[], f32[8,4])) -> pred[] {
      %p = (s32[], f32[8,4]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %n = s32[] constant(12)
      ROOT %lt = pred[] compare(%i, %n), direction=LT
    }

    ENTRY %main (a: f32[8,4]) -> f32[8,4] {
      %a = f32[8,4]{1,0} parameter(0)
      %i0 = s32[] constant(0)
      %init = (s32[], f32[8,4]) tuple(%i0, %a)
      %w = (s32[], f32[8,4]) while(%init), condition=%cond, body=%body
      %ag2 = f32[8,4]{1,0} all-gather(%a), channel_id=2, dimensions={0}
      ROOT %out = f32[8,4]{1,0} get-tuple-element(%w), index=1
    }
    """)
    res = collective_bytes(hlo)
    # loop all-reduce wire bytes: 2 * 8*4*4 * 12 trips + one all-gather
    assert res["total"] == 2 * 128 * 12 + 128
    assert res["by_op"]["all-reduce"] == 2 * 128 * 12
    assert res["by_op"]["all-gather"] == 128
    assert res["naive"] == 2 * 128 + 128


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_debug_mesh
from repro.configs import get_smoke_config, ShapeConfig, SparseUpdateConfig, OptimizerConfig, TrainConfig
from repro.sharding import default_rules, use_rules
from repro.launch.specs import make_train_cell, rules_for
from repro.train import make_train_state, make_train_step
from repro.models import transformer as T

mesh = make_debug_mesh(2, 4)

# --- sharded MoE == local MoE -------------------------------------------
cfg = get_smoke_config("deepseek-moe-16b")
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, num_experts=8))
params = T.init_params(cfg, jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab_size)}
loss_local, _ = T.loss_fn(cfg, (params, None), batch)      # no mesh rules

rules = rules_for(mesh, cfg, ShapeConfig("t", 16, 4, "train"))
with use_rules(rules):
    loss_sharded, _ = jax.jit(lambda p, b: T.loss_fn(cfg, (p, None), b))(params, batch)
ok_moe = abs(float(loss_local) - float(loss_sharded)) < 2e-3

# --- sharded train step == single-device train step ----------------------
cfg2 = get_smoke_config("llama3-8b")
shape = ShapeConfig("t", 16, 4, "train")
tc = TrainConfig(model=cfg2, shape=shape,
                 sparse=SparseUpdateConfig(update_ratio=0.5, num_update_layers=1, channel_block=8),
                 optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1))
state, plan = make_train_state(tc, jax.random.PRNGKey(0))
step = make_train_step(tc, plan)
batch2 = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, cfg2.vocab_size),
          "labels": jax.random.randint(jax.random.PRNGKey(4), (4, 16), 0, cfg2.vocab_size)}
s_ref, m_ref = jax.jit(step)(state, batch2)

rules2 = rules_for(mesh, cfg2, shape)
with use_rules(rules2):
    s_sh, m_sh = jax.jit(step)(state, batch2)
diff = max(float(jnp.abs(a - b).max()) for a, b in
           zip(jax.tree.leaves(s_ref["params_trainable"]),
               jax.tree.leaves(s_sh["params_trainable"])))
ok_train = abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 2e-3 and diff < 2e-2
print("RESULT", ok_moe, ok_train, float(loss_local), float(loss_sharded), diff)
"""


@pytest.mark.slow
def test_multidevice_equivalence_subprocess():
    """8 fake CPU devices: sharded (2x4 mesh) MoE loss and full DGSU train
    step match the single-device reference numerically."""
    out = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT, SRC],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][-1]
    parts = line.split()
    assert parts[1] == "True", f"MoE mismatch: {line}"
    assert parts[2] == "True", f"train-step mismatch: {line}"
