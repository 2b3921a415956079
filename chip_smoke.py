"""Bring-up check on a TPU: the sparse-update trainer and the paged server,
driven through their entry points at full published widths, with random
weights made from a seed.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: tensor-parallel serving

One chip, in order:
  1. device: platform, kind, count, JAX version; exits non-zero unless the
     platform is `tpu` (there is no CPU fallback);
  2. kernels: the four sparse-update kernels (both dW variants) once each,
     compiled, at the rwkv6-3b step's leaf shapes, against kernels/ref.py;
  3. train: `repro.launch.train.main`, rwkv6-3b full config, 4 compact
     steps of 4,096 tokens; finite losses and two Pallas kernel sites per
     selectable leaf in the compiled step;
  4. serve: `repro.launch.serve.main`, rwkv6-3b full config, 8 requests;
     every request completes.

With --four-chips, only: nemotron-4-15b (full config) served over a 4-way
model mesh, with each chip's share of the weights and peak memory; then a
4-layer cut served at mesh 1 and mesh 4, the first prefill step's logits
compared.

Everything runs in this one process, the only one that touches JAX. A
failing phase exits non-zero before the result line. The last stdout line
is {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Times and memory printed here come from a bring-up run, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
GB = 1e9

# dW: bf16 values multiply exactly in f32, so a kernel and the f32 reference
# differ only in the order of their M-term f32 sums, an error of order
# sqrt(M) * 2^-24 of the sums' scale. 1e-3 of the largest entry leaves room
# for that, and still fails a misrouted block or tile, whose error is of
# the entries' own size.
DW_TOL = 1e-3
# fused optimizer: the kernel and the jnp oracle run the same f32 rule; the
# updated bf16 weights may round one bf16 step (2^-8) apart where the f32
# results differ in their last bit, the f32 state only in that last bit.
OPT_W_TOL, OPT_STATE_TOL = 2.0 ** -7, 1e-5
# mesh 1 vs mesh 4 logits of a bf16 model: the 4-way row-parallel matmuls
# round each partial sum to bf16 before the psum, and the split softmax
# reorders the attention sums, a few bf16 steps (2^-8) through 4 layers.
# A shard routed to the wrong rows or a missing psum is off by the logits'
# own size.
LOGITS_TOL = 5e-2


class SmokeFailure(Exception):
    pass


def log(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


def require(cond: bool, phase: str, msg: str):
    if not cond:
        raise SmokeFailure(f"{phase}: {msg}")


def peak_bytes(dev):
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def fmt_bytes(n) -> str:
    return "not reported" if n is None else f"{n / GB:.3f} GB"


def device_phase(require_tpu: bool) -> dict:
    devs = jax.devices()
    d = devs[0]
    log("device", f"platform={d.platform} kind={d.device_kind} "
                  f"count={len(devs)} jax={jax.__version__}")
    if require_tpu and d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform!r}); this check runs on a TPU only")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _compare(name: str, got, want, tol: float):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, "kernels",
            f"{name}: shape {got.shape} != reference {want.shape}")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    ok = bool(np.isfinite(got).all()) and err <= tol * scale
    log("kernels", f"{name}: shape {got.shape} max|err| {err:.3e}, limit "
                   f"{tol:g} x max|ref| {scale:.3e} -> "
                   f"{'ok' if ok else 'FAIL'}")
    require(ok, "kernels", f"{name} disagrees with kernels/ref.py")


def kernel_phase(smoke: bool):
    """Each sparse-update kernel once through `kernels.ops` (compiled on a
    TPU), at the rwkv6-3b step's leaf shapes: 4,096 tokens, a 2560-wide
    time-mix leaf, channel block 128, ratio 0.2 (4 of 20 blocks), 2
    trainable layers. The expert-batched dW has no rwkv6 leaf; it takes the
    same tokens as 4 experts of capacity 1,024."""
    from repro.configs import OptimizerConfig
    from repro.core.sparse_update import SelSpec
    from repro.kernels import ops, ref

    m, d, block, n_sel, steps, experts = (
        (64, 64, 16, 1, 2, 2) if smoke else (4096, 2560, 128, 4, 2, 4))
    spec = SelSpec(block=block, n_shards=1, n_sel=n_sel, n_blocks=d // block)
    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    bf = jnp.bfloat16

    def sel(key, lead=()):
        u = jax.random.uniform(key, lead + (1, spec.n_blocks))
        return jnp.sort(jnp.argsort(u, axis=-1)[..., :n_sel],
                        axis=-1).astype(jnp.int32)

    x = jax.random.normal(ks[0], (m, d), bf)
    dy = jax.random.normal(ks[1], (m, d), bf)
    idx = sel(ks[2])
    highest = jax.default_matmul_precision("highest")
    with highest:
        want = ref.block_sparse_dw_ref(x, dy, idx, block)
    for pipelined in (False, True):
        got = ops.block_sparse_dw(x, dy, idx, spec, pipelined=pipelined)
        _compare("masked_dw" + ("_pipelined" if pipelined else ""), got,
                 want, DW_TOL)

    # rows grouped by expert as the dispatch sorts them: uneven groups, an
    # empty one, and rows past the last group
    sizes = jnp.asarray([m // 2, 0] + [m // (4 * max(1, experts - 2))]
                        * (experts - 2), jnp.int32)
    with highest:
        want = ref.batched_dw_ref(x, dy, sizes, idx, block)
    _compare("batched_dw", ops.block_sparse_dw_batched(x, dy, sizes, idx,
                                                       spec), want, DW_TOL)

    w = jax.random.normal(ks[3], (steps, d, d), bf)
    vals = jax.random.normal(ks[4], (steps, d, 1, n_sel, block), bf)
    idx3 = sel(ks[5], (steps,))
    _compare("scatter_blocks", ops.block_scatter_update(w, vals, idx3, spec),
             ref.block_scatter_update_ref(w, vals, idx3, block), 0.0)

    g = (1e-2 * vals.astype(jnp.float32)).astype(bf)
    mu = jax.random.normal(ks[6], (steps, d, d), jnp.float32)
    nu = jnp.abs(jax.random.normal(ks[7], (steps, d, d), jnp.float32))
    lr, t = jnp.float32(1e-3), jnp.float32(3.0)
    for kind, n_state in (("sgd", 0), ("momentum", 1), ("adamw", 2)):
        oc = OptimizerConfig(kind=kind, learning_rate=1e-3,
                             momentum=0.9 if kind == "momentum" else 0.0)
        state = [mu, nu][:n_state] + [None] * (2 - n_state)
        got = ops.fused_block_optimizer(oc, w, g, idx3, spec, *state, lr, t)
        want = ref.fused_block_opt_ref(
            w, g, idx3, lr, t, *state, kind=kind, momentum=oc.momentum,
            beta1=oc.beta1, beta2=oc.beta2, eps=oc.eps,
            weight_decay=oc.weight_decay)
        _compare(f"fused_block_opt[{kind}] weights", got[0], want[0],
                 OPT_W_TOL)
        for i in range(n_state):
            _compare(f"fused_block_opt[{kind}] state {i}", got[1 + i],
                     want[1 + i], OPT_STATE_TOL)


def train_phase(smoke: bool, on_tpu: bool):
    from repro.launch import train
    size = ["--smoke", "--seq", "32", "--batch", "2"] if smoke else \
        ["--seq", "1024", "--batch", "4"]
    out = train.main(["--arch", "rwkv6-3b", *size, "--compact-grads",
                      "--channel-block", "128", "--update-layers", "2",
                      "--steps", "4", "--log-every", "1",
                      "--seed", str(SEED)])
    losses = out["losses"]
    log("train", f"losses {losses}")
    require(len(losses) == 4 and all(math.isfinite(v) for v in losses),
            "train", f"expected 4 finite losses, got {losses}")
    want_sites = 2 * out["sel_leaves"]
    log("train", f"compiled step: {out['kernel_sites']} tpu_custom_call "
                 f"sites for {out['sel_leaves']} selectable leaves "
                 f"(want {want_sites})")
    if on_tpu:
        require(out["sel_leaves"] > 0 and out["kernel_sites"] == want_sites,
                "train", "the compact step does not hold two Pallas kernel "
                         "sites per selectable leaf")
    else:
        log("train", "off TPU the kernels are interpreted and inlined: "
                     "site count not checked")
    steady = statistics.median(out["step_s"][1:])
    log("train", f"compile {out['compile_s']:.3f} s; step times "
                 f"{[round(s, 6) for s in out['step_s']]} s; steady "
                 f"(median of steps 2-4) {steady:.6f} s; "
                 f"peak_bytes_in_use {fmt_bytes(peak_bytes(jax.devices()[0]))}"
                 " (bring-up run, not a benchmark)")


def serve_phase(smoke: bool):
    from repro.launch import serve
    n_req, gen = 8, 32
    size = ["--smoke", "--prompt-len", "32"] if smoke else \
        ["--prompt-len", "512"]
    stats = serve.main(["--arch", "rwkv6-3b", *size, "--requests",
                        str(n_req), "--gen-len", str(gen), "--batch", "4",
                        "--seed", str(SEED)])
    done = [r for r in stats.results.values()
            if r.status == "completed" and len(r.tokens) == gen]
    log("serve", f"{len(done)}/{n_req} requests completed with {gen} tokens "
                 f"each; peak_bytes_in_use "
                 f"{fmt_bytes(peak_bytes(jax.devices()[0]))}")
    require(stats.requests_completed == n_req and len(done) == n_req,
            "serve", "not every request completed")


def _serve_args(arch, mesh, n_req, prompt, gen, batch):
    from repro.launch import serve
    return serve.add_serve_args(argparse.ArgumentParser()).parse_args(
        ["--arch", arch, "--mesh-model", str(mesh), "--requests", str(n_req),
         "--prompt-len", str(prompt), "--gen-len", str(gen), "--batch",
         str(batch), "--seed", str(SEED)])


def _first_step_logits(cfg, mesh: int, prompt: int) -> np.ndarray:
    """Serve one request and keep the logits of the engine's first step
    (the first prefill chunk of the prompt)."""
    from repro.launch import serve
    args = _serve_args(cfg.name, mesh, 1, prompt, 2, 1)
    _, engine = serve.build_engine(args, cfg)
    step, first = engine._step, []

    def recording(*a):
        out = step(*a)
        if not first:
            first.append(np.asarray(out[0], np.float32))
        return out

    engine._step = recording
    engine.run(serve.build_requests(args, cfg))
    return first[0]


def four_chip_phase(smoke: bool):
    from repro.configs import get_config, get_smoke_config
    from repro.launch import serve
    from repro.models import transformer as T

    n = 4
    devs = jax.devices()[:n]
    require(len(devs) == n, "four-chips", f"needs {n} devices, found "
                                          f"{len(jax.devices())}")
    arch = "nemotron-4-15b"
    if smoke:   # smoke widths with KV heads that split 4 ways
        cfg = dataclasses.replace(get_smoke_config(arch), num_heads=8,
                                  num_kv_heads=4, dtype="bfloat16")
        prompt, gen = 32, 8
    else:
        cfg = get_config(arch)
        prompt, gen = 512, 32
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        jax.eval_shape(functools.partial(T.init_params, cfg),
                       jax.random.PRNGKey(SEED))))
    n_req = 8
    args = _serve_args(arch, n, n_req, prompt, gen, 4)
    _, engine = serve.build_engine(args, cfg)
    held = {d: 0 for d in devs}
    for leaf in jax.tree.leaves(engine.params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    stats = engine.run(serve.build_requests(args, cfg))
    done = [r for r in stats.results.values()
            if r.status == "completed" and len(r.tokens) == gen]
    log("four-chips", f"{cfg.name} ({cfg.num_layers} layers, weights "
                      f"{weights / GB:.3f} GB) on a {n}-way model mesh: "
                      f"{len(done)}/{n_req} requests completed, "
                      f"{stats.tokens_out} tokens")
    require(len(done) == n_req, "four-chips", "not every request completed")
    for d in devs:
        log("four-chips", f"device {d.id}: weights held {held[d] / GB:.3f} GB "
                          f"({held[d] / weights:.4f} of the model), "
                          f"peak_bytes_in_use {fmt_bytes(peak_bytes(d))}")
        # a quarter of the matmul weights plus the replicated norms
        require(held[d] <= 0.3 * weights, "four-chips",
                f"device {d.id} holds more than its share of the weights")
    del engine
    gc.collect()

    cut = dataclasses.replace(cfg, num_layers=4)
    ref = _first_step_logits(cut, 1, prompt)
    gc.collect()
    got = _first_step_logits(cut, n, prompt)
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    ok = bool(np.isfinite(got).all()) and err <= LOGITS_TOL * scale
    log("four-chips", f"4-layer cut, first prefill logits {ref.shape}: mesh "
                      f"{n} vs mesh 1 max|err| {err:.3e}, limit "
                      f"{LOGITS_TOL:g} x max|ref| {scale:.3e}; argmax "
                      f"{int(got.argmax())} vs {int(ref.argmax())} -> "
                      f"{'ok' if ok else 'FAIL'}")
    require(ok, "four-chips", "mesh-4 logits disagree with mesh 1")


def run(four_chips: bool = False, *, smoke: bool = False,
        require_tpu: bool = True):
    """`smoke`/`require_tpu` exist for a CPU rehearsal at smoke widths; the
    command line always runs full widths and requires a TPU."""
    device = device_phase(require_tpu)
    from repro.launch.compile_cache import enable_compile_cache
    log("device", f"compile cache: {enable_compile_cache()}")
    try:
        if four_chips:
            four_chip_phase(smoke)
        else:
            kernel_phase(smoke)
            train_phase(smoke, device["platform"] == "tpu")
            gc.collect()
            serve_phase(smoke)
    except SmokeFailure as e:
        raise SystemExit(f"chip_smoke FAILED in {e}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the tensor-parallel serving phase, on four "
                         "chips")
    run(ap.parse_args(argv).four_chips)


if __name__ == "__main__":
    main()
