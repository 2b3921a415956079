"""Training cells: the program's jitted compact sparse train step
(`repro.train.make_train_step`, as `launch/train.py` builds it: jit with
donation, compact gradients, the Pallas kernels on a TPU backend) over rows
drawn from the seed.

Set-up makes the weights and the whole train state on the device in one
jitted call (the weights go through `make_train_state(..., params=)`),
compiles the step ahead of time, and drives that compiled step through the
checked first steps on the feed the window uses. The window then calls the
same compiled step on the same state. Every step lies in the dynamic phase,
so the selection is redrawn in-graph every step.

After the window and the memory reading, the state is freed and the plain
reference (bench/reference/train_ref.py) follows the checked steps from the
same weights, rows and selection keys; bench/check.py compares.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, generate
from bench import trace_reduce as TRD
from bench.reference import train_ref as TR

TRACE_STEPS = 3
NUMBERS = check.NUMBERS


def program_config(entry: dict):
    """The program's ModelConfig from a configuration file's `model` keys: a
    nested `moe`, `ssm` or `rwkv` object becomes its sub-config, built from
    all of its keys; `rwkv_head_dim` is the RWKV head size."""
    from repro.configs.base import (ModelConfig, MoEConfig, RWKVConfig,
                                    SSMConfig)
    m = dict(entry["model"])
    hd = m.pop("rwkv_head_dim", None)
    if hd:
        m["rwkv"] = {"head_dim": hd}
    for key, sub in (("moe", MoEConfig), ("ssm", SSMConfig),
                     ("rwkv", RWKVConfig)):
        if isinstance(m.get(key), dict):
            m[key] = sub(**m[key])
    return ModelConfig(name=entry["name"], **m)


def train_config(cfg, mix: dict):
    from repro.configs.base import (OptimizerConfig, ShapeConfig,
                                    SparseUpdateConfig, TrainConfig)
    o = mix["optimizer"]
    sparse = SparseUpdateConfig(
        enabled=True, update_ratio=mix["update_ratio"],
        num_update_layers=mix["update_layers"],
        channel_block=mix["channel_block"],
        # the whole run lies in the dynamic phase: a fresh selection every
        # step, drawn in-graph
        phase_fixed_early=0, phase_dynamic=2**30, phase_fixed_late=0)
    opt = OptimizerConfig(kind=o["kind"], learning_rate=o["learning_rate"],
                          momentum=o["momentum"],
                          weight_decay=o["weight_decay"], beta1=o["beta1"],
                          beta2=o["beta2"], eps=o["eps"], warmup_steps=0,
                          decay_steps=0)
    return TrainConfig(model=cfg, shape=ShapeConfig("bench", mix["seq"],
                                                    mix["batch"], "train"),
                       sparse=sparse, optimizer=opt, compact_grads=True)


def keys(seed: int):
    """(weights key, train-state key) from a seed of any size."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    return jax.random.fold_in(base, 1), jax.random.fold_in(base, 2)


def state_rng(state_key):
    """The key the program keeps in its state: make_train_state splits the
    key it is given and keeps the second half."""
    return jax.random.split(state_key)[1]


def kernel_calls(ref, m: dict, mix: dict) -> list:
    """The sparse-update kernel calls of one step, from the shapes: per
    selectable leaf and trainable layer a `masked_dw` over all tokens, or
    for an expert leaf a `batched_dw` over the rows per expert that the
    program's dispatch gives it (`ref.expert_rows`); per leaf one
    `fused_block_opt` over every trainable layer's (and expert's) fan-in."""
    from bench.reference.common import leaf_experts, sel_spec
    k = mix["update_layers"]
    tokens = mix["batch"] * mix["seq"]
    isz = jnp.dtype(m["dtype"]).itemsize
    state = {"sgd": 0, "momentum": 1, "adamw": 2}[mix["optimizer"]["kind"]]
    if mix["optimizer"]["kind"] == "sgd" and mix["optimizer"]["momentum"]:
        state = 1
    calls = []
    for leaf in ref.selectable_leaves(m):
        fan_in, e = leaf[1], leaf_experts(leaf)
        block, _nb, n_sel = sel_spec(leaf[2], mix["update_ratio"],
                                     mix["channel_block"])
        cols = n_sel * block
        if e:
            calls += [("batched_dw", {"e": e, "c": ref.expert_rows(m, mix),
                                      "k": fan_in, "cols": cols,
                                      "itemsize": isz})] * k
        else:
            calls += [("masked_dw", {"m": tokens, "k": fan_in, "cols": cols,
                                     "itemsize": isz})] * k
        calls.append(("fused_block_opt", {"rows": k * max(e, 1) * fan_in,
                                          "cols": cols, "itemsize": isz,
                                          "state": state}))
    return calls


def _seg(tree, name: str):
    return tree["segments"][name]


def _flat(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        out[TR.key_of(tuple(p.key for p in path))] = leaf
    return out


class Job:
    """One training cell: set-up, checked steps, window, reference."""

    def __init__(self, ctx: dict, cfg_entry: dict, mix: dict):
        self.ctx, self.entry, self.mix = ctx, cfg_entry, mix
        self.m = cfg_entry["model"]
        self.ref = ctx["load_reference"](cfg_entry["reference"])
        self.cfg = program_config(cfg_entry)
        # the trainable suffix: the last layers of the program's last segment
        self.seg, self.first = TR.suffix_segment(
            self.ref, self.m, mix["update_layers"])
        self.tc = train_config(self.cfg, mix)
        self.tokens = mix["batch"] * mix["seq"]
        # a planted fault (the tests'): fn(job, batch) -> loss in place of
        # the step
        self.fault = ctx.get("fault")

    # -- set-up --------------------------------------------------------------
    def build(self, seed: int):
        from repro.models import transformer as T
        from repro.train import make_train_state, make_train_step

        self.seed = seed
        self.wkey, self.skey = keys(seed)
        init = TR.make_params(self.ref, self.m)
        want = jax.eval_shape(lambda k: T.init_params(self.cfg, k),
                              jax.random.PRNGKey(0))
        got = jax.eval_shape(init, jax.random.PRNGKey(0))
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise SystemExit("bench: the benchmark's weights do not match "
                             "the program's parameter layout")
        plans = []

        def build_state(wkey, skey):
            state, plan = make_train_state(self.tc, skey, params=init(wkey))
            plans.append(plan)
            return state

        self.state = jax.jit(build_state)(self.wkey, self.skey)
        self.plan = plans[0]
        raw = make_train_step(self.tc, self.plan, donate=True)
        self.raw_step = raw
        step = jax.jit(raw, donate_argnums=raw.donate_argnums)
        self.batches = generate.markov_lm(self.mix, self.m["vocab_size"],
                                          seed, self.mix["feed_batches"])
        self.feed = [{k: jax.device_put(v) for k, v in b.items()}
                     for b in self.batches]
        self.step = step.lower(self.state, self.feed[0]).compile()
        self.memory_analysis = self.step.memory_analysis()
        self.at = 0

    def run_step(self):
        batch = self.feed[self.at % len(self.feed)]
        self.at += 1
        if self.fault is not None:
            return self.fault(self, batch)
        self.state, metrics = self.step(self.state, batch)
        return metrics["loss"]

    def checked_steps(self) -> tuple:
        """Steps 1..n through the window's compiled step and feed; returns
        the program's readings (host copies) and the seconds spent reading
        them, which are not set-up."""
        n = self.mix["checked_steps"]
        opt = self.mix["optimizer"]
        losses, read_s = [], 0.0
        prog = {"grad_norms": None, "w1": None}
        for i in range(n):
            losses.append(float(self.run_step()))
            if i == 0:
                t = time.perf_counter()
                seg = _seg(self.state["params_trainable"], self.seg)
                if opt["kind"] == "adamw":
                    mu = _flat(_seg(self.state["opt"]["mu"], self.seg))
                    prog["grad_norms"] = {
                        k: float(TR._norm(v)) / (1 - opt["beta1"])
                        for k, v in mu.items()}
                else:
                    prog["w1"] = jax.device_get(_flat(seg))
                read_s += time.perf_counter() - t
        t = time.perf_counter()
        prog["wn"] = jax.device_get(
            _flat(_seg(self.state["params_trainable"], self.seg)))
        prog["losses"] = losses
        read_s += time.perf_counter() - t
        return prog, read_s

    # -- window --------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        """Calls the step until `seconds` have passed since the last set-up
        step was ready; counts the steps whose loss is ready inside, and
        keeps the host-clock intervals between them (for the log: whether
        a slow window lost its time in one stall or in every step)."""
        t_start = time.perf_counter()
        deadline = t_start + seconds
        pending = self.run_step()
        done, t_last, bad, gaps = 0, t_start, 0, []
        while True:
            nxt = self.run_step()
            loss = float(pending)
            t = time.perf_counter()
            if t > deadline:
                break
            gaps.append(t - t_last)
            done, t_last = done + 1, t
            bad += not np.isfinite(loss)
            pending = nxt
        float(nxt)
        if done == 0:
            raise SystemExit(f"bench: no step finished inside {seconds} s")
        return {"steps": done, "seconds": t_last - t_start,
                "non_finite": bad, "gaps": sorted(gaps),
                "tokens_per_s": done * self.tokens / (t_last - t_start)}

    def traced(self, trace_dir: str) -> dict:
        TRD.start(trace_dir)
        with jax.profiler.TraceAnnotation("bench_window"):
            for j in range(TRACE_STEPS):
                with jax.profiler.StepTraceAnnotation("train_step",
                                                      step_num=j):
                    loss = self.run_step()
            float(loss)
        jax.profiler.stop_trace()
        events = TRD.load_events(trace_dir)
        span = TRD.host_span(events, "bench_window")
        if span is None:
            raise SystemExit(f"bench: the trace in {trace_dir} has no "
                             f"bench_window span ({len(events)} events)")
        names = [n for k in self.ctx["kernel_costs"].values() for n in k.NAMES]
        red = TRD.reduce(events, span, names, "train_step")
        if not red["steps"]:
            raise SystemExit("bench: the traced window holds no train_step "
                             "program")
        red["events"] = len(events)
        return red

    def free(self):
        for name in ("state", "feed", "step"):
            setattr(self, name, None)
        gc.collect()

    # -- reference -----------------------------------------------------------
    def reference(self, mode: str = "f32", rows=None) -> dict:
        params = jax.jit(TR.make_params(self.ref, self.m))(self.wkey)
        r = TR.Reference(self.ref, self.m, self.mix, mode)
        out = r.run(params, self.batches, state_rng(self.skey),
                    self.mix["checked_steps"], rows=rows)
        out["w0"] = {TR.key_of(p): TR._get(_seg(params, self.seg),
                                           p)[self.first:]
                     for p in TR.seg_paths(self.ref, self.m)}
        return out

    def program_readings(self, prog: dict, w0: dict) -> dict:
        opt = self.mix["optimizer"]
        dn = lambda a, b: float(TR._diff_norm(jnp.asarray(a), b))
        grad = prog["grad_norms"]
        if grad is None:
            grad = {k: dn(v, w0[k]) / opt["learning_rate"]
                    for k, v in prog["w1"].items()}
        return {"losses": prog["losses"], "grad_norms": grad,
                "change_norms": {k: dn(v, w0[k])
                                 for k, v in prog["wn"].items()}}


def run(ctx: dict, cfg_entry: dict, mix: dict, seed: int, seconds: float,
        trace: bool) -> dict:
    log = ctx["log"]
    job = Job(ctx, cfg_entry, mix)
    job.build(seed)
    ma = job.memory_analysis
    log(f"compiled step memory_analysis: arguments "
        f"{ma.argument_size_in_bytes} B, outputs {ma.output_size_in_bytes} B,"
        f" temporaries {ma.temp_size_in_bytes} B, aliased "
        f"{ma.alias_size_in_bytes} B")
    log(f"traffic: {generate.summary(job.batches)}")
    prog, read_s = job.checked_steps()
    setup_s = time.perf_counter() - ctx["t0"] - read_s
    log(f"set-up {setup_s:.6f} s (reading the checked steps took "
        f"{read_s:.6f} s more); checked losses {prog['losses']}")

    compiles_before = ctx["compiles"]()
    win = job.window(seconds)
    in_window = ctx["compiles"]() - compiles_before
    gaps = win["gaps"]
    log(f"window: {win['steps']} steps in {win['seconds']:.6f} s, "
        f"{win['tokens_per_s']:.6f} tokens/s; compilations inside the "
        f"window: {in_window}; s between steps: median "
        f"{gaps[len(gaps) // 2]:.6f}, longest {gaps[-3:][::-1]}")
    peak = ctx["peak_bytes"]()
    log(f"peak_bytes_in_use after the window: {peak} B (compiled step: "
        f"arguments + temporaries "
        f"{ma.argument_size_in_bytes + ma.temp_size_in_bytes} B)")
    fl = job.ref.flops_per_token(job.m, mix["seq"], mix["update_layers"],
                                 mix["update_ratio"], mix["channel_block"])
    layer = {"tokens_per_s": win["tokens_per_s"],
             "flops_per_token": fl["total"],
             "kernel_calls": kernel_calls(job.ref, job.m, mix),
             "trace": None}
    if trace:
        layer["trace"] = job.traced(ctx["trace_dir"])
        t = layer["trace"]
        log(f"trace: {t['events']} events, window {t['window_s']:.6f} s, "
            f"busy {t['busy_s']:.6f} s, steps {t['steps']}, kernels "
            f"{t['kernel_s']}")
        t0 = time.perf_counter()
        layer["program_text"] = job.step.as_text()
        log(f"program text of the run's compiled step for the scope "
            f"readers: {len(layer['program_text'])} characters in "
            f"{time.perf_counter() - t0:.3f} s, in place of a second "
            f"compile of the step")
    job.free()

    t = time.perf_counter()
    ref = job.reference()
    got = job.program_readings(prog, ref["w0"])
    nums = check.readings(got, ref)
    log(f"reference took {time.perf_counter() - t:.6f} s; reference losses "
        f"{ref['losses']}; worst leaves: grad {nums['grad_leaf']}, change "
        f"{nums['change_leaf']}; dropped {nums['dropped_leaves']}")
    correct, checks = check.judge(nums, ctx["limits"])
    return {"correct": correct and win["non_finite"] == 0,
            "attempted": win["steps"], "failed": win["non_finite"],
            "end_to_end": {"train_tokens_per_s": win["tokens_per_s"],
                           "train_peak_hbm_gb": peak / 1e9 if peak else None,
                           "setup_s": setup_s},
            "layer_ctx": layer, "checks": checks,
            "memory_peak_bytes": peak}
