"""Device time of the train step by program scope, from the profiler trace
of a traced run.

The program names its layers with `jax.named_scope` (`embed`,
`frozen_layers`, `trainable_layers`, `token_mix`, `channel_mix`,
`head_loss`, `reselect`, `update`); XLA keeps each op's scope path in the
`op_name` metadata of its HLO instruction, e.g.
`jit(train_step)/transpose(jvp(trainable_layers))/while/body/.../token_mix/dot_general`.
The TPU trace names each device op by its instruction's text
(`%fusion.632 = bf16[2,2048,24576]{...} fusion(...), ...`) without that
metadata, so the op names come from the compiled program:
`program_text(entry, mix)` compiles the cell's train step again as
bench/jobs/train.py compiles it (a hit in the run's compilation cache),
and `attach_scopes(events, text)` gives each device op the `op_name` of
the instruction whose name, result shape and opcode it carries.

`scopes(events, window_ns, names)` attributes each device op's self time
to the scopes in its path. `step_ms(ctx, names)` is what the per-layer
readers call: device ms per traced step under any of `names`, from the
trace the run left in `.bench_trace/<cell>/`.
"""
from __future__ import annotations

import glob
import importlib
import os
import re
import sys
import time

from bench import trace_reduce as TRD

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ("embed", "frozen_layers", "trainable_layers", "head_loss",
         "reselect", "update", "token_mix", "channel_mix")
# the scopes that never nest in one another: with unscoped_s they cover
# the busy time once (bar the few ops XLA hoists out of a stack, which keep
# only token_mix); token_mix and channel_mix lie inside the two stacks
TOP = NAMES[:6]
# a trace's ops are taken as the compiled program's only if this share of
# them carries the signature of an instruction of that program
MATCHED = 0.99
_WRAPPED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+ = .*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def program_text(entry: dict, mix: dict) -> str:
    """The optimized HLO text of the train step of a cell (its configuration
    file's entry and its traffic mix), lowered from the shapes that
    bench/jobs/train.py lowers it from."""
    import jax
    import jax.numpy as jnp
    from bench.jobs import train as J
    from bench.reference import train_ref as TR
    from repro.train import make_train_state, make_train_step

    job = J.Job({"load_reference": lambda name: importlib.import_module(
        f"bench.reference.{name}")}, entry, mix)
    init = TR.make_params(job.ref, job.m)
    plans = []

    def build_state(wkey, skey):
        state, plan = make_train_state(job.tc, skey, params=init(wkey))
        plans.append(plan)
        return state

    state = jax.eval_shape(build_state, *J.keys(0))
    batch = {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32)
             for k in ("tokens", "labels")}
    raw = make_train_step(job.tc, plans[0], donate=True)
    step = jax.jit(raw, donate_argnums=raw.donate_argnums)
    return step.lower(state, batch).compile().as_text()


def signature(text: str) -> str:
    """`%name = shape opcode` of an instruction's text: what the trace's op
    name and the compiled module's text both print alike (the trace also
    prints operand shapes, the module's text does not)."""
    name, _, rest = text.partition(" = ")
    depth = 0
    for i, ch in enumerate(rest):   # a tuple shape holds spaces and parens
        depth += (ch == "(") - (ch == ")")
        if ch == " " and depth == 0:
            return f"{name} = {rest[:i]} {rest[i + 1:].split('(', 1)[0]}"
    return text


def instructions(text: str) -> dict:
    """{instruction name: (its signature, its op_name or "")} of an HLO
    module's text."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            body = m.group(1)
            op = _OP_NAME.search(body)
            out[body.split(" ", 1)[0]] = (signature(body),
                                          op.group(1) if op else "")
    return out


def attach_scopes(events: list, text: str) -> tuple:
    """Sets each device op's `scope` to the op_name of the program's
    instruction whose signature the op's name carries ("" where none
    does); returns the share of the device ops that carry one, and the
    names of the first 3 that do not."""
    table = instructions(text)
    ops, missed = 0, []
    for e in events:
        if not (TRD.DEVICE_PLANE.match(e["plane"]) and e["line"] == "XLA Ops"):
            continue
        ops += 1
        sig, op_name = table.get(e["name"].split(" ", 1)[0], ("", ""))
        if sig and signature(e["name"]) == sig:
            e["scope"] = op_name
        else:
            e["scope"] = ""
            missed.append(e["name"])
    return (1 - len(missed) / ops if ops else 0.0), missed[:3]


def components(scope: str) -> set:
    """The scope path's components, `jvp(...)` and `transpose(...)`
    unwrapped: the forward and the backward of a scope both count."""
    out = set()
    for c in scope.split("/"):
        while (m := _WRAPPED.match(c)):
            c = m.group(1)
        out.add(c)
    return out


def self_times(ops: list, lo: int, hi: int) -> list:
    """(op, self ns) for the ops of one device line: each op's duration
    clipped to [lo, hi) less the union of the clipped ops nested wholly
    inside it (a `while` holds its body's ops)."""
    ops = sorted(ops, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    kids = [[] for _ in ops]
    open_ = []
    for i, e in enumerate(ops):
        end = e["start_ns"] + e["dur_ns"]
        while open_ and (ops[open_[-1]]["start_ns"]
                         + ops[open_[-1]]["dur_ns"]) < end:
            open_.pop()
        iv = TRD._clip(e, lo, hi)
        if open_ and iv is not None:
            kids[open_[-1]].append(iv)
        open_.append(i)
    out = []
    for e, inner in zip(ops, kids):
        iv = TRD._clip(e, lo, hi)
        if iv is not None:
            out.append((e, iv[1] - iv[0] - TRD._union(inner)))
    return out


def scopes(events: list, window_ns: tuple, names) -> dict:
    """Self time of the device ops inside window_ns, by their `scope`.

    Returns scope_s {name: seconds of the ops whose path holds the name},
    unscoped_s (ops whose path holds none of `names`), busy_s (union of op
    intervals, as trace_reduce.reduce counts it) and unscoped_ops (the 5
    op families with most unscoped self time); seconds are averaged over
    the devices."""
    lo, hi = window_ns
    lines = {}
    for e in events:
        if TRD.DEVICE_PLANE.match(e["plane"]) and e["line"] == "XLA Ops":
            lines.setdefault((e["plane"], e["line"]), []).append(e)
    n = max(1, len({p for p, _l in lines}))
    per = {k: 0.0 for k in names}
    unscoped, busy, fam, hits = 0.0, 0.0, {}, {}
    for ops in lines.values():
        busy += TRD._union([iv for iv in (TRD._clip(e, lo, hi) for e in ops)
                            if iv is not None])
        for e, t in self_times(ops, lo, hi):
            if e["scope"] not in hits:
                comps = components(e["scope"])
                hits[e["scope"]] = [k for k in names if k in comps]
            hit = hits[e["scope"]]
            for k in hit:
                per[k] += t
            if not hit:
                unscoped += t
                f = TRD._op_family(e["name"].split(" ", 1)[0].lstrip("%"))
                fam[f] = fam.get(f, 0.0) + t
    top = sorted(fam.items(), key=lambda kv: -kv[1])[:5]
    return {"scope_s": {k: v / n / 1e9 for k, v in per.items()},
            "unscoped_s": unscoped / n / 1e9, "busy_s": busy / n / 1e9,
            "unscoped_ops": [[k, v / n / 1e9] for k, v in top]}


def _log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _read(path: str, window_s: float, steps: float):
    """scopes() of the trace at `path` if its bench_window span is
    `window_s` long and its ops are the compiled step's, else None."""
    t0 = time.perf_counter()
    events = TRD.load_events(os.path.dirname(path))
    span = TRD.host_span(events, "bench_window")
    if span is None or (span[1] - span[0]) / 1e9 != window_s:
        return None
    from bench import run as R
    cell = os.path.relpath(path, os.path.join(ROOT, ".bench_trace")).split(
        os.sep)[0]
    _c, _conf, entry, mix = R.cell_files(R.load_json("BENCHMARK.json"), cell)
    t1 = time.perf_counter()
    share, missed = attach_scopes(events, program_text(entry, mix))
    t2 = time.perf_counter()
    _log(f"scopes: {len(events)} events read in {t1 - t0:.1f} s; the step "
         f"compiled again in {t2 - t1:.1f} s; {share:.6f} of the device ops "
         f"carry an instruction of it; not: {missed}")
    if share < MATCHED:
        return None
    got = scopes(events, span, NAMES)
    top = sum(got["scope_s"][k] for k in TOP) + got["unscoped_s"]
    covered = 1 - got["unscoped_s"] / got["busy_s"] if got["busy_s"] else 0.0
    _log(f"scopes (device s over {steps} traced steps, self time): "
         f"{got['scope_s']}; unscoped {got['unscoped_s']:.6f} in "
         f"{got['unscoped_ops']}; busy {got['busy_s']:.6f}; covered share "
         f"{covered:.6f}; top-level scopes + unscoped {top:.6f}")
    return got


_READ = {}


def traced(ctx):
    """scopes() of the traced window this run just wrote, or None. The
    trace is the newest under .bench_trace/, taken only if its
    bench_window span is the window the run's own reduction read and its
    ops are the compiled step's."""
    tr = ctx.get("trace")
    if not tr or not tr.get("steps") or not tr.get("window_s"):
        return None
    paths = glob.glob(os.path.join(ROOT, ".bench_trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path), tr["window_s"])
    if key not in _READ:
        _READ.clear()
        try:
            _READ[key] = _read(path, tr["window_s"], tr["steps"])
        except Exception as e:  # a reader reports nothing, never fails the run
            _log(f"scopes: not read: {type(e).__name__}: {e}")
            _READ[key] = None
    return _READ[key]


def step_ms(ctx, names) -> float | None:
    """Device ms per traced step of the ops under any of `names`; None
    where the trace holds no such scope (a program without them)."""
    got = traced(ctx)
    if got is None:
        return None
    spent = sum(got["scope_s"][k] for k in names)
    if spent <= 0:
        return None
    return 1000.0 * spent / ctx["trace"]["steps"]
