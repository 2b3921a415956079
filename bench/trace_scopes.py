"""Device time of the train step by program scope, from the profiler trace
of a traced run.

The program names its layers with `jax.named_scope` (`embed`,
`frozen_layers`, `trainable_layers`, `token_mix`, `channel_mix`,
`head_loss`, `reselect`, `update`, and whatever scopes a later program
adds); XLA keeps each op's scope path in the `op_name` metadata of its HLO
instruction, e.g.
`jit(train_step)/transpose(jvp(trainable_layers))/while/body/.../token_mix/dot_general`.
The TPU trace names each device op by its instruction's text
(`%fusion.632 = bf16[2,2048,24576]{...} fusion(...), ...`) without that
metadata, so the op names come from the compiled program: the job puts the
text of the step it ran (`compiled.as_text()`) in the readers' context as
`program_text`, and `attach_scopes(events, text)` gives each device op the
`op_name` of the instruction whose name, result shape and opcode it
carries.

`op_times(events, window_ns)` takes each device op's self time;
`tally(times, names)` gives it to every scope in the op's path that is
among `names`. `step_ms(ctx, names)` is what the per-layer readers call:
device ms per traced step under any of `names`, whatever the names, from
the trace the run left in `.bench_trace/<cell>/`.
"""
from __future__ import annotations

import glob
import os
import re
import sys
import time

from bench import trace_reduce as TRD

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the train step's scopes that every traced run logs; a reader may ask
# step_ms for these or any other scope name
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


def op_times(events: list, window_ns: tuple) -> dict:
    """The device ops inside window_ns with their self time: ops [(scope,
    op name, self ns)] in the order of their device lines, busy_ns (union
    of op intervals, summed over the devices, as trace_reduce.reduce
    counts it) and the number of devices."""
    lo, hi = window_ns
    lines = {}
    for e in events:
        if TRD.DEVICE_PLANE.match(e["plane"]) and e["line"] == "XLA Ops":
            lines.setdefault((e["plane"], e["line"]), []).append(e)
    ops, busy = [], 0.0
    for line in lines.values():
        busy += TRD._union([iv for iv in (TRD._clip(e, lo, hi) for e in line)
                            if iv is not None])
        ops += [(e["scope"], e["name"], t)
                for e, t in TRD.self_times(line, lo, hi)]
    return {"ops": ops, "busy_ns": busy,
            "devices": max(1, len({p for p, _l in lines}))}


def tally(times: dict, names) -> dict:
    """Self time by scope, of op_times' ops.

    Returns scope_s {name: seconds of the ops whose path holds the name},
    unscoped_s (ops whose path holds none of `names`), busy_s and
    unscoped_ops (the 5 op families with most unscoped self time); seconds
    are averaged over the devices."""
    n = times["devices"]
    per = {k: 0.0 for k in names}
    unscoped, fam, hits = 0.0, {}, {}
    for scope, name, t in times["ops"]:
        if scope not in hits:
            comps = components(scope)
            hits[scope] = [k for k in names if k in comps]
        hit = hits[scope]
        for k in hit:
            per[k] += t
        if not hit:
            unscoped += t
            f = TRD._op_family(name.split(" ", 1)[0].lstrip("%"))
            fam[f] = fam.get(f, 0.0) + t
    top = sorted(fam.items(), key=lambda kv: -kv[1])[:5]
    return {"scope_s": {k: v / n / 1e9 for k, v in per.items()},
            "unscoped_s": unscoped / n / 1e9,
            "busy_s": times["busy_ns"] / n / 1e9,
            "unscoped_ops": [[k, v / n / 1e9] for k, v in top]}


def _log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _read(path: str, window_s: float, steps: float, text: str):
    """op_times() of the trace at `path` if its bench_window span is
    `window_s` long and its ops are those of the program whose text is
    `text`, else None."""
    t0 = time.perf_counter()
    events = TRD.load_events(os.path.dirname(path))
    span = TRD.host_span(events, "bench_window")
    if span is None or (span[1] - span[0]) / 1e9 != window_s:
        return None
    t1 = time.perf_counter()
    share, missed = attach_scopes(events, text)
    t2 = time.perf_counter()
    _log(f"scopes: {len(events)} events read in {t1 - t0:.1f} s; matched "
         f"to the run's own compiled step in {t2 - t1:.1f} s (no second "
         f"compile); {share:.6f} of the device ops carry an instruction of "
         f"it; not: {missed}")
    if share < MATCHED:
        return None
    times = op_times(events, span)
    got = tally(times, NAMES)
    top = sum(got["scope_s"][k] for k in TOP) + got["unscoped_s"]
    covered = 1 - got["unscoped_s"] / got["busy_s"] if got["busy_s"] else 0.0
    _log(f"scopes (device s over {steps} traced steps, self time): "
         f"{got['scope_s']}; unscoped {got['unscoped_s']:.6f} in "
         f"{got['unscoped_ops']}; busy {got['busy_s']:.6f}; covered share "
         f"{covered:.6f}; top-level scopes + unscoped {top:.6f}")
    return times


_READ = {}


def traced(ctx):
    """op_times() of the traced window this run just wrote, or None. The
    trace is the newest under .bench_trace/, taken only if its
    bench_window span is the window the run's own reduction read and its
    ops are those of the compiled step whose text the job handed over."""
    tr = ctx.get("trace")
    text = ctx.get("program_text")
    if not tr or not tr.get("steps") or not tr.get("window_s") or not text:
        return None
    paths = glob.glob(os.path.join(ROOT, ".bench_trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path), tr["window_s"], hash(text))
    if key not in _READ:
        _READ.clear()
        try:
            _READ[key] = _read(path, tr["window_s"], tr["steps"], text)
        except Exception as e:  # a reader reports nothing, never fails the run
            _log(f"scopes: not read: {type(e).__name__}: {e}")
            _READ[key] = None
    return _READ[key]


def step_ms(ctx, names) -> float | None:
    """Device ms per traced step of the ops under any of `names`, which may
    be any scope names of the program; None where the trace holds no such
    scope."""
    times = traced(ctx)
    if times is None:
        return None
    got = tally(times, names)
    spent = sum(got["scope_s"][k] for k in names)
    if spent <= 0:
        return None
    return 1000.0 * spent / ctx["trace"]["steps"]
