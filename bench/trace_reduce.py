"""Reduction of a profiler trace to device busy and idle time, per-kernel
and per-step device time, and the breakdown of the longest idle gaps.

`load_events(path)` reads the `.xplane.pb` that `jax.profiler` writes into
plain event dicts; `reduce(events, ...)` works on those alone, so the test
checks it on a small recorded event file.

Event dict: {"plane", "line", "name", "start_ns", "dur_ns", "text"} where
`text` joins the event's string stats (HLO op and kernel names live there).
Device ops are the events of the "XLA Ops" line of a `/device:TPU:<n>`
plane; programs are those of its "XLA Modules" line; host spans are the
events of every line of a `/host:CPU` plane.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def start(trace_dir: str):
    """Start the profiler without its Python function tracer (which would
    slow the host loop being measured); host spans come from the
    benchmark's own annotations."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def load_events(trace_dir: str) -> list:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return []
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev and not is_host(plane.name):
            continue
        for line in plane.lines:
            if dev and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for e in line.events:
                text = " ".join(str(v) for _k, v in e.stats
                                if isinstance(v, str))
                events.append({"plane": plane.name, "line": line.name,
                               "name": e.name, "start_ns": e.start_ns,
                               "dur_ns": e.duration_ns, "text": text})
    return events


def is_host(plane: str) -> bool:
    """A host plane: its lines are the process's threads, and the
    benchmark's TraceAnnotation spans lie on whichever thread opened them."""
    return plane.startswith("/host:CPU")


def _union(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _clip(e, lo, hi):
    s = max(e["start_ns"], lo)
    t = min(e["start_ns"] + e["dur_ns"], hi)
    return (s, t) if t > s else None


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
        iv = _clip(e, lo, hi)
        if open_ and iv is not None:
            kids[open_[-1]].append(iv)
        open_.append(i)
    out = []
    for e, inner in zip(ops, kids):
        iv = _clip(e, lo, hi)
        if iv is not None:
            out.append((e, iv[1] - iv[0] - _union(inner)))
    return out


def _op_family(name: str) -> str:
    return re.sub(r"[.\-_]\d+$", "", name)


def reduce(events: list, window_ns: tuple, kernels: list,
           step_module: str) -> dict:
    """Device time inside window_ns = (start, end) on the trace's clock.

    kernels: names of kernels to time; an op belongs to a kernel when the
    kernel's name appears in the op's name or string stats.
    step_module: a substring of the step program's module name.

    Returns busy_s (union of device op intervals, averaged over devices),
    window_s, kernel_s {kernel: seconds summed over devices}, steps (step
    programs that ran, summed over devices / devices), step_s (their mean
    device duration), device_ops (the 10 op families with most self time:
    an op's time less that of the ops nested inside it, so a `while` does
    not count its body again), and idle_gaps (the 10 longest gaps with the
    host's innermost span in them).
    """
    lo, hi = window_ns
    devices = sorted({e["plane"] for e in events
                      if DEVICE_PLANE.match(e["plane"])})
    busy, kern, fam = 0.0, {k: 0.0 for k in kernels}, {}
    step_durs, gaps = [], []
    for dev in devices:
        ops, dev_ops = [], []
        for e in events:
            if e["plane"] != dev:
                continue
            iv = _clip(e, lo, hi)
            if e["line"] == "XLA Modules":
                if step_module in e["name"] and iv is not None:
                    step_durs.append(e["dur_ns"])
                continue
            if iv is None:
                continue
            ops.append(iv)
            dev_ops.append(e)
            d = iv[1] - iv[0]
            for k in kernels:
                if k in e["name"] or re.search(rf"\b{re.escape(k)}\b",
                                               e["text"]):
                    kern[k] += d
                    break
        busy += _union(ops)
        for e, t in self_times(dev_ops, lo, hi):
            f = _op_family(e["name"])
            fam[f] = fam.get(f, 0.0) + t
        end = lo
        for s, t in sorted(ops):
            if s > end:
                gaps.append((s - end, end, s))
            end = max(end, t)
        if hi > end:
            gaps.append((hi - end, end, hi))
    n = max(1, len(devices))
    host = [e for e in events if is_host(e["plane"])]
    gaps.sort(reverse=True)
    idle = []
    for d, s, t in gaps[:10]:
        mid = (s + t) / 2
        inner = [e for e in host
                 if e["start_ns"] <= mid <= e["start_ns"] + e["dur_ns"]]
        label = min(inner, key=lambda e: e["dur_ns"])["name"] if inner \
            else "host outside the benchmark's spans"
        idle.append([label, d / 1e9])
    ops_top = sorted(fam.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy / n / 1e9, "window_s": (hi - lo) / 1e9,
            "devices": len(devices),
            "kernel_s": {k: v / 1e9 for k, v in kern.items()},
            "steps": len(step_durs) / n,
            "step_s": (sum(step_durs) / len(step_durs) / 1e9
                       if step_durs else None),
            "device_ops": [[k, v / 1e9 / n] for k, v in ops_top],
            "idle_gaps": idle}


def host_span(events: list, name: str):
    """(start_ns, end_ns) of the first host event called `name`."""
    for e in events:
        if is_host(e["plane"]) and e["name"] == name:
            return e["start_ns"], e["start_ns"] + e["dur_ns"]
    return None

