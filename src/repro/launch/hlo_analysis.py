"""Post-SPMD HLO analysis: collective-byte accounting with while-loop
trip-count multiplication.

`compiled.cost_analysis()` counts while bodies ONCE (verified empirically),
and our layer stacks are lax.scan loops — so naive summation undercounts
per-layer collectives by the layer count. This module parses
`compiled.as_text()` into computations, resolves each while loop's trip
count from its condition computation (compare-with-constant), and walks the
call graph from ENTRY multiplying byte counts through the loop nest.

Collectives counted: all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute (+ their async -start forms; -done forms are skipped).
Bytes = sum of operand sizes (the data each device injects into the
interconnect for that op).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Optional

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COMP_START_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?\s*->.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class _Instr:
    name: str
    op: str
    result_bytes: int
    operands: list[str]
    body: Optional[str] = None       # while body computation
    cond: Optional[str] = None       # while condition computation
    called: list[str] = field(default_factory=list)


@dataclass
class _Computation:
    name: str
    instrs: dict[str, _Instr] = field(default_factory=dict)
    trip_const: Optional[int] = None   # if this is a while condition


_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _split_type_op(rhs: str):
    """Split '<type> <op>(<args>...' into (type_str, op, args). Handles
    tuple types with nested parens/brackets and index comments."""
    rhs = _COMMENT_RE.sub("", rhs).strip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    type_str = rhs[: i + 1]
                    rest = rhs[i + 1:].strip()
                    break
        else:
            return None
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        type_str = rhs[:sp]
        rest = rhs[sp + 1:].strip()
    m = re.match(r"([\w\-]+)\((.*)$", rest, re.S)
    if not m:
        return None
    return type_str, m.group(1), m.group(2)
_CALL_ATTR_RE = re.compile(r"(?:condition|body|to_apply|branch_computations)="
                           r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_OPERAND_RE = re.compile(r"%?([\w.\-]+)")


def parse_hlo(text: str) -> dict[str, _Computation]:
    comps: dict[str, _Computation] = {}
    cur: Optional[_Computation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if stripped.endswith("{") and ("->" in stripped or stripped.startswith("ENTRY")):
            m = _COMP_START_RE.match(stripped)
            name = None
            if m:
                name = m.group(1)
            else:  # e.g. "ENTRY %main.123 (args) -> type {"
                m2 = re.search(r"%([\w.\-]+)", stripped)
                name = m2.group(1) if m2 else f"comp{len(comps)}"
            cur = _Computation(name=name)
            comps[name] = cur
            if stripped.startswith("ENTRY"):
                comps["__entry__"] = cur
            continue
        if stripped.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if not mi:
            continue
        name, rhs = mi.group(1), mi.group(2)
        mo = _split_type_op(rhs)
        if not mo:
            continue
        type_str, op, args = mo
        instr = _Instr(name=name, op=op, result_bytes=_shape_bytes(type_str),
                       operands=[])
        # operand names: first段 before any attr like ", dimensions="
        arg_main = args.split("), ")[0] if op == "while" else args
        head = re.split(r",\s*(?:channel_id|dimensions|replica_groups|"
                        r"source_target_pairs|to_apply|condition|body|"
                        r"sharding|slice|direction|use_global)", args)[0]
        for om in re.finditer(r"%([\w.\-]+)", head):
            instr.operands.append(om.group(1))
        if op == "while":
            mc = re.search(r"condition=%?([\w.\-]+)", args)
            mb = re.search(r"body=%?([\w.\-]+)", args)
            instr.cond = mc.group(1) if mc else None
            instr.body = mb.group(1) if mb else None
        else:
            for cm in _CALL_ATTR_RE.finditer(args):
                for cname in re.split(r",\s*", cm.group(1)):
                    instr.called.append(cname.lstrip("%"))
        if op == "constant":
            mcst = re.search(r"constant\((-?\d+)\)", rhs)
            if mcst and cur.trip_const is None:
                cur.trip_const = int(mcst.group(1))
        cur.instrs[name] = instr
    return comps


def _trip_count(comps, cond_name: Optional[str]) -> int:
    """Trip count from a scan-style condition (compare iter < constant)."""
    if cond_name is None or cond_name not in comps:
        return 1
    cond = comps[cond_name]
    # scan-style condition: compare(iter, constant(N)) direction=LT
    if cond.trip_const is not None and cond.trip_const > 0:
        return cond.trip_const
    return 1


def collective_bytes(text: str) -> dict:
    """Total per-device collective operand bytes, loop-trip corrected.

    Returns {"total": int, "by_op": {op: int}, "naive": int}."""
    comps = parse_hlo(text)
    entry = comps.get("__entry__")
    if entry is None:
        return {"total": 0, "by_op": {}, "naive": 0}

    by_op: dict[str, float] = {}
    naive = 0

    def comp_bytes(comp: _Computation, mult: float, seen: tuple) -> float:
        nonlocal naive
        if comp.name in seen:            # recursion guard
            return 0.0
        total = 0.0
        for instr in comp.instrs.values():
            opn = instr.op
            base = None
            for c in _COLLECTIVES:
                if opn == c or opn == c + "-start":
                    base = c
                    break
            if base is not None:
                b = 0
                for o in instr.operands:
                    src = comp.instrs.get(o)
                    if src is not None:
                        b += src.result_bytes
                if b == 0:               # operands unknown -> use result
                    b = instr.result_bytes
                # wire bytes per device (ring algorithms):
                #   all-reduce       ~ 2 x operand  (reduce-scatter + all-gather)
                #   all-gather       ~ result - operand (received bytes)
                #   reduce-scatter   ~ operand - result (sent bytes)
                #   all-to-all       ~ operand     (each device re-sends its shard)
                #   collective-permute ~ operand
                if base == "all-reduce":
                    w = 2 * b
                elif base == "all-gather":
                    w = max(instr.result_bytes - b, b)
                elif base == "reduce-scatter":
                    w = max(b - instr.result_bytes, instr.result_bytes)
                else:
                    w = b
                total += w * mult
                naive += w
                by_op[base] = by_op.get(base, 0.0) + w * mult
            if instr.op == "while" and instr.body in comps:
                trips = _trip_count(comps, instr.cond)
                total += comp_bytes(comps[instr.body], mult * trips,
                                    seen + (comp.name,))
            for cal in instr.called:
                if cal in comps:
                    total += comp_bytes(comps[cal], mult, seen + (comp.name,))
        return total

    total = comp_bytes(entry, 1.0, ())
    return {"total": int(total), "by_op": {k: int(v) for k, v in by_op.items()},
            "naive": int(naive)}


# ---------------------------------------------------------------------------
# zero-init scatter detection (compact-gradient path verification)
# ---------------------------------------------------------------------------
#
# The dense-scatter backward materializes each block-sparse dW by scattering
# the compact blocks into a ZERO buffer (`jnp.put_along_axis(zeros, ...)`),
# which lowers to a stablehlo.scatter whose operand is a broadcast zero
# constant. The compact path's only scatters write updated blocks into LIVE
# tensors (weights / optimizer state). `zero_init_scatters` finds the former
# in jax's StableHLO lowering text (`jax.jit(f).lower(...).as_text()`),
# resolving scatter operands one call level deep (jax outlines
# put_along_axis into private helper funcs whose operand arrives as an
# argument).

_SHLO_FUNC_RE = re.compile(r"func\.func\s+(?:private\s+)?@([\w.\-$]+)\((.*)$")
_SHLO_ZERO_RE = re.compile(
    r"(%[\w#]+)\s*=\s*stablehlo\.constant\s+dense<0(?:\.0*(?:e[+-]?\d+)?)?>")
_SHLO_PROP_RE = re.compile(
    r"(%[\w#]+)\s*=\s*stablehlo\.(?:broadcast_in_dim|reshape|convert|"
    r"transpose)\s+(%[\w#]+)")
_SHLO_SCATTER_RE = re.compile(r'"stablehlo\.scatter"\(([^)]*)\)')
_SHLO_CALL_RE = re.compile(r"=\s*call\s+@([\w.\-$]+)\(([^)]*)\)")
_SHLO_TENSOR_RE = re.compile(r"tensor<([0-9x]*)x?([a-z][\w]*)>")


def _shlo_tensor(type_str: str):
    m = _SHLO_TENSOR_RE.search(type_str)
    if not m:
        return None
    dims = tuple(int(d) for d in m.group(1).split("x") if d) \
        if m.group(1) else ()
    return dims, m.group(2)


def zero_init_scatters(text: str) -> list[dict]:
    """Scatters writing into zero-initialized operands in StableHLO `text`.

    Returns [{"shape": tuple, "dtype": str, "bytes": int, "func": str}] —
    one entry per static occurrence (loop trip counts not applied)."""
    funcs: dict[str, dict] = {}
    cur = None
    pending: list[str] = []            # operands of scatters awaiting types
    for line in text.splitlines():
        s = line.strip()
        fm = _SHLO_FUNC_RE.search(s)
        if fm:
            cur = {"zeros": set(), "scatters": [], "calls": []}
            funcs[fm.group(1)] = cur
            pending = []
            continue
        if cur is None:
            continue
        zm = _SHLO_ZERO_RE.match(s)
        if zm:
            cur["zeros"].add(zm.group(1))
            continue
        pm = _SHLO_PROP_RE.match(s)
        if pm and pm.group(2) in cur["zeros"]:
            cur["zeros"].add(pm.group(1))
            continue
        sm = _SHLO_SCATTER_RE.search(s)
        if sm:
            ops = [o.strip() for o in sm.group(1).split(",")]
            pending.append(ops[0] if ops else "")
            continue
        if pending and s.startswith("})"):
            # region close carries `: (operand_t, idx_t, upd_t) -> result_t`
            out = s.split("->")[-1]
            cur["scatters"].append((pending.pop(), _shlo_tensor(out)))
            continue
        cm = _SHLO_CALL_RE.search(s)
        if cm:
            args = [a.strip() for a in cm.group(2).split(",") if a.strip()]
            cur["calls"].append((cm.group(1), args))

    def rec(shape_dtype, fname):
        if shape_dtype is None:
            return None
        dims, dt = shape_dtype
        n = 1
        for d in dims:
            n *= d
        return {"shape": dims, "dtype": dt,
                "bytes": n * _DTYPE_BYTES.get(dt, 4), "func": fname}

    found = []
    wrappers: dict[str, tuple[int, tuple]] = {}   # func -> (arg idx, shape)
    for name, f in funcs.items():
        for operand, shape_dtype in f["scatters"]:
            if operand in f["zeros"]:
                r = rec(shape_dtype, name)
                if r:
                    found.append(r)
            elif operand.startswith("%arg"):
                try:
                    wrappers[name] = (int(operand[4:]), shape_dtype)
                except ValueError:
                    pass
    for name, f in funcs.items():
        for callee, args in f["calls"]:
            if callee not in wrappers:
                continue
            arg_idx, shape_dtype = wrappers[callee]
            if arg_idx < len(args) and args[arg_idx] in f["zeros"]:
                r = rec(shape_dtype, f"{name}->{callee}")
                if r:
                    found.append(r)
    return found


def weight_gradient_scatters(text: str, specs) -> list[dict]:
    """The subset of `zero_init_scatters(text)` whose shapes match a blocked
    selectable-weight layout — trailing dims (n_shards, n_blocks, block) of
    any SelSpec in `specs` (an iterable). An empty result certifies the
    module contains no full-shape gradient scatter for those weights."""
    sigs = {(sp.n_shards, sp.n_blocks, sp.block) for sp in specs}
    return [r for r in zero_init_scatters(text)
            if len(r["shape"]) >= 3 and tuple(r["shape"][-3:]) in sigs]


# ---------------------------------------------------------------------------
# kernel-launch counting (fused compact-path verification)
# ---------------------------------------------------------------------------
#
# PR 1's compact path issued one pallas_call per TP shard for the sparse dW
# and K x n_shards calls for the block writeback; the fused kernels (PR 3)
# must lower to a CONSTANT number of launch sites per selectable weight
# leaf. On TPU each pallas_call appears in the compiled HLO as a
# tpu_custom_call/Mosaic custom-call; on CPU (interpret mode) the kernel is
# inlined into plain HLO, so the detector also counts `pallas_call`
# equations directly in the jaxpr — backend-independent and what CI runs.

_KERNEL_CALL_RE = re.compile(
    r"custom[-_]call[^\n]*?(?:tpu_custom_call|mosaic|pallas)", re.I)


def _iter_sub_jaxprs(val):
    import jax.extend.core as jc
    if isinstance(val, jc.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jc.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _iter_sub_jaxprs(v)


def kernel_launch_count(obj) -> int:
    """Static Pallas/Mosaic kernel-launch sites in a lowered train step.

    `obj` is either compiled/lowered HLO text (counts tpu_custom_call /
    Mosaic / pallas custom-calls — the TPU path) or a jaxpr / ClosedJaxpr
    (counts `pallas_call` equations recursively through scan/while/pjit
    bodies — the backend-independent path CI uses, since interpret-mode
    lowering inlines kernels into plain HLO). Each site is one compiled
    kernel; a site inside a scan body launches once per trip but the count
    stays O(1) in the trip count — the fused compact path must show a
    constant number of sites per selectable weight leaf, not
    O(K x n_shards)."""
    if isinstance(obj, str):
        return len(_KERNEL_CALL_RE.findall(obj))
    return sum(kernel_launch_breakdown(obj).values())


def kernel_launch_breakdown(obj) -> dict[str, int]:
    """`kernel_launch_count` split by kernel name (jaxpr path only).

    Walks the same recursion as `kernel_launch_count` and keys each
    `pallas_call` site by the stable `name=` its kernel module passes
    (`masked_dw`, `batched_dw`, `fused_block_opt`, ...), so a test can
    certify the per-KERNEL launch budget of a lowered train step — e.g.
    the MoE compact step must show exactly one `batched_dw` site and one
    fused-optimizer site per expert-sharded leaf, independent of
    n_experts / K / n_shards."""
    jaxpr = getattr(obj, "jaxpr", obj)      # ClosedJaxpr -> Jaxpr
    out: dict[str, int] = {}

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params.get("name") or "pallas_call"
                out[name] = out.get(name, 0) + 1
                continue
            for val in eqn.params.values():
                for sub in _iter_sub_jaxprs(val):
                    walk(sub)

    walk(jaxpr)
    return out


# ---------------------------------------------------------------------------
# tensor-parallel replication audit (sharded serving verification)
# ---------------------------------------------------------------------------
#
# The serve mesh promises per-shard FLOPs ~1/N on every weight matmul whose
# sharded dim divides the mesh (models/decoding `paged_param_specs`). That
# property reverts SILENTLY: dropping a leaf's PartitionSpec makes the leaf
# arrive replicated inside the shard_map, the shape-based fallback in model
# code happily runs the full-size matmul on every shard, and tokens stay
# correct — only the FLOP saving is gone. The audit makes that revert loud:
# trace the sharded step, walk every sub-jaxpr (shard_map bodies carry LOCAL
# shapes), and flag any dot_general consuming an operand whose shape equals
# the FULL per-step shape of a leaf the sharding policy says must shard.
# `models/decoding.sharded_param_shapes` builds the forbidden set and the
# allowlist (policy-replicated leaves, e.g. indivisible rwkv head mats) from
# the same divisibility rules the spec builder uses.


def replicated_matmul_leaves(fn, args, forbidden_shapes) -> list[tuple]:
    """Shapes of dot_general operands in `fn(*args)`'s jaxpr that match a
    forbidden (full, unsharded) weight shape — empty means every policy-
    sharded matmul really ran on its local shard. Recurses through
    shard_map / scan / while / pjit / remat bodies."""
    import jax as _jax
    forbidden = {tuple(s) for s in forbidden_shapes}
    hits: list[tuple] = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                for v in eqn.invars:
                    shape = tuple(getattr(v.aval, "shape", ()))
                    if shape in forbidden:
                        hits.append(shape)
            for val in eqn.params.values():
                for sub in _iter_sub_jaxprs(val):
                    walk(sub)

    walk(_jax.make_jaxpr(fn)(*args).jaxpr)
    return hits


def while_trip_counts(text: str) -> list[int]:
    comps = parse_hlo(text)
    out = []
    for comp in comps.values():
        for instr in comp.instrs.values():
            if instr.op == "while":
                out.append(_trip_count(comps, instr.cond))
    return out
