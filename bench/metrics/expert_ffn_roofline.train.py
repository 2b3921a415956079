"""expert_ffn_roofline.train: the held experts' work against its roofline.

Least time per step: the held experts' SwiGLU matmuls over the rows the
dropless dispatch hands them (the cell reference's `expert_rows` per held
expert) — forward in every frozen expert layer; forward, recompute and the
input gradient in every trainable one — each the larger of its FLOPs over
the bf16 peak and its bytes (weights, input and output rows) over the HBM
bandwidth, plus the least time of each `batched_dw` call of the step
(bench/kernels/batched_dw.py), which also runs under the scope. That over
the device time per step of the ops under the program's `experts` scope.

The reader is handed no cell name: it takes the cell of BENCHMARK.json
whose configuration and traffic give the run's own FLOPs per token and
sparse-update kernel calls (`flops_per_token`, `kernel_calls` in ctx), and
reads nothing where no cell does (a cell cut to another size).

`expert_rows` is the mean over the routed experts: where the seed's held
experts draw fewer rows than that (the step's `moe_rows_held`, which the
reader cannot see), the least time counts rows that did not run and the
share reads high by as much."""
import importlib
import json
import os

from bench import trace_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _cell(ctx):
    """(model, traffic mix, reference module) of the cell whose FLOPs per
    token and kernel calls are the run's, or None."""
    from bench.jobs.train import kernel_calls
    if ctx.get("flops_per_token") is None:
        return None
    bench = _load("BENCHMARK.json")
    confs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        entry = _load(confs[cell["config"]]["file"])
        m = entry["model"]
        mix = _load("bench", "traffic", cell["traffic"] + ".json")
        if "moe" not in m or mix.get("kind") != "train":
            continue
        ref = importlib.import_module(f"bench.reference.{entry['reference']}")
        fl = ref.flops_per_token(m, mix["seq"], mix["update_layers"],
                                 mix["update_ratio"], mix["channel_block"])
        if fl["total"] == ctx["flops_per_token"] and [
                list(c) for c in kernel_calls(ref, m, mix)] == [
                list(c) for c in ctx.get("kernel_calls") or []]:
            return m, mix, ref
    return None


def least_s(m, mix, ref, peak, kernel_calls, costs) -> float:
    """Least device seconds per step of the work under `experts`."""
    moe = m["moe"]
    d, ff, e = m["d_model"], m["d_ff"], moe["num_experts"]
    rows = e * ref.expert_rows(m, mix)
    isz = 2 if m["dtype"] == "bfloat16" else 4
    k_train = mix["update_layers"]
    frozen = sum(n for _name, n in ref.segments(m)
                 if _name == "blocks") - k_train

    def matmul(k, n):
        flops = 2 * rows * k * n
        nbytes = (e * k * n + rows * (k + n)) * isz
        return max(flops / peak["bf16_flops_per_s"],
                   nbytes / peak["hbm_bytes_per_s"])

    swiglu = 2 * matmul(d, ff) + matmul(ff, d)
    dx = matmul(d, ff) + 2 * matmul(ff, d)
    total = frozen * swiglu + k_train * (2 * swiglu + dx)
    for kernel, call in kernel_calls:
        if kernel == "batched_dw":
            flops, nbytes = costs["batched_dw"].cost(call)
            total += max(flops / peak["bf16_flops_per_s"],
                         nbytes / peak["hbm_bytes_per_s"])
    return total


def read(ctx):
    spent = trace_scopes.step_ms(ctx, ("experts",))
    if spent is None or not ctx.get("peak"):
        return None
    cell = _cell(ctx)
    if cell is None:
        return None
    least = least_s(*cell, ctx["peak"], ctx.get("kernel_calls") or [],
                    ctx["kernel_costs"])
    return 100.0 * least / (spent / 1000.0)
