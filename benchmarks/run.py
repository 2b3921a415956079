# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark runner — one module per paper table/figure:

    table2_evaluation  Paper Table II (No-FT/Last/Full/Fixed/Dynamic + memory)
    fig2_layer_depth   Paper Fig. 2  (more later layers @ same budget wins)
    fig4_weights_updated Paper Fig. 4 (coverage: dynamic >> fixed; ~2%/iter)
    pruning_table      Paper §IV-B   (channel/pattern sparsity, FLOPs)
    memory_table       Paper's 98% feature-memory claim, per-arch
    kernel_micro       Pallas kernel oracles + fused-vs-loop + skip ratios

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Modules that expose a BENCH_JSON name and a RECORDS list (kernel_micro ->
BENCH_kernels.json) additionally get their machine-readable records dumped
to that file at the repo root, so the perf trajectory is tracked across PRs.
"""
import argparse
import importlib
import json
import pathlib
import sys
import traceback

_ROOT = pathlib.Path(__file__).resolve().parent.parent

MODULES = [
    "fig4_weights_updated",
    "pruning_table",
    "memory_table",
    "kernel_micro",
    "fig2_layer_depth",
    "table2_evaluation",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    mods = [args.only] if args.only else MODULES
    print("name,us_per_call,derived")
    failed = 0
    for name in mods:
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            for row in mod.run():
                print(",".join(str(x) for x in row), flush=True)
            json_name = getattr(mod, "BENCH_JSON", None)
            records = getattr(mod, "RECORDS", None)
            if json_name and records:
                path = _ROOT / json_name
                path.write_text(json.dumps(records, indent=1) + "\n")
                print(f"# wrote {path}", file=sys.stderr, flush=True)
        except Exception:  # noqa: BLE001
            failed += 1
            traceback.print_exc()
            print(f"{name},0,ERROR", flush=True)
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
