"""Readings that set a cell's limits (bench/limits/<cell>.json), on the
chip at the cell's own size. The benchmark's runs do not run this.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 13 \
        [--control 3] [--out calibrate.jsonl]

Training cells, for each seed: the program through the cell's checked
steps (set-up as a run makes it, no window), then the plain reference in
float32. Prints the program's numbers (loss_gap, grad_gap, change_gap)
against the reference: the lower readings. On the first `--control` seeds
it also puts in the program's place
  - the control: the reference with every matmul in scaled fp8 (e4m3), the
    precision below the configuration's bfloat16;
  - the planted fault "half of the batch left out": the reference on the
    first half of each batch's rows, the mean taken over those;
and prints their numbers: the upper readings. The fault "state returned
unchanged" reads 1 on change_gap by construction and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import run as R  # noqa: E402


def readings_for_seed(job, seed: int, control: bool) -> dict:
    from bench import check
    job.build(seed)
    prog, _ = job.checked_steps()
    job.free()
    t = time.perf_counter()
    ref = job.reference("f32")
    out = {"seed": seed, "program": check.readings(
        job.program_readings(prog, ref["w0"]), ref),
        "program_losses": prog["losses"], "ref_losses": ref["losses"],
        "reference_s": time.perf_counter() - t}
    if prog["w1"] is not None:
        # SGD reads its first gradient from the weights' first change: the
        # share of each leaf's weights that the first step moved
        out["moved_share"] = {k: float((v != ref["w0"][k]).mean())
                              for k, v in prog["w1"].items()}
    if control:
        for name, kw in (("fp8", {"mode": "fp8"}),
                         ("half_batch", {"rows": job.mix["batch"] // 2})):
            other = job.reference(**kw)
            out[name] = check.readings(other, ref)
            del other
            gc.collect()
    del ref
    gc.collect()
    return out


def main(argv=None, *, require_tpu: bool = True, overrides=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--lr", type=float, default=None,
                    help="the mix's learning rate in place of its own")
    args = ap.parse_args(argv)
    bench = R.load_json("BENCHMARK.json")
    peaks = R.load_json("bench", "peaks.json")
    cell, _conf, entry, mix = R.cell_files(bench, args.workload)
    overrides = overrides or {}
    entry = overrides.get("entry", entry)
    mix = overrides.get("mix", mix)
    if args.lr is not None:
        mix = dict(mix, optimizer=dict(mix["optimizer"],
                                       learning_rate=args.lr))
    if require_tpu:
        R.device_info(cell["chips"], peaks)
        R.enable_cache()
    import importlib
    kind = importlib.import_module(f"bench.jobs.{mix['kind']}")
    ctx = {"t0": R.T0, "log": R.log, "load_reference": lambda n:
           importlib.import_module(f"bench.reference.{n}")}
    job = kind.Job(ctx, entry, mix)
    results = []
    for i, seed in enumerate(args.seeds):
        res = readings_for_seed(job, seed, i < args.control)
        results.append(res)
        line = json.dumps(res)
        print(line, flush=True)
        if args.out:
            with open(os.path.join(R.ROOT, args.out), "a") as f:
                f.write(line + "\n")
    return results


if __name__ == "__main__":
    main()
