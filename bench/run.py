"""Runs one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell of BENCHMARK.json by name, its configuration
(bench/configs/<config>.json) and traffic mix (bench/traffic/<mix>.json),
runs the job of the mix's kind (bench/jobs/<kind>.py), and prints one JSON
object as the last line of standard output. `--trace 0` reports the cell's
end-to-end metrics; `--trace 1` its per-layer metrics, each read by its own
reader (bench/metrics/<metric>.py) from the run's counters and a profiler
trace of a few extra steps after the window.

It exits non-zero, and prints no result, when JAX finds no TPU, fewer
chips than the cell asks for, or a device kind that bench/peaks.json does
not list. The compilation cache lives in <checkout>/.jax_cache.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file of the benchmark by path (metric names hold dots)."""
    name = "bench_" + path.replace("/", "_").replace(".", "_").replace("-",
                                                                       "_")
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def finite(x):
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300 if x > 0 else -1e300
    return x


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def cell_files(bench: dict, workload: str):
    """(cell, config entry of BENCHMARK.json, config file, mix) by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    entry = load_json(conf["file"])
    mix = load_json("bench", "traffic", cell["traffic"] + ".json")
    return cell, conf, entry, mix


def device_info(chips: int, peaks: dict) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"platform={d.platform} kind={d.device_kind} count={len(devs)} "
        f"jax={jax.__version__}")
    if d.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform {d.platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    if d.device_kind not in peaks["devices"]:
        raise SystemExit(f"bench: device kind {d.device_kind!r} is not in "
                         f"bench/peaks.json")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def enable_cache():
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def compile_counter():
    import jax
    count = [0]

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return lambda: count[0]


def peak_reader(chips: int):
    import jax

    def read():
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[:chips]]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None
    return read


def layer_metrics(bench: dict, cell: dict, ctx: dict) -> dict:
    """Each per-layer metric the cell lists, from its own reader; a reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for spec in bench["per_layer"]:
        if "workloads" in spec and cell["name"] not in spec["workloads"]:
            continue
        reader = load_module(f"bench/metrics/{spec['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def main(argv=None, *, require_tpu: bool = True, overrides=None) -> dict:
    """Runs the cell; returns the result dict (also printed). `overrides`
    and `require_tpu=False` exist for the CPU tests, which run a cell at a
    tiny size: {"cell", "entry", "mix", "limits", "fault"}."""
    args = parse(argv)
    bench = load_json("BENCHMARK.json")
    peaks = load_json("bench", "peaks.json")
    overrides = overrides or {}
    if "cell" in overrides:
        cell = overrides["cell"]
        entry, mix = overrides["entry"], overrides["mix"]
    else:
        cell, _conf, entry, mix = cell_files(bench, args.workload)
        entry = overrides.get("entry", entry)
        mix = overrides.get("mix", mix)
    limits_path = os.path.join(ROOT, "bench", "limits", cell["name"] + ".json")
    limits = overrides.get("limits") or (
        load_json("bench", "limits", cell["name"] + ".json")
        if os.path.exists(limits_path) else {})

    import jax
    if require_tpu:
        device = device_info(cell["chips"], peaks)
        peak = peaks["devices"][device["kind"]]
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": cell["chips"]}
        peak = next(iter(peaks["devices"].values()))
    if require_tpu:
        enable_cache()
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = {"t0": T0, "log": log, "limits": limits, "chips": cell["chips"],
           "compiles": compile_counter(), "peak_bytes": peak_reader(
               cell["chips"]), "trace_dir": trace_dir, "peak": peak,
           "fault": overrides.get("fault"),
           "load_reference": lambda name: importlib.import_module(
               f"bench.reference.{name}"),
           "kernel_costs": {}}
    for k in sorted(os.listdir(os.path.join(ROOT, "bench", "kernels"))):
        if k.endswith(".py"):
            ctx["kernel_costs"][k[:-3]] = load_module(f"bench/kernels/{k}")
    job = importlib.import_module(f"bench.jobs.{mix['kind']}")
    res = job.run(ctx, entry, mix, args.seed, args.seconds,
                  bool(args.trace))

    # read by the job after the window, before the reference ran
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    lc = dict(res["layer_ctx"], peak=peak, chips=cell["chips"],
              kernel_costs=ctx["kernel_costs"])
    if args.trace:
        metrics = layer_metrics(bench, cell, lc)
        tr = lc.get("trace") or {}
        device["busy_s"] = tr.get("busy_s")
        device["window_s"] = tr.get("window_s")
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {}
        for spec in bench["end_to_end"]:
            if "workloads" in spec and cell["name"] not in spec["workloads"]:
                continue
            v = res["end_to_end"].get(spec["name"])
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": units[
                    spec["name"]]}
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and lc.get("trace"):
        out["breakdown"] = {"device_ops": lc["trace"]["device_ops"],
                            "idle_gaps": lc["trace"]["idle_gaps"]}
    out["checks"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                     for k, v in res["checks"].items()}
    for k, v in out["checks"].items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
