import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import: jax locks device count at first init.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single --out experiments/dryrun

Outputs one JSON per cell into --out (default experiments/dryrun):
bytes-per-device (arguments/outputs/temps), HLO flops (body-once; see
hlo_analysis), trip-corrected collective bytes by op, and cell metadata.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, SHAPES, SparseUpdateConfig, cell_is_skipped,
                           get_config)
from repro.launch import hlo_analysis
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.specs import (input_specs, make_decode_cell, make_prefill_cell,
                                make_train_cell, rules_for)
from repro.sharding import use_rules


def _mem_dict(m) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        out[k] = int(getattr(m, k, 0) or 0)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             mode: str = "sparse", update_ratio: float = 0.2,
             donate: bool = True, mesh_shape: tuple | None = None) -> dict:
    """mesh_shape: optional (data, model) override over the same 256 chips —
    used by the §Perf hillclimb (TP degree tuning); the deliverable table
    always uses the assigned 16x16 / 2x16x16 meshes."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_is_skipped(arch, shape_name)
    mesh_name = ("2x16x16" if multi_pod else "16x16") if mesh_shape is None \
        else "x".join(map(str, mesh_shape))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "mode": mode,
           "kind": shape.kind}
    if skip:
        rec["status"] = "SKIP"
        rec["skip_reason"] = skip
        return rec

    import contextlib
    from repro.core.sparse_update import compact_allreduce
    cgr_ctx = compact_allreduce(True) if mode == "cgr" else contextlib.nullcontext()

    t0 = time.time()
    if mesh_shape is not None:
        mesh = make_debug_mesh(*mesh_shape)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(mesh, cfg, shape)
    with use_rules(rules), cgr_ctx:
        if shape.kind == "train":
            if mode in ("sparse", "cgr"):
                sparse = SparseUpdateConfig(update_ratio=update_ratio,
                                            num_update_layers=0 or _k(cfg),
                                            channel_block=128)
            else:
                sparse = SparseUpdateConfig(enabled=False)
            step_fn, state_abs, state_sh, batch_abs, batch_sh, plan = \
                make_train_cell(cfg, shape, rules, sparse=sparse)
            jitted = jax.jit(step_fn,
                             in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_abs, batch_abs)
            if mode in ("sparse", "cgr"):
                from repro.core.selection import selected_fraction
                rec["selected_param_fraction"] = selected_fraction(plan, cfg)
                rec["trainable_scan_steps"] = sum(plan.seg_trainable.values())
        elif shape.kind == "decode":
            step_fn, abs_args, shs = make_decode_cell(cfg, shape, rules)
            jitted = jax.jit(step_fn, in_shardings=shs,
                             out_shardings=(None, shs[2]),
                             donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(*abs_args)
        else:  # prefill
            step_fn, abs_args, shs = make_prefill_cell(cfg, shape, rules)
            jitted = jax.jit(step_fn, in_shardings=shs, out_shardings=None)
            lowered = jitted.lower(*abs_args)

        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1

        mem = compiled.memory_analysis()
        rec["memory"] = _mem_dict(mem)
        cost = compiled.cost_analysis() or {}
        rec["hlo_flops_body_once"] = float(cost.get("flops", 0.0))
        rec["hlo_bytes_body_once"] = float(cost.get("bytes accessed", 0.0))
        txt = compiled.as_text()
        rec["hlo_instruction_count"] = txt.count(" = ")
        coll = hlo_analysis.collective_bytes(txt)
        rec["collective_bytes_per_device"] = coll["total"]
        rec["collective_wire_bytes_per_device"] = coll["total"]
        rec["collective_bytes_by_op"] = coll["by_op"]
        rec["collective_bytes_naive"] = coll["naive"]
        rec["while_trip_counts"] = sorted(set(
            hlo_analysis.while_trip_counts(txt)))
        rec["num_devices"] = mesh.size
        rec["status"] = "OK"
    return rec


def _k(cfg) -> int:
    from repro.launch.specs import _default_k
    return _default_k(cfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--mode", choices=["sparse", "dense", "cgr"],
                    default="sparse")
    ap.add_argument("--update-ratio", type=float, default=0.2)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        for multi in meshes:
            tag = f"{arch}__{shape}__{'multi' if multi else 'single'}__{args.mode}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[dryrun] {tag}: cached")
                continue
            try:
                rec = run_cell(arch, shape, multi_pod=multi, mode=args.mode,
                               update_ratio=args.update_ratio)
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if multi else "16x16",
                       "mode": args.mode, "status": "FAIL",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                failures += 1
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            status = rec["status"]
            extra = ""
            if status == "OK":
                mb = rec["memory"]["argument_size_in_bytes"] / 2**20
                tmb = rec["memory"]["temp_size_in_bytes"] / 2**20
                extra = (f"args={mb:.0f}MiB temp={tmb:.0f}MiB "
                         f"coll={rec['collective_bytes_per_device']/2**20:.1f}MiB "
                         f"compile={rec['compile_s']:.0f}s")
            elif status == "FAIL":
                extra = rec["error"][:160]
            print(f"[dryrun] {tag}: {status} {extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
