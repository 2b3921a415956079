"""Serving launcher: thin CLI over the paged continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --requests 16 --batch 4 --prompt-len 32 --gen-len 16 \
        --page-size 16 --shared-prefix-len 16 --stream

Requests are admitted into fixed decode slots backed by a paged KV cache:
prompts chunk-prefill a page at a time (long admissions never stall
in-flight decodes), common prompt prefixes share refcounted pages
copy-on-write, and `--stream` prints tokens as they are sampled. Reported
request/token counts cover COMPLETED requests only — padded slots and
cancelled/timed-out requests are never counted. On the CPU this serves the
smoke configs; on a TPU the full configs, on one chip or, with
`--mesh-model N`, tensor-parallel over N chips. Parameters are built
straight into their serving layout, so no chip ever holds a whole
unsharded copy of a model that needs the mesh.
"""
from __future__ import annotations

import argparse
import functools

import jax

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import decoding as D
from repro.models import transformer as T
from repro.runtime.chaos import FaultSchedule
from repro.serve import PersonalizationConfig, ServeEngine
from repro.serve.engine import (make_branching_prefix_requests,
                                make_random_requests,
                                make_shared_prefix_requests)


def init_serve_params(cfg, key, rules=None):
    """Random parameters, initialized in one jitted program whose outputs
    land directly in the serving layout: sharded per `paged_param_specs`
    when `rules` carry a mesh, on the default device otherwise."""
    init = functools.partial(T.init_params, cfg)
    if rules is None:
        return jax.jit(init)(key)
    from repro.sharding import spec_tree_to_shardings
    shardings = spec_tree_to_shardings(rules.mesh, D.paged_param_specs(
        cfg, jax.eval_shape(init, key), rules))
    return jax.jit(init, out_shardings=shardings)(key)


def build_engine(args, cfg=None):
    cfg = cfg or (get_smoke_config(args.arch) if args.smoke
                  else get_config(args.arch))
    rules = None
    if getattr(args, "mesh_model", 1) > 1:
        from repro.launch.mesh import make_serve_mesh
        from repro.sharding import default_rules
        rules = default_rules(make_serve_mesh(args.mesh_model))
    params = init_serve_params(cfg, jax.random.PRNGKey(args.seed), rules)
    p13n = None
    if args.users > 0:
        from repro.configs.base import OptimizerConfig, SparseUpdateConfig
        p13n = PersonalizationConfig(
            sparse=SparseUpdateConfig(
                update_ratio=args.personalize_ratio,
                num_update_layers=args.personalize_layers),
            optimizer=OptimizerConfig(kind="sgd",
                                      learning_rate=args.personalize_lr),
            store_capacity=args.delta_capacity,
            train_tokens=args.train_tokens, seed=args.seed)
    chaos = None
    if args.fault_rate > 0.0 or args.kill_after is not None:
        chaos = FaultSchedule(args.chaos_seed, fault_rate=args.fault_rate,
                              slow_s=args.chaos_slow_s,
                              kill_after=args.kill_after)
    flash_decode = True if getattr(args, "flash_decode", False) else None
    engine = ServeEngine(
        cfg, params, num_slots=args.batch,
        max_len=args.prompt_len + args.gen_len,
        temperature=args.temperature, eos_id=args.eos_id, seed=args.seed,
        page_size=args.page_size, num_pages=args.num_pages,
        prefix_sharing=not args.no_prefix_sharing,
        prefix_mode=args.prefix_mode,
        prefix_persist=args.prefix_persist,
        personalization=p13n,
        chaos=chaos, max_retries=args.max_retries,
        shed_watermark=args.shed_watermark, watchdog_s=args.watchdog_s,
        journal=args.journal, rules=rules, flash_decode=flash_decode)
    return cfg, engine


def build_requests(args, cfg):
    if getattr(args, "branching_prefix", False):
        reqs = make_branching_prefix_requests(
            cfg, args.requests, args.prompt_len, args.gen_len,
            page_size=args.page_size,
            max_prefix_pages=max(1, (args.prompt_len - 1) // args.page_size
                                 - 1),
            seed=args.seed)
    elif args.shared_prefix_len > 0:
        reqs = make_shared_prefix_requests(
            cfg, args.requests, args.shared_prefix_len, args.prompt_len,
            args.gen_len, seed=args.seed)
    else:
        reqs = make_random_requests(cfg, args.requests, args.prompt_len,
                                    args.gen_len, seed=args.seed)
    for r in reqs:
        r.timeout_s = args.timeout_s
        if args.users > 0:
            r.user = r.rid % args.users  # round-robin user routing
        if args.stream:
            r.stream = lambda rid, tok: print(
                f"[stream] rid={rid} token={tok}")
    return reqs


def add_serve_args(ap: argparse.ArgumentParser):
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV-cache page")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool capacity (default: batch * max pages "
                         "per request, i.e. contiguous-equivalent)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable cross-request prompt-prefix page sharing")
    ap.add_argument("--prefix-mode", choices=("radix", "chain", "off"),
                    default="radix",
                    help="prefix-reuse structure: radix tree with state "
                         "snapshots + spill (default), legacy chain-hash "
                         "baseline, or off")
    ap.add_argument("--prefix-persist", type=str, default=None,
                    help="directory for the persistent prefix tree: the "
                         "spill tier is saved there after each run and "
                         "restored at engine start (radix mode only)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="> 0: requests share a common prompt prefix of "
                         "this many tokens (system-prompt workload)")
    ap.add_argument("--branching-prefix", action="store_true",
                    help="partially-overlapping (zipf-branching) prefix "
                         "workload instead of uniform-random prompts")
    ap.add_argument("--timeout-s", type=float, default=None,
                    help="per-request wall-clock deadline")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are sampled")
    ap.add_argument("--users", type=int, default=0,
                    help="> 0: route requests round-robin across this many "
                         "user ids and personalize per user (delta store + "
                         "online train waves)")
    ap.add_argument("--personalize-lr", type=float, default=0.05,
                    help="online train-wave sgd learning rate")
    ap.add_argument("--personalize-layers", type=int, default=2,
                    help="trainable layer suffix K for per-user deltas")
    ap.add_argument("--personalize-ratio", type=float, default=0.25,
                    help="channel update ratio for per-user deltas")
    ap.add_argument("--train-tokens", type=int, default=16,
                    help="tokens per online train wave")
    ap.add_argument("--delta-capacity", type=int, default=32,
                    help="max resident per-user deltas (hard LRU bound)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="> 0: deterministic chaos injection — per-draw "
                         "probability of page-alloc / step / stream / slow "
                         "faults (runtime/chaos.py)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="fault-schedule seed: same seed, same faults")
    ap.add_argument("--chaos-slow-s", type=float, default=0.002,
                    help="injected straggler delay per slow fault")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="transient faults absorbed per request before it "
                         "is quarantined")
    ap.add_argument("--shed-watermark", type=float, default=0.0,
                    help="> 0: defer admission when free pages would drop "
                         "below this fraction of the pool")
    ap.add_argument("--watchdog-s", type=float, default=None,
                    help="quarantine a request making no progress for this "
                         "many seconds")
    ap.add_argument("--journal", type=str, default=None,
                    help="request-lifecycle journal file: admitted-but-"
                         "unfinished requests are replayed after a restart")
    ap.add_argument("--kill-after", type=int, default=None,
                    help="inject a hard crash after N completed requests "
                         "(exercises journal replay + prefix persistence)")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="> 1: run paged decode through shard_map over a "
                         "(1, N) device mesh — page pools shard over KV "
                         "heads along the model axis, page tables and slot "
                         "state stay replicated")
    ap.add_argument("--flash-decode", action="store_true",
                    help="force the flash-decoding split softmax (page-"
                         "tiled online-softmax partials) even single-device;"
                         " default: on when --mesh-model > 1, off otherwise")
    return ap


def main(argv=None):
    args = add_serve_args(argparse.ArgumentParser()).parse_args(argv)
    enable_compile_cache()
    cfg, engine = build_engine(args)
    stats = engine.run(build_requests(args, cfg), verbose=not args.stream)
    print(f"[serve] {stats.requests_completed}/{args.requests} requests "
          f"({stats.requests_cancelled} cancelled), "
          f"{stats.tokens_out} tokens in {stats.wall_s:.2f}s "
          f"({stats.tok_per_s:.1f} tok/s incl. compile, "
          f"{stats.refills} slot refills, "
          f"{stats.prefill_chunks} prefill chunks)")
    print(f"[serve] latency p50 {stats.latency_p50_s * 1e3:.1f}ms "
          f"p95 {stats.latency_p95_s * 1e3:.1f}ms")
    print(f"[serve] pages {stats.pages_peak}/{stats.pages_total} peak "
          f"(util {stats.page_util:.2f}), "
          f"prefix hit rate {stats.prefix_hit_rate:.2f}, "
          f"{stats.cow_splits} COW splits")
    if stats.mesh_shards > 1:
        print(f"[serve] mesh: {stats.mesh_shards} model-axis shards, "
              f"{stats.pool_shard_bytes} pool bytes/shard")
    if stats.prefix_mode == "radix":
        print(f"[serve] radix: {stats.radix_nodes} nodes, "
              f"snapshot hit rate {stats.snapshot_hit_rate:.2f} "
              f"({stats.snapshots_stored} stored), "
              f"{stats.spills} spills / {stats.rehydrates} rehydrates, "
              f"{stats.spill_entries} tier entries")
    if args.fault_rate > 0.0 or args.kill_after is not None \
            or args.journal is not None:
        print(f"[serve] chaos: {stats.faults_injected} faults injected "
              f"{dict(stats.faults_by_kind)}, {stats.retries} retries, "
              f"{stats.sheds} sheds, {stats.quarantined} quarantined, "
              f"{stats.watchdog_kills} watchdog kills, "
              f"{stats.stream_errors} stream errors, "
              f"{stats.journal_replays} journal replays, "
              f"{stats.stragglers} straggler waves")
    if args.users > 0:
        print(f"[serve] personalization: {args.users} users, "
              f"{stats.train_waves} train waves "
              f"({stats.train_wave_ms_per_token:.2f}ms/token overhead), "
              f"delta hit rate {stats.delta_hit_rate:.2f}, "
              f"{stats.delta_resident_bytes} delta bytes resident, "
              f"{stats.delta_evictions} evictions")
    return stats


if __name__ == "__main__":
    main()
