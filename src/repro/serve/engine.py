"""Continuous-batching engine over a paged KV cache.

One engine iteration:

1. *Timeouts*: requests past their deadline are cancelled (queued ones are
   dropped without admission); their tokens never reach the throughput
   counters.
2. *Admission*: while a FREE slot and a queued request exist AND the page
   pool can cover the request's worst-case page need, bind the request to
   the slot. Prompt-prefix sharing (every cache family) attaches cached
   pages from the radix tree — and, for archs with ring/recurrent state,
   restores the recurrent-state snapshot at the deepest matched page
   boundary — so the matched prefix tokens are never recomputed. The
   match stays pinned in the tree until the slot closes.
3. *Chunked prefill*: every PREFILL slot advances by ONE page-sized chunk
   through the same ``paged_step`` the decode uses (B=1), so a long prompt
   admission never stalls in-flight decodes. The final chunk's logits yield
   the request's first sampled token.
4. *Decode*: one jitted fixed-shape ``paged_step`` over all slots (S=1)
   with per-slot start positions and an active mask; inactive rows keep
   their state bit-for-bit and their page writes are dropped.

Copy-on-write: any write into a page shared with the prefix cache or
another request first COW-splits it (exclusive copy of the device rows in
every layer's pool). The canonical trigger: a request registers its
partially-filled last prompt page, then COWs it on its first decode write,
leaving the cached page frozen at prompt-only content.

Prefix reuse modes (`prefix_mode`): "radix" (default) is the radix tree
over token pages with recurrent-state snapshots and the host spill tier —
evicted/ended trees survive across `run()` calls and, with
`prefix_persist`, across engine restarts. "chain" is the legacy flat
chain-hash baseline (fully-paged archs only, dies with `run()`), kept for
comparison. "off" disables sharing entirely.

PRNG: the engine key is split every step, so temperature sampling and the
placeholder-embeds input path (``cfg.embed_inputs`` frontends) never reuse
a key across steps.

Robustness (``runtime/chaos.py`` is the serve-side fault story):

- *Deterministic fault injection*: a seeded ``FaultSchedule`` makes page
  allocations, prefill/decode steps, and stream callbacks fail (or run
  slow) on a replayable schedule. Step faults fire BEFORE the jitted call
  and alloc faults before any pool mutation, so every injected failure is
  retryable without state repair — under greedy decoding, faults change
  latency and counters, never served tokens (pinned by parity tests).
- *Graceful degradation*: a faulted slot retries with capped exponential
  backoff (its batch row is masked out, state frozen bit-for-bit); a
  request that exhausts ``max_retries`` — or trips the hung-request
  watchdog — is closed as "quarantined" so one poison request can never
  wedge a slot; admission sheds (defers) load when free pages would drop
  below ``shed_watermark`` (shed requests keep their `timeout_s`
  accounting); stream-callback exceptions are absorbed, not fatal; every
  engine iteration feeds a ``StragglerMonitor``.
- *Crash safety*: with ``journal=...`` every admission/completion is
  fsynced to an append-only request journal; ``recover_requests()`` on a
  restarted engine replays in-flight requests, and the prefix spill tier
  (flushed on BOTH clean exit and crash unwind) turns their re-prefill
  into prefix/snapshot hits. ``InjectedCrash`` (``kill_after``) simulates
  the hard kill end to end.
"""
from __future__ import annotations

import dataclasses
import os
import time
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import (CheckpointCorruptError,
                                      restore_spill_tier, save_spill_tier)
from repro.models import decoding as D
from repro.runtime.chaos import FaultKind, InjectedCrash, InjectedFault
from repro.runtime.fault import StragglerMonitor
from repro.serve.deltas import DeltaStore, PersonalizationConfig
from repro.serve.journal import RequestJournal
from repro.serve.paging import (ChainPrefixCache, PagePool, RadixPrefixCache,
                                SpillTier)
from repro.serve.sampling import sample_token
from repro.serve.scheduler import Request, Scheduler, Slot, SlotState

__all__ = ["RequestResult", "ServeEngine", "ServeStats",
           "make_random_requests", "make_shared_prefix_requests",
           "make_branching_prefix_requests"]


def _graft_like(tpl, blob):
    """Re-attach empty subtrees that checkpoint serialization drops: walk
    the template's dict skeleton and take `blob`'s value wherever the
    template has leaves below. Host trees only (structure work, no data)."""
    if isinstance(tpl, dict):
        return {k: _graft_like(v, blob.get(k, {}) if isinstance(blob, dict)
                               else {}) for k, v in tpl.items()}
    return blob


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: list            # sampled token ids, in order
    latency_s: float        # submit -> completion (includes queueing)
    status: str = "completed"   # completed | cancelled


@dataclasses.dataclass
class ServeStats:
    requests_completed: int
    requests_cancelled: int
    tokens_out: int         # tokens of COMPLETED requests only
    tokens_cancelled: int
    wall_s: float
    tok_per_s: float
    latency_p50_s: float
    latency_p95_s: float
    refills: int            # admissions that recycled a dirty slot
    prefill_chunks: int     # chunked-prefill steps run
    prefix_hit_tokens: int  # prompt tokens served from shared pages
    prefix_lookup_tokens: int
    pages_total: int        # page-pool capacity
    pages_peak: int         # peak pages in use (sharing lowers this)
    cow_splits: int
    results: dict           # rid -> RequestResult
    # prefix-reuse internals (zero when prefix_mode != "radix")
    prefix_mode: str = "off"
    prefix_lookups: int = 0         # admission-time cache lookups
    state_lookups: int = 0          # lookups that asked for a state snapshot
    radix_nodes: int = 0            # tree nodes at end of run
    snapshot_hits: int = 0          # matches that restored recurrent state
    snapshots_stored: int = 0
    spills: int = 0                 # entries written to the host spill tier
    rehydrates: int = 0             # spilled entries re-attached on match
    spill_entries: int = 0          # tier size at end of run
    # per-user personalization (all zero when the engine has none)
    delta_hits: int = 0             # delta-store admissions that hit
    delta_lookups: int = 0          # delta-store admissions total
    delta_evictions: int = 0
    delta_resident_bytes: int = 0   # host bytes of resident deltas at end
    train_waves: int = 0            # online train waves run
    train_wave_s: float = 0.0       # wall time spent in train waves
    wave_losses: list = dataclasses.field(default_factory=list)
    # (user, pre-update loss) per wave, in wave order
    # robustness / chaos (all zero without a FaultSchedule / journal)
    faults_injected: int = 0        # chaos draws that fired during this run
    faults_by_kind: dict = dataclasses.field(default_factory=dict)
    retries: int = 0                # transient faults absorbed by backoff
    sheds: int = 0                  # requests deferred by the load-shed watermark
    quarantined: int = 0            # requests closed as poison
    tokens_quarantined: int = 0
    watchdog_kills: int = 0         # quarantines from the hung-request watchdog
    stream_errors: int = 0          # stream-callback exceptions absorbed
    journal_replays: int = 0        # re-admissions recovered from the journal
    stragglers: int = 0             # engine iterations flagged as stragglers
    # sharded serving (defaults = single-device engine)
    mesh_shards: int = 1            # model-axis shards the pools split into
    pool_shard_bytes: int = 0       # page-pool bytes resident per shard
    # phase-split throughput: wall time spent inside the jitted step (host
    # sync included) and tokens processed, split prefill vs decode — the
    # mesh sweep reports these per mesh row since the two phases scale
    # differently with tensor parallelism
    prefill_s: float = 0.0
    decode_s: float = 0.0
    prefill_tokens: int = 0         # VALID prompt tokens prefilled (pad excl.)
    decode_tokens: int = 0          # tokens sampled for runnable slots

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hit_tokens / max(1, self.prefix_lookup_tokens)

    @property
    def page_util(self) -> float:
        return self.pages_peak / max(1, self.pages_total)

    @property
    def snapshot_hit_rate(self) -> float:
        """Snapshot hits over STATE-FAMILY lookups only. Attention-family
        lookups never ask for a snapshot, so denominating by ALL prefix
        lookups (the old behaviour) diluted the rate toward zero on mixed
        llama3+jamba workloads."""
        return self.snapshot_hits / max(1, self.state_lookups)

    @property
    def delta_hit_rate(self) -> float:
        return self.delta_hits / max(1, self.delta_lookups)

    @property
    def prefill_tok_per_s(self) -> float:
        return self.prefill_tokens / max(self.prefill_s, 1e-9)

    @property
    def decode_tok_per_s(self) -> float:
        return self.decode_tokens / max(self.decode_s, 1e-9)

    @property
    def train_wave_ms_per_token(self) -> float:
        """Train-wave overhead in MILLISECONDS amortized over every decoded
        token. `train_wave_s` is seconds; the former `wave_s_per_token`
        name left the *1e3 to each call site — one missed conversion
        under-reported wave cost by 1000x, so the property now owns it."""
        return self.train_wave_s * 1e3 / max(1, self.tokens_out)


class ServeEngine:
    """Paged continuous-batching serve loop for one model + parameter set."""

    def __init__(self, cfg, params, *, num_slots: int, max_len: int,
                 temperature: float = 0.0, eos_id: Optional[int] = None,
                 seed: int = 0, page_size: int = 16,
                 num_pages: Optional[int] = None, prefix_sharing: bool = True,
                 prefix_mode: str = "radix",
                 prefix_persist: Optional[str] = None,
                 spill_entries: int = 4096, snapshot_budget: int = 256,
                 max_tree_nodes: int = 4096,
                 personalization: Optional[PersonalizationConfig] = None,
                 chaos=None, max_retries: int = 3,
                 retry_backoff_s: float = 0.005,
                 retry_backoff_cap_s: float = 0.1,
                 shed_watermark: float = 0.0,
                 watchdog_s: Optional[float] = None,
                 journal=None, straggler_factor: float = 2.5,
                 rules=None, flash_decode: Optional[bool] = None):
        assert num_slots >= 1 and max_len >= 2 and page_size >= 1
        assert prefix_mode in ("radix", "chain", "off")
        assert max_retries >= 0 and 0.0 <= shed_watermark < 1.0
        # robustness knobs (see the module docstring's Robustness section);
        # `chaos` is a runtime.chaos.FaultSchedule or None — every injection
        # point is gated on it, so a chaos-free engine runs the exact
        # pre-chaos code path
        self.chaos = chaos
        self.max_retries = max_retries
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_backoff_cap_s = float(retry_backoff_cap_s)
        self.shed_watermark = float(shed_watermark)
        self.watchdog_s = watchdog_s
        self._straggler_factor = straggler_factor
        self._journal = RequestJournal(journal) if isinstance(journal, str) \
            else journal
        self._stream_errors = 0
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.page_size = page_size
        self.max_pages = -(-max_len // page_size)
        self.has_pages = D.has_paged_layers(cfg)
        self._need_state = D.has_state_layers(cfg)
        # default pool = contiguous capacity (num_slots full-length tables);
        # prefix sharing makes the PEAK usage come in under it. State-only
        # archs (rwkv) have no paged layers and no pool at all.
        if not self.has_pages:
            self.num_pages = 0
        else:
            self.num_pages = num_pages if num_pages is not None else \
                num_slots * self.max_pages
        # Placeholder-embeds frontends have no token identity to key reuse
        # on; the chain baseline additionally needs every layer paged (it
        # has no snapshots to cover ring/recurrent state).
        if not prefix_sharing or cfg.embed_inputs:
            prefix_mode = "off"
        elif prefix_mode == "chain" and (self._need_state
                                         or not self.has_pages):
            prefix_mode = "off"
        self.prefix_mode = prefix_mode
        self.prefix_sharing = prefix_mode != "off"
        self.snapshot_budget = snapshot_budget
        self.max_tree_nodes = max_tree_nodes
        # ONE spill tier per engine, shared by every run()'s tree: prefix
        # state survives pool teardown (and, with prefix_persist, restarts)
        self._spill = SpillTier(spill_entries) \
            if prefix_mode == "radix" else None
        self._cache = None      # built per run(); None until the first run
        self._persist_path = None
        if prefix_persist is not None and self._spill is not None:
            os.makedirs(prefix_persist, exist_ok=True)
            self._persist_path = os.path.join(prefix_persist,
                                              "prefix_tree.ckpt")
            if os.path.exists(self._persist_path):
                try:
                    meta = restore_spill_tier(self._persist_path, self._spill)
                except CheckpointCorruptError as e:
                    # torn persist file (crash mid-write): cold start beats
                    # crashing the restart
                    import warnings
                    warnings.warn(f"prefix-persist tree is corrupt ({e}); "
                                  "starting cold")
                    self._spill.clear()
                    meta = {"page_size": page_size, "max_len": max_len,
                            "model": cfg.name}
                if (meta.get("page_size") != page_size
                        or meta.get("max_len") != max_len
                        or meta.get("model") != cfg.name):
                    self._spill.clear()     # incompatible tree: start cold
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self._key = jax.random.PRNGKey(seed)
        self._zero_key = jax.random.PRNGKey(0)
        self._decode_length = jnp.ones((num_slots,), jnp.int32)

        # sharded serving: with AxisRules carrying a mesh + model axis, the
        # step runs through shard_map — page pools shard over KV heads, page
        # tables / batch / slot state stay replicated (see models/decoding
        # `make_sharded_paged_step`). flash_decode defaults on when sharded
        # (that is the point of splitting long contexts across cores) and
        # off single-device, keeping that path bit-identical.
        self.rules = rules
        self.mesh_shards = 1
        if rules is not None:
            if rules.mesh is None or rules.model_axis is None:
                raise ValueError(
                    "sharded serving needs AxisRules built from a mesh with "
                    f"a model axis (got mesh={rules.mesh!r}, "
                    f"model_axis={rules.model_axis!r})")
            self.mesh_shards = D.validate_pool_sharding(cfg, rules)
        self.flash_decode = flash_decode if flash_decode is not None \
            else rules is not None

        ps = page_size
        if rules is not None:
            # a no-op for params already built in this layout
            # (`launch.serve.init_serve_params`); the engine keeps no
            # other copy unless personalization needs one (below)
            from repro.sharding import spec_tree_to_shardings
            self.params = params = jax.device_put(
                params, spec_tree_to_shardings(
                    rules.mesh, D.paged_param_specs(cfg, params, rules)))
            self._step = D.make_sharded_paged_step(
                cfg, rules, params, page_size=ps,
                flash_decode=self.flash_decode)
        else:
            fd = self.flash_decode
            self._step = jax.jit(
                lambda p, batch, state, pools, pt, deltas: D.paged_step(
                    cfg, p, batch, state, pools, pt, page_size=ps,
                    deltas=deltas, flash_decode=fd))
        # on a mesh, pin every pool/state-producing helper to the canonical
        # layout (pools sharded over KV heads, state replicated): otherwise
        # a COW split or row insert hands the next step a differently-laid-
        # out input, costing a duplicate jit cache entry per batch shape and
        # letting pools silently degrade to a replicated (full-size) layout
        pool_out = state_out = None
        if rules is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            pool_out = NamedSharding(rules.mesh, D.pool_pspec(rules))
            state_out = NamedSharding(rules.mesh, PartitionSpec())
        self._state_shard = state_out
        self._extract = jax.jit(D.cache_extract_row, out_shardings=state_out)
        self._insert = jax.jit(D.cache_insert_row, out_shardings=state_out)
        self._reset = jax.jit(D.cache_reset_row, out_shardings=state_out)
        self._copy = jax.jit(
            lambda pools, src, dst: D.copy_pool_rows(pools, src, dst, ps),
            out_shardings=pool_out)
        self._read_rows = jax.jit(
            lambda pools, src: D.read_pool_rows(pools, src, ps))
        self._write_rows = jax.jit(D.write_pool_rows,
                                   out_shardings=pool_out)
        self._sample = jax.jit(
            lambda logits, key: sample_token(logits, key, self.temperature))

        self._p13n = personalization
        self._dbatch = None
        if personalization is not None:
            self._init_personalization()

    # -- per-user personalization ------------------------------------------

    def _init_personalization(self):
        """Build the delta-aware serving pieces: the selection plan pruned to
        decode-coverable leaves, the frozen/trainable base split, the online
        train wave, and the per-user delta store. Requests with user=None
        keep zero delta rows (an exact no-op) under the SAME jitted step."""
        from repro.core import build_plan, random_selection
        from repro.core.delta import (DeltaState, decode_delta_spec,
                                      zeros_delta_tree)
        from repro.train.steps import make_online_wave, split_params

        p = self._p13n
        assert not self.cfg.embed_inputs, (
            "personalization trains on token streams; embed-input frontends "
            "have none")
        plan = build_plan(self.cfg, p.sparse, 0)
        # waves train on one whole copy of the params on the mesh's first
        # device: they must be bit-identical to a single-device engine's,
        # or the deltas — and so the served tokens — would diverge across
        # mesh sizes. That copy is the one full model a mesh engine holds
        # on one chip, so personalization needs a model that fits a chip.
        base = self.params if self.rules is None else jax.device_put(
            self.params, self.rules.mesh.devices.flat[0])
        frozen, trainable = split_params(base, plan)
        spec = decode_delta_spec(plan, trainable["segments"])
        if not spec:
            raise ValueError(
                "no decode-coverable selectable leaves for this arch "
                "(personalized decode covers attn/mlp projections only)")
        # train exactly what decode can apply: waves update only the covered
        # leaves, so the served model IS the trained one
        self._plan = dataclasses.replace(plan, spec=spec)
        self._frozen, self._trainable = frozen, trainable
        self._seg_steps = {
            seg: int(jax.tree.leaves(self.params["segments"][seg])[0].shape[0])
            for seg in spec}
        self._delta_key = jax.random.PRNGKey(p.seed)
        self._wave = jax.jit(make_online_wave(
            self.cfg, p.sparse, p.optimizer, self._plan,
            wave_tokens=p.train_tokens))
        self._zeros_delta = zeros_delta_tree
        self._deltas = DeltaStore(p.store_capacity, self._make_delta_entry)
        self._DeltaState = DeltaState
        self._random_selection = random_selection

    def _make_delta_entry(self, user):
        """Fresh zero delta with this user's fixed channel selection (the
        user id seeds the selection, so it is stable across evictions)."""
        salt = zlib.crc32(str(user).encode()) & 0x7FFFFFFF
        key = jax.random.fold_in(self._delta_key, salt)
        idx_dev = self._random_selection(self._plan, key)
        idx = {seg: jax.tree.map(np.asarray, idx_dev[seg])
               for seg in self._plan.spec}
        vals = self._zeros_delta(self._trainable["segments"], idx,
                                 self._plan.spec, xp=np)
        return self._DeltaState(idx=idx, vals=vals)

    def _delta_batch_zeros(self):
        """Device-resident per-slot delta rows, all zero: {seg: {"idx",
        "val"}} with leaves [scan_steps, num_slots, ...] so they ride the
        layer scan next to the params (zero rows over the frozen prefix and
        for non-personalized slots)."""
        from repro.core.sparse_update import SelSpec
        b = self.num_slots
        out = {}
        for seg, spec in self._plan.spec.items():
            steps = self._seg_steps[seg]
            is_sp = lambda x: isinstance(x, SelSpec)
            idx = jax.tree.map(
                lambda sp: jnp.zeros((steps, b, sp.n_shards, sp.n_sel),
                                     jnp.int32), spec, is_leaf=is_sp)

            def wv(stack, sp):
                if isinstance(sp, SelSpec):
                    d_in = stack.shape[1]
                    return jnp.zeros(
                        (steps, b, d_in, sp.n_shards, sp.n_sel, sp.block),
                        jnp.float32)
                return {k: wv(stack[k], sp[k]) for k in sp}

            out[seg] = {"idx": idx,
                        "val": wv(self._trainable["segments"][seg], spec)}
        return out

    def _delta_row_tree(self, entry):
        """Lift a host DeltaState into [scan_steps, 1, ...] device rows,
        zero-padded over the frozen layer prefix (trainable = LAST K steps),
        ready for `cache_insert_row` into the slot's delta batch row."""
        out = {}
        for seg in self._plan.spec:
            steps = self._seg_steps[seg]

            def pad(leaf, dt):
                src = np.zeros((steps, 1) + leaf.shape[1:], dt)
                src[steps - leaf.shape[0]:, 0] = leaf
                return jnp.asarray(src)

            out[seg] = {
                "idx": jax.tree.map(lambda a: pad(a, np.int32),
                                    entry.idx[seg]),
                "val": jax.tree.map(lambda a: pad(a, np.float32),
                                    entry.vals[seg]),
            }
        return out

    def _online_wave(self, slot, sched):
        """Run one compact train wave on the completed request's token
        stream, advance the user's delta in the store, and re-materialize
        the delta rows of any live slot of the same user (their in-flight
        decode picks up the update mid-stream)."""
        req = slot.request
        p = self._p13n
        stream = np.concatenate([
            np.asarray(req.tokens, np.int64),
            np.asarray(slot.out_tokens, np.int64)])
        n = p.train_tokens
        arr = stream[-(n + 1):] if len(stream) >= n + 1 \
            else np.resize(stream, n + 1)
        batch = {"tokens": jnp.asarray(arr[:-1], jnp.int32)[None],
                 "labels": jnp.asarray(arr[1:], jnp.int32)[None]}
        entry = self._deltas.get(req.user)
        vals_dev = jax.tree.map(jnp.asarray, entry.vals)
        idx_dev = jax.tree.map(jnp.asarray, entry.idx)
        t0 = time.perf_counter()
        new_vals, metrics = self._wave(self._trainable, self._frozen,
                                       vals_dev, idx_dev, batch,
                                       self._next_key())
        jax.block_until_ready(new_vals)
        self._wave_s += time.perf_counter() - t0
        self._wave_count += 1
        self._wave_losses.append((req.user, float(metrics["loss"])))
        entry.vals = jax.tree.map(np.asarray, new_vals)
        self._deltas.put(req.user, entry)
        row_tree = None
        for other in sched.live_slots():
            if other is slot or other.request is None or \
                    other.request.user != req.user:
                continue
            if row_tree is None:
                row_tree = self._delta_row_tree(entry)
            self._dbatch = self._insert(self._dbatch, row_tree, other.index)

    # -- input plumbing ----------------------------------------------------

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _sample_key(self):
        """Greedy sampling ignores the key — skip the per-token split."""
        return self._zero_key if self.temperature <= 0.0 else self._next_key()

    def _chunk_batch(self, req: Request, start: int, size: int):
        """Prefill chunk, always padded to one fixed page-sized shape: the
        final partial chunk would otherwise retrace `paged_step` for every
        distinct prompt-length residue. `length` masks the padding inside
        the step (writes dropped, state frozen, logits at length-1)."""
        ps = self.page_size
        batch = {"start": jnp.asarray([start], jnp.int32),
                 "active": jnp.asarray([True]),
                 "length": jnp.asarray([size], jnp.int32)}
        if self.cfg.embed_inputs:
            emb = np.asarray(req.embeds[start:start + size])
            if size < ps:
                emb = np.pad(emb, ((0, ps - size), (0, 0)))
            batch["embeds"] = jnp.asarray(emb)[None]
        else:
            toks = np.asarray(req.tokens[start:start + size], np.int32)
            if size < ps:
                toks = np.pad(toks, (0, ps - size))
            batch["tokens"] = jnp.asarray(toks)[None]
        return batch

    def _decode_batch(self, tokens_row, pos_row, active_row=None):
        if active_row is None:
            active_row = [True] * self.num_slots
        batch = {"start": jnp.asarray(pos_row, jnp.int32),
                 "active": jnp.asarray(active_row),
                 "length": self._decode_length}
        if self.cfg.embed_inputs:
            # placeholder frontend: fresh embeds each step (fresh key per
            # step — a reused key would feed identical inputs every step)
            batch["embeds"] = jax.random.normal(
                self._next_key(), (self.num_slots, 1, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        else:
            batch["tokens"] = jnp.asarray(tokens_row, jnp.int32)[:, None]
        return batch

    # -- page bookkeeping --------------------------------------------------

    def _pages_needed(self, req: Request) -> int:
        if not self.has_pages:
            return 0
        # the final sampled token is returned but never written back
        written = req.prompt_len + req.max_new_tokens - 1
        return -(-written // self.page_size)

    def _worst_case_need(self, slot: Slot) -> int:
        """Pages this live request may still allocate: unallocated logical
        pages plus (at most) one COW of the shared page at its write
        boundary. Full shared prefix pages are never written again, so they
        never COW under the holder."""
        need = sum(1 for pg in range(self._pages_needed(slot.request))
                   if self._pt[slot.index, pg] < 0)
        wp = slot.pos // self.page_size
        if wp < self.max_pages:
            pid = self._pt[slot.index, wp]
            if pid >= 0 and self._pool.ref[pid] > 1:
                need += 1
        return need

    def _headroom(self, sched) -> int:
        avail = self._pool.free_pages
        if self._cache is not None:
            avail += self._cache.evictable()
        return avail - sum(self._worst_case_need(s)
                           for s in sched.live_slots())

    def _evict_until_free(self) -> None:
        while not self._pool.free_pages:
            if self._cache is None or not self._cache.evict_one():
                raise RuntimeError("page pool exhausted with nothing "
                                   "evictable (reservation bug)")

    def _alloc_page(self) -> int:
        self._evict_until_free()
        return self._pool.alloc()

    def _ensure_writable(self, slot: Slot, lo: int, hi: int, pools):
        """Make every page covering token positions [lo, hi) allocated and
        exclusive to `slot`, COW-splitting shared pages (copying their
        device rows) before any write lands in them."""
        if not self.has_pages:
            return pools
        ps = self.page_size
        for pg in range(lo // ps, -(-hi // ps)):
            pid = int(self._pt[slot.index, pg])
            if pid < 0:
                pid = self._alloc_page()
                assert pg == len(slot.page_ids), "non-contiguous page alloc"
                slot.page_ids.append(pid)
                self._pt[slot.index, pg] = pid
            elif self._pool.ref[pid] > 1:
                self._evict_until_free()
                new = self._pool.cow_split(pid)
                pools = self._copy(pools, pid * ps, new * ps)
                slot.page_ids[pg] = new
                self._pt[slot.index, pg] = new
        return pools

    def _page_reader(self, pid: int):
        """Device -> host: one page's token rows from EVERY layer's pool
        (the radix cache's spill callback)."""
        return jax.device_get(
            self._read_rows(self._pools, pid * self.page_size))

    def _page_writer(self, pid: int, blob) -> None:
        """Host -> device: write a spilled page's rows back into EVERY
        layer's pool (the radix cache's rehydrate callback). Grafts the
        blob onto the live pool structure first — disk roundtrips drop
        empty subtrees."""
        blob = _graft_like(self._pools, blob)
        self._pools = self._write_rows(
            self._pools, jax.tree.map(jnp.asarray, blob),
            pid * self.page_size)

    def _release_slot(self, slot: Slot):
        for pid in slot.page_ids:
            self._pool.decref(pid)
        slot.page_ids = []
        slot.registered_pages = 0
        if slot.match is not None:
            if self._cache is not None:
                self._cache.release(slot.match)
            slot.match = None
        self._pt[slot.index, :] = -1

    # -- robustness --------------------------------------------------------

    def _transient_fault(self, slot: Slot) -> bool:
        """Absorb one transient fault on `slot`'s request: count the retry
        and schedule capped exponential backoff. Returns True when the
        retry budget is exhausted — the caller quarantines the request so
        a poison request can never wedge the slot forever."""
        slot.retries += 1
        self._retry_events += 1
        if slot.retries > self.max_retries:
            return True
        back = min(self.retry_backoff_cap_s,
                   self.retry_backoff_s * (2 ** (slot.retries - 1)))
        slot.retry_at = time.perf_counter() + back
        return False

    def _wrap_stream(self, req: Request):
        """Guard a request's stream callback: injected stream faults AND
        real exceptions raised by the callback are absorbed (counted in
        `stream_errors`, treated as "keep generating") — a broken client
        degrades its own stream, it never crashes the engine or changes
        the served tokens. Returning False still cancels."""
        inner = req.stream
        if inner is None or getattr(inner, "_chaos_guarded", False):
            return inner

        def guarded(rid, tok):
            try:
                if self.chaos is not None:
                    self.chaos.maybe_raise(FaultKind.STREAM, site=rid)
                return inner(rid, tok)
            except Exception:
                self._stream_errors += 1
                return None
        guarded._chaos_guarded = True
        return guarded

    def _persist_prefix_state(self) -> None:
        """Flush the radix tree (pages + snapshots) into the host spill
        tier — and, with `prefix_persist`, to disk — while the device
        pools are still alive. Runs on clean exit AND on the crash-unwind
        path, so a killed engine still leaves a warm tree behind."""
        if self.prefix_mode != "radix" or self._cache is None:
            return
        self._cache.spill_all()
        if self._persist_path is not None:
            save_spill_tier(self._persist_path, self._spill,
                            meta={"page_size": self.page_size,
                                  "max_len": self.max_len,
                                  "model": self.cfg.name})

    def recover_requests(self) -> list[Request]:
        """In-flight requests from the journal: admitted by a previous
        (crashed) engine, never completed. Feed them back through `run()`
        — with `prefix_persist` their already-prefilled pages come back
        as prefix/snapshot hits instead of recomputation. Returns [] when
        the engine has no journal."""
        if self._journal is None:
            return []
        return self._journal.pending_requests()

    # -- serve loop --------------------------------------------------------

    def run(self, requests: list[Request], verbose: bool = False) -> ServeStats:
        try:
            return self._run(requests, verbose)
        except BaseException:
            # crash unwind — including InjectedCrash, which `except
            # Exception` recovery code can never swallow: flush the radix
            # tree to the spill tier (and disk, with prefix_persist) so a
            # restarted engine replays journaled requests against a warm
            # prefix cache instead of a cold one
            self._persist_prefix_state()
            raise

    def _run(self, requests: list[Request], verbose: bool) -> ServeStats:
        for r in requests:
            assert r.max_new_tokens >= 1, (
                f"request {r.rid}: max_new_tokens must be >= 1")
            assert r.prompt_len + r.max_new_tokens <= self.max_len, (
                f"request {r.rid}: prompt {r.prompt_len} + gen "
                f"{r.max_new_tokens} exceeds max_len {self.max_len}")
            assert self._pages_needed(r) <= self.num_pages, (
                f"request {r.rid} needs {self._pages_needed(r)} pages; "
                f"pool has {self.num_pages}")
        sched = Scheduler(self.num_slots, eos_id=self.eos_id)
        # rids journaled by a previous (crashed) engine and re-admitted in
        # this run count as journal replays
        replay_rids = (self._journal.pending_rids()
                       if self._journal is not None else set())
        for r in requests:
            r.stream = self._wrap_stream(r)
            sched.submit(r)
        chaos = self.chaos
        faults0 = chaos.faults_injected if chaos is not None else 0
        kinds0 = dict(chaos.faults_by_kind) if chaos is not None else {}
        self._retry_events = 0
        self._stream_errors = 0
        self._watchdog_kills = 0
        self._prefill_s = self._decode_s = 0.0
        self._prefill_tokens = self._decode_tokens = 0
        journal_replays = 0
        shed_rids: set[int] = set()
        mon = StragglerMonitor(factor=self._straggler_factor)

        state, self._pools = D.init_serve_cache(
            self.cfg, self.num_slots, self.max_len,
            max(1, self.num_pages), self.page_size)
        if self.rules is not None:
            # pools shard over KV heads along the model axis; state and the
            # page table stay replicated (page table is a host-side np
            # array, see below). device_put onto the CANONICAL layouts so
            # the very first step call keys the same jit cache entry as
            # steady state.
            from jax.sharding import NamedSharding
            shard = NamedSharding(self.rules.mesh, D.pool_pspec(self.rules))
            self._pools = jax.tree.map(
                lambda a: jax.device_put(a, shard), self._pools)
            state = jax.tree.map(
                lambda a: jax.device_put(a, self._state_shard), state)
        self._pool_bytes = sum(a.size * a.dtype.itemsize
                               for a in jax.tree.leaves(self._pools))
        self._pt = np.full((self.num_slots, self.max_pages), -1, np.int32)
        self._pool = PagePool(max(1, self.num_pages), self.page_size,
                              chaos=self.chaos)
        if self.prefix_mode == "radix":
            self._cache = RadixPrefixCache(
                self._pool, has_pages=self.has_pages,
                reader=self._page_reader if self.has_pages else None,
                writer=self._page_writer if self.has_pages else None,
                spill=self._spill, snapshot_budget=self.snapshot_budget,
                max_nodes=self.max_tree_nodes)
        elif self.prefix_mode == "chain":
            self._cache = ChainPrefixCache(self._pool)
        else:
            self._cache = None
        if self._p13n is not None:
            self._dbatch = self._delta_batch_zeros()
            if self.rules is not None:
                self._dbatch = jax.tree.map(
                    lambda a: jax.device_put(a, self._state_shard),
                    self._dbatch)
            self._duser = [None] * self.num_slots
            self._wave_s, self._wave_count = 0.0, 0
            self._wave_losses = []
        prefill_chunks = 0
        results: dict[int, RequestResult] = {}
        t0 = time.perf_counter()
        deadline = {r.rid: (t0 + r.timeout_s if r.timeout_s is not None
                            else None) for r in requests}

        def close(slot, status):
            req = slot.request
            if self._p13n is not None and req.user is not None:
                if status == "completed" and req.tokens is not None:
                    self._online_wave(slot, sched)
                self._deltas.release(req.user)
            results[req.rid] = RequestResult(
                req.rid, list(slot.out_tokens),
                time.perf_counter() - t0, status)
            self._release_slot(slot)
            if self._journal is not None:
                self._journal.done(req.rid, status)
            # injected crash fires AFTER the journal records this request
            # done and its slot is released: the completed request is never
            # replayed, and pool accounting stays consistent for the
            # crash-unwind prefix flush
            if chaos is not None and status == "completed" \
                    and chaos.crash_due(sched.requests_completed):
                raise InjectedCrash(
                    f"injected crash after {sched.requests_completed} "
                    f"completed request(s)")
            if verbose and status == "completed":
                print(f"[serve] completed {sched.requests_completed}"
                      f"/{len(requests)} requests")

        it_prev, it_work = None, False
        while not sched.done:
            now = time.perf_counter()
            # per-wave serve timing: only iterations that ran a jitted step
            # count — idle/backoff spins are sub-ms and would drag the
            # median down until every real wave looked like a straggler
            if it_prev is not None and it_work:
                mon.record(now - it_prev)
            it_prev, it_work = now, False
            # 1) deadlines: cancel overdue slots, drop overdue queued
            # requests; watchdog-quarantine slots that stopped progressing
            for slot in sched.live_slots():
                dl = deadline[slot.request.rid]
                if dl is not None and now > dl:
                    sched.cancel(slot)
                    close(slot, "cancelled")
                    continue
                if (self.watchdog_s is not None
                        and now - slot.last_progress > self.watchdog_s):
                    self._watchdog_kills += 1
                    sched.quarantine(slot)
                    close(slot, "quarantined")
            for req in [q for q in sched.queue
                        if deadline[q.rid] is not None
                        and now > deadline[q.rid]]:
                sched.drop_queued(req)
                results[req.rid] = RequestResult(req.rid, [], 0.0, "cancelled")

            # 2) admission (two-phase: page-pool pressure can defer the
            # queue head without disturbing FIFO order)
            while (adm := sched.peek_admission()) is not None:
                slot, req = adm
                mr, matched, covered = None, [], 0
                # personalized requests compute K/V under their own delta:
                # sharing those pages (or adopting shared ones) would serve
                # another user's prefix from the wrong weights
                if self._cache is not None and req.tokens is not None \
                        and req.user is None:
                    # leave >= 1 prompt token uncached: something must
                    # produce the logits that sample the first token
                    mr = self._cache.match(
                        np.asarray(req.tokens), req.prompt_len - 1,
                        need_state=self._need_state)
                    matched, covered = mr.pages, mr.covered
                has_partial = bool(matched) and matched[-1][1] < self.page_size
                need = self._pages_needed(req) - len(matched) + int(has_partial)
                pressure = self.has_pages and self._headroom(sched) < need
                # load shedding: admitting would drop free pages below the
                # watermark, so defer while anything is in flight. The shed
                # request stays queued, keeps ticking toward its own
                # timeout_s (the queued-deadline drop above provides the
                # accounting) — never silently dropped.
                shed = (not pressure and self.has_pages
                        and self.shed_watermark > 0.0
                        and bool(sched.live_slots())
                        and self._headroom(sched) - need
                        < self.shed_watermark * self.num_pages)
                if pressure or shed:
                    if mr is not None:              # roll the match back
                        self._cache.abandon(mr, req.prompt_len)
                        mr, matched, covered = None, [], 0
                    if sched.live_slots():
                        if shed:
                            shed_rids.add(req.rid)  # counted once per rid
                        break       # retry when an in-flight request frees pages
                    # nothing in flight will ever free pages: admit WITHOUT
                    # sharing — with no live slots every cache page is
                    # evictable, so pages_needed <= num_pages always fits
                    # (the watermark never blocks this path: degraded
                    # trickle admission beats deadlock)
                    assert self._headroom(sched) >= self._pages_needed(req)
                sched.commit_admission(slot, prefilled=covered)
                slot.last_progress = time.perf_counter()
                if self._journal is not None:
                    if req.rid in replay_rids:
                        journal_replays += 1
                        replay_rids.discard(req.rid)
                    self._journal.admit(req)
                slot.match = mr     # pinned until the slot closes
                slot.page_ids = [pid for pid, _ in matched]
                slot.registered_pages = len(matched) - int(has_partial)
                self._pt[slot.index, :] = -1
                self._pt[slot.index, :len(matched)] = slot.page_ids
                if mr is not None and mr.snapshot is not None:
                    # restore the recurrent state at the matched boundary;
                    # prefill resumes from slot.pos = covered
                    blob = _graft_like(state, mr.snapshot)
                    state = self._insert(
                        state, jax.tree.map(jnp.asarray, blob), slot.index)
                else:
                    state = self._reset(state, slot.index)
                if self._p13n is not None:
                    if req.user is not None:
                        entry = self._deltas.admit(req.user)
                        self._dbatch = self._insert(
                            self._dbatch, self._delta_row_tree(entry),
                            slot.index)
                        self._duser[slot.index] = req.user
                    elif self._duser[slot.index] is not None:
                        # recycle a slot a personalized request left dirty
                        self._dbatch = self._reset(self._dbatch, slot.index)
                        self._duser[slot.index] = None

            # 3) chunked prefill: one page-sized chunk per PREFILL slot
            for slot in sched.prefill_slots():
                req = slot.request
                if now < slot.retry_at:
                    continue        # backing off after a transient fault
                # faults are injected BEFORE the jitted step and before any
                # pool mutation, so absorbing one and retrying next
                # iteration replays the identical chunk — injected faults
                # can delay a request but never change its tokens
                try:
                    if chaos is not None:
                        chaos.maybe_raise(FaultKind.STEP, site=req.rid)
                        if chaos.draw(FaultKind.SLOW, site=req.rid):
                            time.sleep(chaos.slow_s)
                    shareable = (self._cache is not None
                                 and req.tokens is not None
                                 and req.user is None)
                    # chunk-time adoption: a page a CONCURRENT slot
                    # registered since our admission can be attached
                    # instead of recomputed (same-wave admissions of a
                    # common prefix share this way). State archs skip it:
                    # adopting K/V rows without restoring the recurrent
                    # state at that boundary would skip the state those
                    # tokens should have produced.
                    while (shareable and not self._need_state
                           and slot.pos % self.page_size == 0
                           and slot.pos + self.page_size <= req.prompt_len - 1
                           and slot.pos // self.page_size == len(slot.page_ids)):
                        pid = self._cache.match_page(
                            np.asarray(req.tokens), slot.pos)
                        if pid is None:
                            break
                        slot.page_ids.append(pid)
                        self._pt[slot.index, len(slot.page_ids) - 1] = pid
                        slot.pos += self.page_size
                        slot.registered_pages = len(slot.page_ids)
                    size = min(self.page_size, req.prompt_len - slot.pos)
                    self._pools = self._ensure_writable(
                        slot, slot.pos, slot.pos + size, self._pools)
                    st_row = self._extract(state, slot.index)
                    pt_row = jnp.asarray(self._pt[slot.index:slot.index + 1])
                    d_row = None if self._dbatch is None else \
                        self._extract(self._dbatch, slot.index)
                    ts = time.perf_counter()
                    logits, st_row, self._pools = self._step(
                        self.params, self._chunk_batch(req, slot.pos, size),
                        st_row, self._pools, pt_row, d_row)
                    jax.block_until_ready(logits)
                    self._prefill_s += time.perf_counter() - ts
                    self._prefill_tokens += size
                    state = self._insert(state, st_row, slot.index)
                    slot.pos += size
                    prefill_chunks += 1
                    it_work = True
                    slot.last_progress = time.perf_counter()
                    if shareable and self.has_pages:
                        slot.registered_pages = self._cache.insert_pages(
                            np.asarray(req.tokens),
                            min(slot.pos, req.prompt_len) // self.page_size,
                            slot.page_ids, slot.registered_pages)
                    if (shareable and self._need_state and slot.pos > 0
                            and slot.pos % self.page_size == 0
                            and self._cache.wants_snapshot(
                                np.asarray(req.tokens), slot.pos)):
                        # recurrent state at this page boundary, copied to
                        # host: the snapshot that lets a later
                        # shared-prefix request resume from here instead
                        # of re-prefilling
                        blob = jax.tree.map(
                            np.asarray,
                            jax.device_get(self._extract(state, slot.index)))
                        self._cache.insert_snapshot(
                            np.asarray(req.tokens), slot.pos, blob)
                    if slot.pos == req.prompt_len:
                        sched.finish_prefill(slot)
                        if shareable and self.has_pages \
                                and not self._need_state \
                                and self._headroom(sched) >= 1:
                            self._cache.insert_partial(
                                np.asarray(req.tokens), slot.page_ids[-1])
                        first = int(
                            self._sample(logits, self._sample_key())[0])
                        outcome = sched.record_token(slot, first)
                        if outcome is not None:
                            close(slot, "completed" if outcome == "done"
                                  else "cancelled")
                except InjectedFault:
                    # partial progress before the fault (adopted pages,
                    # incremental allocs) is recorded on the slot, so the
                    # retry resumes consistently instead of re-doing it
                    if self._transient_fault(slot):
                        sched.quarantine(slot)
                        close(slot, "quarantined")

            active = sched.active_slots()
            if not active:
                if not sched.prefill_slots() and sched.queue:
                    # nothing live and the admission loop still left the
                    # queue untouched: the forced unshared-admission path
                    # guarantees this is unreachable unless accounting broke
                    raise RuntimeError(
                        "serve deadlock: queued requests but no admissible "
                        "slot (page-pool accounting bug)")
                continue

            # 4) one decode step over the full fixed-shape batch; each slot
            # consumes its last sampled token at position slot.pos. Slots
            # backing off after a transient fault — or drawing one now —
            # are masked out of active_row: an inactive row keeps its state
            # and cache bit-for-bit (existing engine contract), so the
            # retried step feeds identical inputs and, with greedy
            # sampling's fixed key, produces the identical token.
            runnable = []
            for slot in active:
                if now < slot.retry_at:
                    continue
                try:
                    if chaos is not None:
                        chaos.maybe_raise(FaultKind.STEP,
                                          site=slot.request.rid)
                        if chaos.draw(FaultKind.SLOW, site=slot.request.rid):
                            time.sleep(chaos.slow_s)
                    self._pools = self._ensure_writable(
                        slot, slot.pos, slot.pos + 1, self._pools)
                except InjectedFault:
                    if self._transient_fault(slot):
                        sched.quarantine(slot)
                        close(slot, "quarantined")
                    continue
                runnable.append(slot)
            if not runnable:
                time.sleep(0.0005)  # everyone backing off: don't busy-spin
                continue
            run_idx = {s.index for s in runnable}
            tokens_row = [s.last_token for s in sched.slots]
            pos_row = [min(s.pos, self.max_len - 1) for s in sched.slots]
            active_row = [s.state is SlotState.ACTIVE and s.index in run_idx
                          for s in sched.slots]
            ts = time.perf_counter()
            logits, state, self._pools = self._step(
                self.params,
                self._decode_batch(tokens_row, pos_row, active_row),
                state, self._pools, jnp.asarray(self._pt), self._dbatch)
            jax.block_until_ready(logits)
            self._decode_s += time.perf_counter() - ts
            self._decode_tokens += len(runnable)
            it_work = True
            toks = np.asarray(self._sample(logits, self._sample_key()))
            for slot in runnable:         # inactive rows: sampled, discarded
                slot.pos += 1             # the fed token is now cached
                slot.last_progress = time.perf_counter()
                outcome = sched.record_token(slot, int(toks[slot.index]))
                if outcome is not None:
                    close(slot, "completed" if outcome == "done"
                          else "cancelled")

        self._persist_prefix_state()
        wall = time.perf_counter() - t0
        lat = [r.latency_s for r in results.values()
               if r.status == "completed"] or [0.0]
        c = self._cache
        if chaos is not None:
            d_faults = chaos.faults_injected - faults0
            d_kinds = {k: v - kinds0.get(k, 0)
                       for k, v in chaos.faults_by_kind.items()
                       if v - kinds0.get(k, 0)}
        else:
            d_faults, d_kinds = 0, {}
        return ServeStats(
            requests_completed=sched.requests_completed,
            requests_cancelled=sched.requests_cancelled,
            tokens_out=sched.tokens_out,
            tokens_cancelled=sched.tokens_cancelled,
            wall_s=wall,
            tok_per_s=sched.tokens_out / max(wall, 1e-9),
            latency_p50_s=float(np.percentile(lat, 50)),
            latency_p95_s=float(np.percentile(lat, 95)),
            refills=sched.refills,
            prefill_chunks=prefill_chunks,
            prefix_hit_tokens=(c.hit_tokens if c is not None else 0),
            prefix_lookup_tokens=(c.lookup_tokens if c is not None else 0),
            pages_total=self.num_pages,
            pages_peak=self._pool.peak_in_use,
            cow_splits=self._pool.cow_splits,
            results=results,
            prefix_mode=self.prefix_mode,
            prefix_lookups=(c.lookups if c is not None else 0),
            state_lookups=(c.state_lookups if c is not None else 0),
            radix_nodes=(c.node_count if c is not None else 0),
            snapshot_hits=(c.snapshot_hits if c is not None else 0),
            snapshots_stored=(c.snapshots_stored if c is not None else 0),
            spills=(c.spills if c is not None else 0),
            rehydrates=(c.rehydrates if c is not None else 0),
            spill_entries=(len(self._spill) if self._spill is not None else 0),
            delta_hits=(self._deltas.hits if self._p13n is not None else 0),
            delta_lookups=(self._deltas.hits + self._deltas.misses
                           if self._p13n is not None else 0),
            delta_evictions=(self._deltas.evictions
                             if self._p13n is not None else 0),
            delta_resident_bytes=(self._deltas.resident_bytes
                                  if self._p13n is not None else 0),
            train_waves=(self._wave_count if self._p13n is not None else 0),
            train_wave_s=(self._wave_s if self._p13n is not None else 0.0),
            wave_losses=(list(self._wave_losses)
                         if self._p13n is not None else []),
            faults_injected=d_faults,
            faults_by_kind=d_kinds,
            retries=self._retry_events,
            sheds=len(shed_rids),
            quarantined=sched.requests_quarantined,
            tokens_quarantined=sched.tokens_quarantined,
            watchdog_kills=self._watchdog_kills,
            stream_errors=self._stream_errors,
            journal_replays=journal_replays,
            stragglers=len(mon.flagged),
            mesh_shards=self.mesh_shards,
            pool_shard_bytes=self._pool_bytes // max(1, self.mesh_shards),
            prefill_s=self._prefill_s,
            decode_s=self._decode_s,
            prefill_tokens=self._prefill_tokens,
            decode_tokens=self._decode_tokens,
        )


def make_random_requests(cfg, n: int, prompt_len: int, gen_len: int,
                         seed: int = 0, **req_kw) -> list[Request]:
    """Uniform-random prompts (token ids, or embeds for embed-input
    frontends) — the synthetic serving workload."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        if cfg.embed_inputs:
            emb = rng.standard_normal(
                (prompt_len, cfg.d_model)).astype(np.float32)
            reqs.append(Request(rid, gen_len, embeds=emb, **req_kw))
        else:
            toks = rng.integers(
                0, cfg.vocab_size, prompt_len).astype(np.int32)
            reqs.append(Request(rid, gen_len, tokens=toks, **req_kw))
    return reqs


def make_shared_prefix_requests(cfg, n: int, prefix_len: int, prompt_len: int,
                                gen_len: int, seed: int = 0) -> list[Request]:
    """Workload with a common `prefix_len`-token prompt prefix (system-
    prompt style): later admissions hit the prefix cache and share pages."""
    assert 0 < prefix_len <= prompt_len
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    reqs = []
    for rid in range(n):
        tail = rng.integers(
            0, cfg.vocab_size, prompt_len - prefix_len).astype(np.int32)
        reqs.append(Request(rid, gen_len,
                            tokens=np.concatenate([prefix, tail])))
    return reqs


def make_branching_prefix_requests(cfg, n: int, prompt_len: int, gen_len: int,
                                   *, page_size: int = 16,
                                   max_prefix_pages: int = 4, branch: int = 2,
                                   zipf_a: float = 1.5,
                                   seed: int = 0) -> list[Request]:
    """Partially-overlapping prefix workload: prompts walk a `branch`-ary
    token tree with zipf-skewed branch popularity (few-shot preambles that
    agree for a while, then diverge), so pairs of requests share SOME page-
    aligned prefix but rarely the whole prompt. This is the workload where
    the radix tree's arbitrary-prefix matching beats whole-chain hashing.
    Page content at each tree position is keyed by the path to it, so equal
    paths yield identical tokens across requests (and across runs)."""
    assert prompt_len > max_prefix_pages * page_size
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, branch + 1) ** zipf_a
    w /= w.sum()
    reqs = []
    for rid in range(n):
        depth = 1 + int(rng.integers(0, max_prefix_pages))
        path: list[int] = []
        pages = []
        for _ in range(depth):
            path.append(int(rng.choice(branch, p=w)))
            pages.append(np.random.default_rng([seed, *path]).integers(
                0, cfg.vocab_size, page_size).astype(np.int32))
        prefix = np.concatenate(pages)
        tail = rng.integers(0, cfg.vocab_size,
                            prompt_len - len(prefix)).astype(np.int32)
        reqs.append(Request(rid, gen_len,
                            tokens=np.concatenate([prefix, tail])))
    return reqs
