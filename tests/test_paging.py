"""Property-based tests for the page allocator and the prefix caches.

Model-based: a python-dict reference tracks who holds references to which
page; after arbitrary op sequences the pool must agree with the model,
never double-free, never leak (releasing every reference returns the pool
to fully-free). The radix tree is additionally checked against a
brute-force longest-common-prefix reference and its own structural audit
(`check()`): refcount conservation, no page leaks, pinned nodes never
evicted, spill -> rehydrate byte-identical. Runs under hypothesis when
installed, and under the seeded-random fallback in `repro.testing`
otherwise — either way the invariants are exercised, not skipped.
"""
import numpy as np
import pytest

from repro.testing import given, settings, st
from repro.serve.paging import (ChainPrefixCache, PagePool, RadixPrefixCache,
                                SpillTier)

PS = 4


# ---------------------------------------------------------------------------
# PagePool: alloc/free/incref/decref/cow_split never double-free, never leak
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(
    ops=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 2 ** 20)),
                 min_size=1, max_size=120),
    num_pages=st.integers(1, 8),
)
def test_page_pool_model(ops, num_pages):
    pool = PagePool(num_pages, PS)
    held = []                       # references we own, with multiplicity
    for op, arg in ops:
        if op == 0 and pool.free_pages:                    # alloc
            held.append(pool.alloc())
        elif op == 1 and held:                             # incref
            pid = held[arg % len(held)]
            pool.incref(pid)
            held.append(pid)
        elif op == 2 and held:                             # decref
            pool.decref(held.pop(arg % len(held)))
        elif op == 3:                                      # cow_split
            shared = sorted({p for p in held if pool.ref[p] >= 2})
            if shared and pool.free_pages:
                pid = shared[arg % len(shared)]
                held.remove(pid)
                held.append(pool.cow_split(pid))
        pool.check()
        assert pool.in_use == len(set(held))
        for pid in set(held):
            assert pool.ref[pid] == held.count(pid)
    for pid in list(held):          # release everything: no page may leak
        pool.decref(pid)
    pool.check()
    assert pool.free_pages == num_pages


def test_page_pool_double_free_raises():
    pool = PagePool(2, PS)
    pid = pool.alloc()
    pool.decref(pid)
    with pytest.raises(RuntimeError, match="double-free"):
        pool.decref(pid)
    pool.check()


def test_cow_split_semantics():
    pool = PagePool(3, PS)
    pid = pool.alloc()
    pool.incref(pid)                # shared between two holders
    new = pool.cow_split(pid)
    assert new != pid
    assert pool.ref[pid] == 1 and pool.ref[new] == 1
    assert pool.cow_splits == 1
    pool.decref(pid)
    pool.decref(new)
    pool.check()
    assert pool.free_pages == 3


def test_alloc_exhausted_raises():
    pool = PagePool(1, PS)
    pool.alloc()
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc()


# ---------------------------------------------------------------------------
# RadixPrefixCache: matching agrees with a brute-force LCP reference, pages
# always hold the claimed content, eviction respects pins and refcounts, and
# spill -> rehydrate is byte-identical
# ---------------------------------------------------------------------------

class _FakeDevice:
    """Stand-in for the engine's layer pools: one host array of token rows,
    written by 'prefill' and moved through the spill reader/writer."""

    def __init__(self, num_pages):
        self.rows = np.zeros((num_pages * PS, 3), np.float32)

    def reader(self, pid):
        return {"rows": self.rows[pid * PS:(pid + 1) * PS].copy()}

    def writer(self, pid, blob):
        self.rows[pid * PS:(pid + 1) * PS] = blob["rows"]

    def fill(self, pid, toks):
        """Page content derived from token content — makes 'does this page
        hold the right rows' checkable after any sharing/spill shuffle."""
        self.rows[pid * PS:(pid + 1) * PS] = \
            np.asarray(toks, np.float32)[:, None]


def _mk_radix(num_pages=64, spill=None, **kw):
    pool = PagePool(num_pages, PS)
    dev = _FakeDevice(num_pages)
    cache = RadixPrefixCache(pool, has_pages=True, reader=dev.reader,
                             writer=dev.writer, spill=spill, **kw)
    return pool, dev, cache


def _submit(pool, dev, cache, toks):
    """Drive one request through the engine's cache protocol: match,
    COW the partial continuation, 'prefill' the uncovered pages, insert.
    Returns the pages the request held (already released)."""
    plen = len(toks)
    mr = cache.match(toks, plen - 1)
    assert mr.covered <= plen - 1
    off = 0
    for pid, fill in mr.pages:      # matched pages hold exactly these rows,
        # whether prefilled here or written back by a rehydrate
        assert np.array_equal(dev.rows[pid * PS:pid * PS + fill, 0],
                              toks[off:off + fill])
        off += fill
    held = [pid for pid, _ in mr.pages]
    n_full = sum(1 for _, f in mr.pages if f == PS)
    if mr.pages and mr.pages[-1][1] < PS:
        if pool.free_pages:         # append => COW the shared page first
            new = pool.cow_split(mr.pages[-1][0])
            lo = (len(held) - 1) * PS
            dev.fill(new, np.resize(toks[lo:], PS))
            held[-1] = new
        else:
            pool.decref(held.pop())
    n_pages = -(-plen // PS)
    while len(held) < n_pages and pool.free_pages:
        pid = pool.alloc()
        lo = len(held) * PS
        dev.fill(pid, np.resize(toks[lo:], PS))
        held.append(pid)
    if len(held) == n_pages:
        reg = cache.insert_pages(toks, plen // PS, held, n_full)
        assert reg == plen // PS
        if plen % PS:
            cache.insert_partial(toks, held[-1])
    cache.release(mr)
    pool.check()
    cache.check()
    for pid in held:                # request finishes
        pool.decref(pid)
    return held


def _brute_force_shared_pages(toks, registered):
    """Reference: full pages of `toks` any fully-registered prompt shares."""
    best = 0
    for r in registered:
        n = 0
        lim = min(len(toks), len(r)) // PS
        while n < lim and np.array_equal(toks[n * PS:(n + 1) * PS],
                                         r[n * PS:(n + 1) * PS]):
            n += 1
        best = max(best, min(n, len(r) // PS))
    return best


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2 ** 31 - 1),
    n_reqs=st.integers(1, 10),
    vocab=st.sampled_from([2, 3, 50]),      # tiny vocab: forced collisions
)
def test_radix_match_vs_brute_force_lcp(seed, n_reqs, vocab):
    rng = np.random.default_rng(seed)
    pool, dev, cache = _mk_radix()
    registered = []                 # prompts whose full pages all landed
    for _ in range(n_reqs):
        plen = int(rng.integers(1, 5 * PS))
        toks = rng.integers(0, vocab, plen).astype(np.int32)
        cap = plen - 1
        mr = cache.match(toks, cap)
        n_full = sum(1 for _, f in mr.pages if f == PS)
        # the tree must find every full page a registered prompt shares
        # (up to the >=1-uncached-token cap) — the radix guarantee the
        # whole-chain design could only give for whole registered chains
        assert n_full >= min(_brute_force_shared_pages(toks, registered),
                             cap // PS)
        cache.abandon(mr, plen)
        held = _submit(pool, dev, cache, toks)
        if len(held) == -(-plen // PS):
            registered.append(toks)
    while cache.evict_one():        # drain: nothing may leak
        pool.check()
        cache.check()
    assert cache.node_count == 0
    assert pool.free_pages == pool.num_pages


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2 ** 31 - 1),
    n_ops=st.integers(5, 30),
)
def test_radix_invariants_under_random_ops(seed, n_ops):
    """Random submit/match/evict/spill/rehydrate interleavings: pool and
    tree audits hold after every op, and a full drain frees every page."""
    rng = np.random.default_rng(seed)
    spill = SpillTier(32)
    pool, dev, cache = _mk_radix(num_pages=16, spill=spill)
    pinned = []                     # live matches (simulated open slots)
    for _ in range(n_ops):
        op = rng.integers(0, 4)
        if op == 0:                 # submit a request end-to-end
            plen = int(rng.integers(1, 4 * PS))
            toks = rng.integers(0, 3, plen).astype(np.int32)
            if pool.free_pages >= -(-plen // PS) + 1:
                _submit(pool, dev, cache, toks)
        elif op == 1 and cache.node_count:      # evict one leaf
            cache.evict_one()
        elif op == 2:               # match and HOLD the pin (open slot)
            plen = int(rng.integers(2, 4 * PS))
            toks = rng.integers(0, 3, plen).astype(np.int32)
            mr = cache.match(toks, plen - 1)
            pinned.append((mr, plen))
        else:                       # close an open slot
            if pinned:
                mr, plen = pinned.pop(int(rng.integers(0, len(pinned))))
                for pid, _ in mr.pages:
                    pool.decref(pid)
                cache.release(mr)
        pool.check()
        cache.check()
    for mr, _ in pinned:
        for pid, _ in mr.pages:
            pool.decref(pid)
        cache.release(mr)
    while cache.evict_one():
        pool.check()
        cache.check()
    assert cache.node_count == 0
    assert pool.free_pages == pool.num_pages


def test_radix_pinned_never_evicted():
    pool, dev, cache = _mk_radix(num_pages=8)
    toks = np.arange(2 * PS, dtype=np.int32)
    _submit(pool, dev, cache, toks)
    mr = cache.match(toks, 2 * PS - 1)          # pins the deepest node
    # matched pages are referenced by the match => nothing evictable
    assert cache.evictable() == 0
    assert not cache.evict_one()
    for pid, _ in mr.pages:
        pool.decref(pid)
    # pages released but the PIN alone must still protect the node
    assert not cache.evict_one()
    cache.release(mr)
    assert cache.evict_one()
    while cache.evict_one():
        pass
    pool.check()
    assert pool.free_pages == pool.num_pages


def test_radix_eviction_is_lru_leaf_first():
    """Two sibling branches: the least-recently-touched leaf goes first,
    and evicting a leaf makes its parent evictable next."""
    pool, dev, cache = _mk_radix(num_pages=16)
    shared = np.arange(PS, dtype=np.int32)
    a = np.concatenate([shared, np.full(PS, 90, np.int32)])
    b = np.concatenate([shared, np.full(PS, 91, np.int32)])
    _submit(pool, dev, cache, a)
    _submit(pool, dev, cache, b)       # splits: shared + 2 leaves
    assert cache.node_count == 3
    # touch branch a AFTER b: b's leaf is now the LRU leaf
    cache.abandon(cache.match(a, 2 * PS - 1), 2 * PS)
    free0 = pool.free_pages
    assert cache.evict_one()
    assert pool.free_pages == free0 + 1
    # branch a must still fully match; b's tail must be gone
    mr = cache.match(a, 2 * PS - 1)
    assert sum(f for _, f in mr.pages) >= 2 * PS - 1
    cache.abandon(mr, 2 * PS)
    mr = cache.match(b, 2 * PS - 1)
    assert mr.covered == PS                     # only the shared page left
    cache.abandon(mr, 2 * PS)
    while cache.evict_one():
        pass
    cache.check()
    pool.check()
    assert pool.free_pages == pool.num_pages


def test_spill_rehydrate_roundtrip_byte_identical():
    """Evicting a node writes its device rows (and snapshot) to the host
    tier; a later match re-attaches them bit-for-bit."""
    spill = SpillTier(16)
    pool, dev, cache = _mk_radix(num_pages=8, spill=spill)
    toks = np.arange(3 * PS, dtype=np.int32)
    held = _submit(pool, dev, cache, toks)
    snap = {"s": np.arange(5, dtype=np.float32), "last": np.ones(2)}
    assert cache.insert_snapshot(toks, 2 * PS, {k: v.copy()
                                                for k, v in snap.items()})
    want_rows = [dev.rows[pid * PS:(pid + 1) * PS].copy()
                 for pid in held[:2]]
    while cache.evict_one():
        pass
    assert cache.node_count == 0 and pool.free_pages == pool.num_pages
    assert len(spill) == 3 and cache.spills >= 3
    dev.rows[:] = -1                            # scramble the device pools
    mr = cache.match(toks, 3 * PS - 1, need_state=True)
    assert cache.rehydrates == 2
    assert mr.covered == 2 * PS and mr.snapshot is not None
    for k in snap:
        assert np.array_equal(mr.snapshot[k], snap[k])
    got = np.concatenate([dev.rows[pid * PS:(pid + 1) * PS]
                          for pid, _ in mr.pages])
    assert np.array_equal(got, np.concatenate(want_rows))
    cache.abandon(mr, 3 * PS)
    cache.check()
    while cache.evict_one():
        pass
    pool.check()
    assert pool.free_pages == pool.num_pages


def test_spill_tier_writeback_queue_bound():
    """The tier is an O(1) LRU writeback queue: overflowing drops the
    least-recently-written entry, re-putting refreshes recency."""
    tier = SpillTier(max_entries=3)
    for i in range(3):
        tier.put(np.asarray([i], np.int32), snap={"x": np.asarray([i])})
    tier.put(np.asarray([0], np.int32), snap={"x": np.asarray([10])})
    tier.put(np.asarray([3], np.int32), snap={"x": np.asarray([3])})
    assert len(tier) == 3 and tier.evicted == 1
    assert tier.peek(np.asarray([1], np.int32)) is None     # LRU dropped
    assert tier.peek(np.asarray([0], np.int32))["snap"]["x"][0] == 10
    assert [int(t[0]) for t, _ in tier.items()] == [2, 0, 3]


def test_stateless_snapshot_cache():
    """Page-less archs (rwkv): nodes carry snapshots only, need_state
    matching clamps to the deepest snapshot boundary, and the snapshot
    budget spills the oldest blob to the tier."""
    pool = PagePool(1, PS)
    spill = SpillTier(8)
    cache = RadixPrefixCache(pool, has_pages=False, spill=spill,
                             snapshot_budget=2)
    toks = np.arange(4 * PS, dtype=np.int32)
    assert cache.wants_snapshot(toks, PS)
    assert not cache.wants_snapshot(toks, PS + 1)       # not page-aligned
    cache.insert_snapshot(toks, PS, {"s": np.full(3, 1.0)})
    assert not cache.wants_snapshot(toks, PS)           # first write wins
    cache.insert_snapshot(toks, 3 * PS, {"s": np.full(3, 3.0)})
    mr = cache.match(toks, 4 * PS - 1, need_state=True)
    assert mr.covered == 3 * PS and mr.snapshot["s"][0] == 3.0
    assert not mr.pages                                  # nothing paged
    cache.release(mr)
    # a diverging prompt only reaches the shallower snapshot
    div = toks.copy()
    div[2 * PS] += 1
    mr = cache.match(div, 4 * PS - 1, need_state=True)
    assert mr.covered == PS and mr.snapshot["s"][0] == 1.0
    cache.abandon(mr, len(div))
    # budget = 2: a third snapshot spills the LRU blob to the host tier
    cache.insert_snapshot(toks, 2 * PS, {"s": np.full(3, 2.0)})
    assert len(cache._snaps) == 2 and cache.spills == 1 and len(spill) == 1
    cache.check()
    while cache.evict_one():
        pass
    assert cache.node_count == 0
    pool.check()


def test_partial_continuations_coexist_only_in_radix():
    """Content-distinct partial continuations of the same full-page spine:
    the radix tree keeps both, the chain baseline's one-slot-per-chain
    design keeps only the first — a strict radix win."""
    base = np.arange(PS, dtype=np.int32)
    p1 = np.concatenate([base, np.asarray([50, 51], np.int32)])
    p2 = np.concatenate([base, np.asarray([60, 61], np.int32)])

    pool, dev, cache = _mk_radix(num_pages=8)
    _submit(pool, dev, cache, p1)
    _submit(pool, dev, cache, p2)
    for q in (p1, p2):
        mr = cache.match(np.append(q, 7).astype(np.int32), len(q))
        assert mr.covered == len(q), q          # full page + its partial
        cache.abandon(mr, len(q) + 1)

    chain_pool = PagePool(8, PS)
    chain = ChainPrefixCache(chain_pool)
    pids = [chain_pool.alloc() for _ in range(3)]
    chain.insert_pages(p1, 1, pids[:1], 0)
    chain.insert_partial(p1, pids[1])
    assert chain.insert_partial(p2, pids[2]) is False   # slot taken
    mr = chain.match(np.append(p2, 7).astype(np.int32), len(p2))
    assert mr.covered == PS                     # partial p2 NOT matched
    chain.abandon(mr, len(p2) + 1)
    while cache.evict_one():
        pass
    pool.check()
    assert pool.free_pages == pool.num_pages


def test_partial_slots_lru_bounded():
    """At most `partial_slots` continuations per spine are retained,
    LRU-displaced beyond that — the tree must not hoard one speculative
    page per historical request (that would push peak page usage ABOVE
    the no-sharing run's)."""
    pool, dev, cache = _mk_radix(num_pages=16)
    base = np.arange(PS, dtype=np.int32)
    tails = [np.concatenate([base, np.asarray([t, t + 1], np.int32)])
             for t in (50, 60, 70)]
    for t in tails:
        _submit(pool, dev, cache, t)
    assert cache.node_count == 3            # spine + partial_slots leaves
    # the oldest partial was displaced: its prompt only matches the spine
    mr = cache.match(np.append(tails[0], 7).astype(np.int32), len(tails[0]))
    assert mr.covered == PS
    cache.abandon(mr, len(tails[0]) + 1)
    for t in tails[1:]:                     # the newer two still hit fully
        mr = cache.match(np.append(t, 7).astype(np.int32), len(t))
        assert mr.covered == len(t), t
        cache.abandon(mr, len(t) + 1)
    while cache.evict_one():
        pass
    pool.check()
    assert pool.free_pages == pool.num_pages


def test_exact_multiple_match_downgrades_last_full_page():
    """fill == 0 edge, match side: an identical exact-multiple prompt must
    reuse the registrant's LAST full page as a ps-1 partial match (the
    >= 1-uncached-token cap blocks a full match), while a prompt whose last
    page differs must not. Checked for BOTH cache implementations."""
    for make in (lambda p: _mk_radix(num_pages=6)[2],
                 ChainPrefixCache):
        pool = PagePool(6, PS)
        cache = make(pool) if make is ChainPrefixCache else None
        if cache is None:
            pool, dev, cache = _mk_radix(num_pages=6)
        toks = np.asarray(range(2 * PS), np.int32)
        pids = [pool.alloc(), pool.alloc()]
        cache.insert_pages(toks, 2, pids, 0)

        mr = cache.match(toks, len(toks) - 1)
        assert mr.covered == 2 * PS - 1
        assert [f for _, f in mr.pages] == [PS, PS - 1]
        assert mr.pages[-1][0] == pids[-1]
        assert pool.ref[pids[-1]] == 3      # holder + cache + this match
        cache.abandon(mr, len(toks))

        # the downgrade is content-gated on the full last page
        diff = toks.copy()
        diff[-1] += 1
        mr = cache.match(diff, len(diff) - 1)
        assert mr.covered == PS and [f for _, f in mr.pages] == [PS]
        for pid, _ in mr.pages:
            pool.decref(pid)
        cache.release(mr)

        # a LONGER prompt sharing the pages must still full-match both (the
        # downgrade only fires when the cap — not a miss — stopped the loop)
        longer = np.concatenate([toks, np.asarray([7, 8], np.int32)])
        mr = cache.match(longer, len(longer) - 1)
        assert mr.covered == 2 * PS and [f for _, f in mr.pages] == [PS, PS]
        for pid, _ in mr.pages:
            pool.decref(pid)
        cache.release(mr)
        for pid in pids:
            pool.decref(pid)
        while cache.evict_one():
            pass
        pool.check()
        assert pool.free_pages == 6


def test_exact_multiple_registers_no_partial():
    """fill == 0 edge: a prompt whose length is an exact page multiple has
    no partially-filled last page — insert_partial must refuse, take no
    pool reference, and add no node."""
    pool, dev, cache = _mk_radix(num_pages=4)
    toks = np.arange(2 * PS, dtype=np.int32)
    pids = [pool.alloc(), pool.alloc()]
    cache.insert_pages(toks, 2, pids, 0)
    refs_before = pool.ref.copy()
    nodes_before = cache.node_count
    assert cache.insert_partial(toks, pids[-1]) is False
    assert (pool.ref == refs_before).all()
    assert cache.node_count == nodes_before
    for pid in pids:
        pool.decref(pid)
    while cache.evict_one():
        pass
    pool.check()
    assert pool.free_pages == 4


def test_prefix_match_is_content_checked():
    """A partial-page entry only matches identical token content."""
    pool, dev, cache = _mk_radix(num_pages=4)
    toks = np.asarray([1, 2, 3, 4, 5, 6], np.int32)     # 1 full + 2 partial
    pids = [pool.alloc(), pool.alloc()]
    cache.insert_pages(toks, 1, pids, 0)
    cache.insert_partial(toks, pids[1])
    same = np.asarray([1, 2, 3, 4, 5, 6, 9], np.int32)
    mr = cache.match(same, len(same) - 1)
    assert mr.covered == 6 and [f for _, f in mr.pages] == [PS, 2]
    for pid, _ in mr.pages:
        pool.decref(pid)
    cache.release(mr)
    diff = np.asarray([1, 2, 3, 4, 5, 7, 9], np.int32)  # partial differs
    mr = cache.match(diff, len(diff) - 1)
    assert mr.covered == PS and [f for _, f in mr.pages] == [PS]
    for pid, _ in mr.pages:
        pool.decref(pid)
    cache.release(mr)
    for pid in pids:
        pool.decref(pid)
    while cache.evict_one():
        pass
    pool.check()
    assert pool.free_pages == 4


# ---------------------------------------------------------------------------
# ChainPrefixCache baseline keeps its original model-based coverage under the
# unified interface
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2 ** 31 - 1),
    n_reqs=st.integers(1, 8),
    vocab=st.sampled_from([2, 3, 50]),
)
def test_chain_prefix_cache_model(seed, n_reqs, vocab):
    rng = np.random.default_rng(seed)
    pool = PagePool(64, PS)
    cache = ChainPrefixCache(pool)
    content = {}
    for _ in range(n_reqs):
        plen = int(rng.integers(1, 4 * PS))
        toks = rng.integers(0, vocab, plen).astype(np.int32)
        mr = cache.match(toks, plen - 1)
        assert mr.covered <= plen - 1
        off = 0
        for pid, fill in mr.pages:
            assert content[pid][:fill * 4] == np.ascontiguousarray(
                toks[off:off + fill]).tobytes()[:fill * 4]
            off += fill
        held = [pid for pid, _ in mr.pages]
        n_full = sum(1 for _, f in mr.pages if f == PS)
        if mr.pages and mr.pages[-1][1] < PS:
            if pool.free_pages:
                new = pool.cow_split(mr.pages[-1][0])
                lo = (len(held) - 1) * PS
                content[new] = np.ascontiguousarray(
                    toks[lo:lo + PS]).tobytes()
                held[-1] = new
            else:
                pool.decref(held.pop())
        n_pages = -(-plen // PS)
        while len(held) < n_pages and pool.free_pages:
            pid = pool.alloc()
            lo = len(held) * PS
            content[pid] = np.ascontiguousarray(toks[lo:lo + PS]).tobytes()
            held.append(pid)
        if len(held) == n_pages:
            reg = cache.insert_pages(toks, plen // PS, held, n_full)
            assert reg == plen // PS
            if plen % PS and rng.random() < 0.7:
                cache.insert_partial(toks, held[-1])
        pool.check()
        for pid in held:
            pool.decref(pid)
        pool.check()
    while cache.evict_one():
        pool.check()
    assert pool.free_pages == pool.num_pages
