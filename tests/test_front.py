"""Front-door chaos + parity suite (drives serve/front.py via dist/chaos.py).

Two invariants, proven under injected shard failures, shard stalls longer
than the dispatcher timeout, 4x-capacity queue floods, and clock skew:

  * no request is ever silently dropped — every submitted ticket resolves
    with exactly one explicit status, and the stats ledger balances
    (submitted == served_exact + served_degraded + shed);
  * non-degraded responses are bit-identical to `engine.search_batch` —
    docs, positions, fallback flags, ranked float32 scores, and the
    postings_read accounting, for single- AND multi-shard backends.
"""
import time

import numpy as np
import pytest

from repro.core.api import (MODE_NEAR, MODE_PHRASE, STATUS_SERVED_DEGRADED,
                            STATUS_SERVED_EXACT, STATUS_SHED, SearchRequest)
from repro.dist.chaos import ChaosShard, SkewedClock, flood
from repro.dist.fault_tolerance import ShardDispatcher, merge_topk
from repro.serve.front import (FrontDoor, FrontDoorConfig, ShardBackend,
                               build_doc_shards, merge_shard_responses)

# generous enough that first-call jit compiles never masquerade as stalls
SLOW = 300.0
FAST_CFG = dict(default_deadline_ms=600_000.0, shard_timeout_s=SLOW)


def _requests(corpus, n=48, ranked_every=3, seed=11):
    """Phrase/near/ranked mix with known source docs (so hits are nonempty)."""
    rng = np.random.default_rng(seed)
    reqs = []
    d = 0
    while len(reqs) < n:
        d = (d + 7) % corpus.n_docs
        toks = np.asarray(corpus.doc(d))
        if len(toks) < 12:
            continue
        st = int(rng.integers(0, len(toks) - 8))
        k = int(rng.integers(2, 4))
        i = len(reqs)
        if ranked_every and i % ranked_every == 2:
            reqs.append(SearchRequest(tuple(int(x) for x in toks[st:st + k]),
                                      mode=MODE_PHRASE, rank=True, top_k=10))
        elif i % 2:
            reqs.append(SearchRequest(
                tuple(int(x) for x in toks[st:st + 2 * k:2]),
                mode=MODE_NEAR, window=6))
        else:
            reqs.append(SearchRequest(tuple(int(x) for x in toks[st:st + k]),
                                      mode=MODE_PHRASE))
    return reqs


def _assert_identical(ref, got):
    assert np.array_equal(ref.doc, got.doc)
    assert np.array_equal(ref.pos, got.pos)
    assert ref.postings_read == got.postings_read
    assert ref.used_fallback == got.used_fallback
    assert ref.doc_only == got.doc_only
    assert ref.subplan_types == got.subplan_types
    assert ref.ranked == got.ranked
    if ref.ranked:
        assert np.array_equal(ref.doc_ids, got.doc_ids)
        assert np.array_equal(ref.doc_scores, got.doc_scores)
        assert np.array_equal(ref.anchor_scores, got.anchor_scores)


def _ledger_balances(front):
    st = front.stats
    assert st.responded == st.submitted, \
        f"silent drop: {st.submitted} submitted, {st.responded} responded"


@pytest.fixture(scope="module")
def shard_world(small_world):
    corpus, index = small_world["corpus"], small_world["index"]
    backends, replicas = build_doc_shards(corpus, index, 4, replicate=True)
    return {"corpus": corpus, "index": index, "engine": small_world["engine"],
            "backends": backends, "replicas": replicas,
            "requests": _requests(corpus),
            }


@pytest.fixture(scope="module")
def reference(shard_world):
    return shard_world["engine"].search_batch(shard_world["requests"])


# ---------------------------------------------------------------------------
# parity: SERVED_EXACT == engine.search_batch, bit for bit
# ---------------------------------------------------------------------------


def test_front_single_shard_bit_identical(shard_world, reference):
    front = FrontDoor(shard_world["index"], cfg=FrontDoorConfig(**FAST_CFG))
    try:
        got = front.search_batch(shard_world["requests"])
        for ref, g in zip(reference, got):
            assert g.status == STATUS_SERVED_EXACT
            assert g.shards == (0,)
            _assert_identical(ref, g)
        _ledger_balances(front)
        assert front.stats.shed == 0
    finally:
        front.close()


def test_front_multi_shard_bit_identical(shard_world, reference):
    front = FrontDoor(shard_world["index"], backends=shard_world["backends"],
                      cfg=FrontDoorConfig(cache_capacity=0, **FAST_CFG))
    try:
        got = front.search_batch(shard_world["requests"])
        for ref, g in zip(reference, got):
            assert g.status == STATUS_SERVED_EXACT
            assert g.shards == (0, 1, 2, 3)
            _assert_identical(ref, g)
        _ledger_balances(front)
    finally:
        front.close()


def test_front_flex_overflow_exact(shard_world, small_world):
    """A plan wider than the batched executor's caps routes through the flex
    bucket and still comes back SERVED_EXACT + bit-identical."""
    from repro.core.batch_executor import G_CAP
    corpus, eng = shard_world["corpus"], shard_world["engine"]
    req = None
    for d in range(corpus.n_docs):
        toks = corpus.doc(d)
        for st in range(0, max(len(toks) - G_CAP - 3, 0), 4):
            q = toks[st:st + G_CAP + 3].tolist()
            plan = eng.plan(q, mode=MODE_PHRASE)
            # stop words become checks, not groups: need a window whose plan
            # really carries > G_CAP AND-groups in one subplan
            if any(sp.supported and len(sp.groups) > G_CAP
                   for sp in plan.subplans):
                req = SearchRequest(q, mode=MODE_PHRASE)
                break
        if req is not None:
            break
    assert req is not None, "no >G_CAP-group windows found"
    ref = eng.search_batch([req])[0]
    front = FrontDoor(shard_world["index"], cfg=FrontDoorConfig(**FAST_CFG))
    try:
        got = front.search(req)
        assert got.status == STATUS_SERVED_EXACT
        _assert_identical(ref, got)
        assert front.stats.flex_routed >= 1
    finally:
        front.close()


def test_front_cache_hit(shard_world, reference):
    front = FrontDoor(shard_world["index"],
                      cfg=FrontDoorConfig(cache_capacity=16, **FAST_CFG))
    try:
        req = shard_world["requests"][0]
        first = front.search(req)
        assert not first.cached
        again = front.search(req)
        assert again.cached and again.status == STATUS_SERVED_EXACT
        assert front.stats.cache_hits == 1
        _assert_identical(first, again)
        _assert_identical(reference[0], again)
    finally:
        front.close()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_front_rate_limit_sheds_explicitly(shard_world):
    front = FrontDoor(shard_world["index"],
                      cfg=FrontDoorConfig(rate_per_s=0.001, rate_burst=3,
                                          cache_capacity=0, **FAST_CFG))
    try:
        reqs = shard_world["requests"][:12]
        tickets = flood(front, reqs, client="greedy")
        resps = [t.result() for t in tickets]
        shed = [r for r in resps if r.status == STATUS_SHED]
        ok = [r for r in resps if r.status != STATUS_SHED]
        assert len(ok) == 3 and len(shed) == 9
        assert all(r.shed_reason == "rate_limited" for r in shed)
        # a second client has its own bucket
        other = front.search(reqs[0], client="polite")
        assert other.status == STATUS_SERVED_EXACT
        _ledger_balances(front)
    finally:
        front.close()


def test_front_queue_flood_no_silent_drops(shard_world, reference):
    """4x-capacity flood while a chaos shard pins the dispatcher: every
    ticket resolves; overflow is shed with reason queue_full; everything
    that was admitted is served bit-exactly once the stall clears."""
    chaos = ChaosShard(ShardBackend(shard_world["index"]), stall_s=1.0)
    front = FrontDoor(shard_world["index"], backends=[chaos],
                      cfg=FrontDoorConfig(max_queue=8, max_batch=4,
                                          cache_capacity=0, **FAST_CFG))
    try:
        reqs = (shard_world["requests"] * 2)[:64]    # 8x queue capacity
        tickets = flood(front, reqs, wait=False)
        resps = [t.result(timeout=SLOW) for t in tickets]
        statuses = {}
        for r in resps:
            statuses[(r.status, r.shed_reason)] = \
                statuses.get((r.status, r.shed_reason), 0) + 1
        assert statuses.get((STATUS_SHED, "queue_full"), 0) > 0
        served = [i for i, r in enumerate(resps)
                  if r.status == STATUS_SERVED_EXACT]
        assert served, statuses
        ref_all = {i: r for i, r in enumerate(reference)}
        for i in served:
            _assert_identical(ref_all[i % len(reference)], resps[i])
        # the ledger balances: nothing hung, nothing vanished
        _ledger_balances(front)
        assert front.stats.shed == statuses.get((STATUS_SHED, "queue_full"), 0)
    finally:
        chaos.set()
        front.close()


def test_front_clock_skew_deadline_shed(shard_world):
    """Queued requests admitted under one clock become unmeetable when the
    clock steps forward (NTP jump / long pause): they shed with reason
    deadline instead of burning the whole batch's budget."""
    clock = SkewedClock()
    stall = ChaosShard(ShardBackend(shard_world["index"]), stall_s=1.5)
    front = FrontDoor(shard_world["index"], backends=[stall],
                      cfg=FrontDoorConfig(default_deadline_ms=5000.0,
                                          shard_timeout_s=SLOW, max_batch=2,
                                          cache_capacity=0),
                      clock=clock)
    try:
        reqs = shard_world["requests"][:8]
        tickets = [front.submit(r) for r in reqs]
        clock.skew_s = 30.0          # every queued deadline is now in the past
        resps = [t.result(timeout=SLOW) for t in tickets]
        assert any(r.status == STATUS_SHED and r.shed_reason == "deadline"
                   for r in resps)
        assert all(r.status in (STATUS_SHED, STATUS_SERVED_EXACT,
                                STATUS_SERVED_DEGRADED) for r in resps)
        _ledger_balances(front)
    finally:
        stall.set()
        front.close()


# ---------------------------------------------------------------------------
# degradation: shard failure, stall, replica rescue
# ---------------------------------------------------------------------------


def test_front_replica_rescues_failed_primary(shard_world, reference):
    """Primary shard 1 fails hard; its replica absorbs the re-dispatch and
    the responses stay SERVED_EXACT and bit-identical."""
    backends = [ChaosShard(b) for b in shard_world["backends"]]
    backends[1].set(fail=True)
    front = FrontDoor(shard_world["index"], backends=backends,
                      replicas=shard_world["replicas"],
                      cfg=FrontDoorConfig(cache_capacity=0, **FAST_CFG))
    try:
        reqs = shard_world["requests"][:16]
        got = front.search_batch(reqs)
        for ref, g in zip(reference[:16], got):
            assert g.status == STATUS_SERVED_EXACT
            assert g.shards == (0, 1, 2, 3)
            _assert_identical(ref, g)
        assert front.dispatcher.stats.redispatched > 0
        assert backends[1].calls > 0
        _ledger_balances(front)
    finally:
        front.close()


def test_front_dead_shard_degrades_explicitly(shard_world, reference):
    """Shard 2 stalls past the dispatcher timeout with NO replica: responses
    degrade explicitly — status SERVED_DEGRADED, contributing shards listed,
    and no doc from the dead shard's range is fabricated."""
    backends = [ChaosShard(b) for b in shard_world["backends"]]
    backends[2].set(stall_s=8.0)
    lo = shard_world["backends"][2].doc_base
    hi = lo + shard_world["backends"][2].n_docs
    front = FrontDoor(shard_world["index"], backends=backends,
                      cfg=FrontDoorConfig(default_deadline_ms=600_000.0,
                                          shard_timeout_s=1.0, max_retries=1,
                                          retry_backoff_ms=5.0,
                                          cache_capacity=0))
    try:
        reqs = shard_world["requests"][:8]
        got = front.search_batch(reqs)
        for ref, g in zip(reference[:8], got):
            assert g.status == STATUS_SERVED_DEGRADED
            assert g.shed_reason == "shards"
            assert g.shards == (0, 1, 3)
            docs = g.doc[g.doc >= 0]
            assert not np.any((docs >= lo) & (docs < hi))
            # the live shards' contribution is exactly the reference minus
            # the dead range
            keep = (ref.doc < lo) | (ref.doc >= hi)
            if not ref.doc_only and not g.doc_only:
                assert np.array_equal(ref.doc[keep], g.doc)
                assert np.array_equal(ref.pos[keep], g.pos)
        # bounded retry actually ran, and never un-degraded the result
        assert front.stats.retries > 0
        _ledger_balances(front)
        assert front.stats.served_degraded == len(reqs)
    finally:
        backends[2].set()
        front.close()


def test_front_all_shards_down_still_responds(shard_world):
    chaos = ChaosShard(ShardBackend(shard_world["index"]), fail=True)
    front = FrontDoor(shard_world["index"], backends=[chaos],
                      cfg=FrontDoorConfig(default_deadline_ms=600_000.0,
                                          shard_timeout_s=2.0, max_retries=1,
                                          retry_backoff_ms=5.0,
                                          cache_capacity=0))
    try:
        got = front.search_batch(shard_world["requests"][:4])
        for g in got:
            assert g.status == STATUS_SERVED_DEGRADED
            assert g.shed_reason == "no_shards"
            assert g.shards == () and len(g.doc) == 0
        _ledger_balances(front)
    finally:
        chaos.set()
        front.close()


# ---------------------------------------------------------------------------
# satellite: ShardDispatcher merge path under concurrent replica failure +
# timeout, against real serve arenas (the doc-sharded backends)
# ---------------------------------------------------------------------------


def test_dispatcher_concurrent_stall_and_fail(shard_world):
    """Three concurrent fault modes in ONE dispatch: shard 0 healthy,
    shard 1 stalls past timeout but its replica is healthy (rescued),
    shard 2 fails hard AND its replica fails (lost)."""
    b = shard_world["backends"]
    primaries = [ChaosShard(b[0]), ChaosShard(b[1], stall_s=6.0),
                 ChaosShard(b[2], fail=True)]
    replicas = [ChaosShard(shard_world["replicas"][0]),
                ChaosShard(shard_world["replicas"][1]),
                ChaosShard(shard_world["replicas"][2], fail=True)]
    d = ShardDispatcher(primaries, replica_fns=replicas, timeout=1.5)
    reqs = shard_world["requests"][:6]
    try:
        out = d.dispatch(reqs)
        assert out[0] is not None
        assert out[1] is not None          # replica rescued the straggler
        assert out[2] is None              # primary AND replica down
        assert replicas[1].calls == 1 and replicas[2].calls == 1
        assert d.stats.redispatched == 2 and d.stats.failed == 1
        # the rescued shard's answers match a direct call to the replica
        direct = shard_world["replicas"][1](reqs)
        for x, y in zip(out[1], direct):
            _assert_identical(x, y)
        # subset re-dispatch heals the lost shard once chaos clears
        primaries[2].set()
        again = d.dispatch(reqs, shards=[2])
        assert again[2] is not None and again[0] is None and again[1] is None
    finally:
        primaries[1].set()
        d.close()


def test_dispatcher_merge_topk_real_ranked_outputs(shard_world):
    """merge_topk over real per-shard ranked outputs equals the global
    ranked doc list (scores are per-doc sums, disjoint across doc shards)."""
    req = next(r for r in shard_world["requests"] if r.rank)
    per_shard = [b([req])[0] for b in shard_world["backends"]]
    # positional hits win over shard-local doc-only fallbacks (the same
    # have_pos gating merge_shard_responses applies)
    rows = [np.stack([r.doc_scores.astype(np.float64),
                      r.doc_ids.astype(np.float64)], axis=1)
            for r in per_shard
            if not r.doc_only and r.doc_ids is not None and len(r.doc_ids)]
    merged = merge_topk(rows, k=req.top_k)
    ref = shard_world["engine"].search_batch([req])[0]
    assert len(merged) == len(ref.doc_ids)
    np.testing.assert_allclose(merged[:, 0],
                               np.sort(ref.doc_scores)[::-1], rtol=0)
    assert set(merged[:, 1].astype(int)) == set(int(x) for x in ref.doc_ids)


# ---------------------------------------------------------------------------
# satellite: serve-tier slab sizing derived from the plan population
# ---------------------------------------------------------------------------


def test_serve_tier_ladder_kills_dead_slab_rows(small_world):
    """The packed unpack no longer runs over dead slab rows: with the
    G=8/F=8/T=2*queries caps, a smoke workload's steps use pow2-tight row
    counts and population-derived (G, F, P0, P) tiers."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve.search_serve import SearchServe, SearchServeConfig

    corpus, index = small_world["corpus"], small_world["index"]
    cfg = SearchServeConfig(queries=16, postings_pad=4096, seed_pad=1024,
                            n_basic=1, n_expanded=1, n_stop=1, n_first=1,
                            n_multi=1)
    serve = SearchServe(index, cfg, make_host_mesh(data=1, model=1))
    reqs = _requests(corpus, n=16)
    got = serve.search_batch(reqs)
    ref = small_world["engine"].search_batch(reqs)
    for x, y in zip(ref, got):
        _assert_identical(x, y)
    st = serve.executor.slab_stats
    assert st["steps"] > 0
    # tight T: pow2 padding bounds dead rows per step
    assert st["slab_rows"] <= 2 * st["live_rows"] + 4 * st["steps"]
    # population-derived tiers: the slab is far below the cap slab the old
    # fixed shapes would have billed (T=32 rows x G8/F8/P0=1024/P=4096)
    cap_elems = st["steps"] * cfg.task_rows * (
        cfg.fetch_slots * cfg.p_seed
        + (cfg.groups - 1) * cfg.fetch_slots * cfg.postings_pad)
    assert st["slab_elems"] < cap_elems / 4
    assert len(serve.executor._tiers) <= 3


# ---------------------------------------------------------------------------
# mutable index: segment ingest vs the result cache, late-shard backfill
# ---------------------------------------------------------------------------


def test_front_segment_ingest_never_serves_stale_cache(small_world):
    """THE stale-cache regression: a cached response must never survive a
    segment ingest.  Query before ingest (cached), ingest a batch containing
    a new matching doc, re-query — the response must be fresh (non-cached),
    contain the new doc, and the stale tripwire must stay at zero."""
    from repro.core.segments import SegmentManager, corpus_batches

    corpus, index = small_world["corpus"], small_world["index"]
    batches = corpus_batches(corpus, 4)
    pre_docs = sum(b.n_docs for b in batches[:3])
    mgr = SegmentManager(small_world["lex"], small_world["ana"],
                         params=index.params, auto_merge=False)
    for b in batches[:3]:
        mgr.ingest(b)
    # query sourced from a batch-4 doc (not yet ingested)
    d_new = pre_docs + batches[3].n_docs // 2
    toks = corpus.doc(d_new)
    req = SearchRequest(tuple(int(x) for x in toks[4:7]), mode=MODE_PHRASE)
    front = FrontDoor(segments=mgr,
                      cfg=FrontDoorConfig(cache_capacity=16, **FAST_CFG))
    try:
        first = front.search(req)
        assert first.status == STATUS_SERVED_EXACT and not first.cached
        assert all(int(x) < pre_docs for x in first.doc)
        again = front.search(req)
        assert again.cached and front.stats.cache_hits == 1

        mgr.ingest(batches[3])              # the index just changed

        fresh = front.search(req)
        assert not fresh.cached, "served a pre-ingest cached response"
        assert fresh.status == STATUS_SERVED_EXACT
        assert d_new in set(int(x) for x in fresh.doc)
        # bit-identical to the one-shot engine over the full corpus
        ref = small_world["engine"].search_batch([req])[0]
        assert np.array_equal(ref.doc, fresh.doc)
        assert np.array_equal(ref.pos, fresh.pos)
        assert ref.used_fallback == fresh.used_fallback
        assert ref.doc_only == fresh.doc_only
        # the new generation caches normally
        again2 = front.search(req)
        assert again2.cached and np.array_equal(fresh.doc, again2.doc)
        assert front.stats.generation_bumps >= 1
        assert front.stats.stale_cache_hits == 0
        _ledger_balances(front)
    finally:
        front.close()
        mgr.close()


def test_front_late_shard_backfills_cache(shard_world, reference):
    """A shard that answers AFTER the dispatch timeout degrades the delivered
    response — but its work is not thrown away: the straggler's result
    re-merges into the cache, and the next identical query is SERVED_EXACT
    and bit-identical to the unsharded engine."""
    backends = [ChaosShard(b) for b in shard_world["backends"]]
    backends[1].set(stall_s=3.0)
    front = FrontDoor(shard_world["index"], backends=backends,
                      cfg=FrontDoorConfig(default_deadline_ms=600_000.0,
                                          shard_timeout_s=1.0, max_retries=0,
                                          cache_capacity=16))
    try:
        req = shard_world["requests"][0]
        got = front.search(req)
        assert got.status == STATUS_SERVED_DEGRADED
        assert got.shed_reason == "shards"
        assert got.shards == (0, 2, 3)
        # the straggler finishes ~2s later and backfills the cache
        deadline = time.monotonic() + SLOW
        while front.stats.backfilled < 1:
            assert time.monotonic() < deadline, "backfill never landed"
            time.sleep(0.02)
        again = front.search(req)
        assert again.cached and again.status == STATUS_SERVED_EXACT
        assert again.shards == (0, 1, 2, 3)
        _assert_identical(reference[0], again)
        assert front.stats.stale_cache_hits == 0
        _ledger_balances(front)
    finally:
        backends[1].set()
        front.close()


# ---------------------------------------------------------------------------
# open-loop smoke: offered load through the front door, shed_rate == 0
# ---------------------------------------------------------------------------


def test_front_open_loop_smoke_no_shedding(shard_world):
    """Paced offered load at smoke scale: everything served exactly, nothing
    shed, p99 under a generous deadline (the CI gate in stricter form runs
    in the bench smoke)."""
    front = FrontDoor(shard_world["index"],
                      cfg=FrontDoorConfig(default_deadline_ms=30_000.0,
                                          shard_timeout_s=SLOW,
                                          cache_capacity=0))
    try:
        reqs = shard_world["requests"][:24]
        front.search_batch(reqs)     # warm the jit caches
        front.stats = type(front.stats)()   # count the paced requests only
        tickets = []
        for r in reqs:
            tickets.append(front.submit(r))
            time.sleep(0.005)
        deadline = time.monotonic() + SLOW
        while front.stats.responded < front.stats.submitted:
            assert time.monotonic() < deadline, "front door hung"
            time.sleep(0.01)
        assert front.stats.shed == 0
        assert front.stats.served_degraded == 0
        lat = [t.result(0).latency_ms for t in tickets]
        assert np.percentile(lat, 99) <= 30_000.0
        _ledger_balances(front)
    finally:
        front.close()


def test_front_kword_ingest_never_serves_stale_cache(small_world):
    """K-word twin of the stale-cache regression: a cached kword response
    must never survive a segment ingest — re-query post-ingest is fresh,
    EXACT, contains the newly ingested source doc, and is bit-identical to
    the one-shot engine over the full corpus."""
    from repro.core.segments import SegmentManager, corpus_batches

    corpus, index = small_world["corpus"], small_world["index"]
    batches = corpus_batches(corpus, 4)
    pre_docs = sum(b.n_docs for b in batches[:3])
    mgr = SegmentManager(small_world["lex"], small_world["ana"],
                         params=index.params, auto_merge=False)
    for b in batches[:3]:
        mgr.ingest(b)
    # kword query sourced from a batch-4 doc (not yet ingested)
    d_new = pre_docs + batches[3].n_docs // 2
    toks = corpus.doc(d_new)
    req = SearchRequest(tuple(int(x) for x in toks[4:8]), mode="kword",
                        window=5)
    front = FrontDoor(segments=mgr,
                      cfg=FrontDoorConfig(cache_capacity=16, **FAST_CFG))
    try:
        first = front.search(req)
        assert first.status == STATUS_SERVED_EXACT and not first.cached
        assert all(int(x) < pre_docs for x in first.doc)
        again = front.search(req)
        assert again.cached and front.stats.cache_hits == 1

        mgr.ingest(batches[3])              # the index just changed

        fresh = front.search(req)
        assert not fresh.cached, "served a pre-ingest cached kword response"
        assert fresh.status == STATUS_SERVED_EXACT
        assert d_new in set(int(x) for x in fresh.doc)
        ref = small_world["engine"].search_batch([req])[0]
        assert np.array_equal(ref.doc, fresh.doc)
        assert np.array_equal(ref.pos, fresh.pos)
        assert ref.used_fallback == fresh.used_fallback
        assert ref.doc_only == fresh.doc_only
        # postings_read deliberately unasserted: the segment union plans
        # with the manager's own occ stats (same bits, different accounting)
        again2 = front.search(req)
        assert again2.cached and np.array_equal(fresh.doc, again2.doc)
        assert front.stats.generation_bumps >= 1
        assert front.stats.stale_cache_hits == 0
        _ledger_balances(front)
    finally:
        front.close()
        mgr.close()
