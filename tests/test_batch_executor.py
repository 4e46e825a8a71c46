"""Batched executor: search_batch must agree with per-query search (the
flexible executor) and with the brute-force oracle on mixed Type 1-4 query
batches, including doc-only fallback queries inside a batch; and the Pallas
banded-intersect path must agree with the ref path on re-based int32 keys."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdditionalIndexEngine, BatchExecutor,
                        SearchRequest, brute_force_search)
from repro.core.planner import MODE_NEAR, MODE_PHRASE
from repro.kernels import ops


def _mixed_batch(small_world, n=50, seed=11):
    """Phrase + near queries sampled from indexed docs (the paper's 2.1/2.2
    procedure) plus hand-picked stop-heavy queries for Type 1/4 coverage."""
    corpus = small_world["corpus"]
    lex = small_world["lex"]
    ana = small_world["ana"]
    rng = np.random.default_rng(seed)
    queries, modes = [], []
    while len(queries) < n:
        d = int(rng.integers(corpus.n_docs))
        toks = corpus.doc(d)
        k = int(rng.integers(3, 6))
        if len(toks) < 2 * k + 2:
            continue
        st = int(rng.integers(0, len(toks) - 2 * k))
        queries.append(toks[st:st + k].tolist())
        modes.append(MODE_PHRASE)
        if len(queries) < n:
            queries.append(toks[st:st + 2 * k:2].tolist())
            modes.append(MODE_NEAR)
    # short queries: single-word (one-group task) and two-word
    t0 = corpus.doc(0)
    queries.append([int(t0[0])])
    modes.append(MODE_PHRASE)
    queries.append([int(t0[0]), int(t0[1])])
    modes.append(MODE_PHRASE)
    # stop-run (Type 1) and stop-mixed (Type 4) windows, if the corpus has any
    stops = 0
    for d in range(corpus.n_docs):
        toks = corpus.doc(d)
        forms = ana.primary[toks]
        is_stop = np.asarray(lex.is_stop(forms))
        for st in range(len(toks) - 3):
            if is_stop[st:st + 3].all() and stops < 4:
                queries.append(toks[st:st + 3].tolist())
                modes.append(MODE_PHRASE)
                stops += 1
        if stops >= 4:
            break
    return queries, modes


def _same_result(r1, r2) -> bool:
    return (np.array_equal(r1.doc, r2.doc) and np.array_equal(r1.pos, r2.pos)
            and r1.postings_read == r2.postings_read
            and r1.used_fallback == r2.used_fallback
            and r1.doc_only == r2.doc_only
            and r1.subplan_types == r2.subplan_types)


def test_search_batch_matches_per_query(small_world):
    eng = small_world["engine"]
    queries, modes = _mixed_batch(small_world)
    batch = eng.search_batch([SearchRequest(q, mode=m)
                              for q, m in zip(queries, modes)])
    assert len(batch) == len(queries)
    for q, m, got in zip(queries, modes, batch):
        want = eng.search(SearchRequest(q, mode=m))
        assert _same_result(want, got), (q, m)


def test_search_batch_matches_per_query_ordinary(small_world):
    base = small_world["ordinary"]
    queries, modes = _mixed_batch(small_world, n=24, seed=3)
    batch = base.search_batch([SearchRequest(q, mode=m)
                               for q, m in zip(queries, modes)])
    for q, m, got in zip(queries, modes, batch):
        want = base.search(SearchRequest(q, mode=m))
        assert _same_result(want, got), (q, m)


def test_search_batch_matches_brute_force(small_world):
    """Positional results (or the doc-only fallback set) against the
    O(corpus) oracle, per query of a mixed batch."""
    eng = small_world["engine"]
    corpus, index = small_world["corpus"], small_world["index"]
    queries, modes = _mixed_batch(small_world, n=20, seed=5)
    batch = eng.search_batch([SearchRequest(q, mode=m)
                              for q, m in zip(queries, modes)])
    for q, m, r in zip(queries, modes, batch):
        positional, doc_level = brute_force_search(corpus, index, q, mode=m)
        if r.doc_only:
            assert set(r.doc.tolist()) == doc_level, (q, m)
        else:
            got = set(zip(r.doc.tolist(), r.pos.tolist()))
            assert got == positional, (q, m)


def test_search_batch_fallback_queries_in_batch(small_world):
    """Queries that positionally miss (scrambled word order across docs) must
    fall back to doc-only results inside a batch, exactly like per-query."""
    corpus = small_world["corpus"]
    eng = small_world["engine"]
    rng = np.random.default_rng(23)
    queries = []
    for _ in range(8):
        d1, d2 = rng.integers(corpus.n_docs, size=2)
        t1, t2 = corpus.doc(int(d1)), corpus.doc(int(d2))
        if len(t1) < 8 or len(t2) < 8:
            continue
        queries.append([int(t1[3]), int(t2[5]), int(t1[7])])
    assert queries
    batch = eng.search_batch([SearchRequest(q) for q in queries])
    n_fallback = 0
    for q, r in zip(queries, batch):
        want = eng.search(SearchRequest(q, mode=MODE_PHRASE))
        assert _same_result(want, r)
        n_fallback += int(r.used_fallback)
    assert n_fallback > 0    # the batch did exercise the fallback path


def test_search_batch_pallas_matches_ref(small_world):
    eng_p = AdditionalIndexEngine(small_world["index"], batch_impl="pallas")
    eng_r = small_world["engine"]
    queries, modes = _mixed_batch(small_world, n=16, seed=7)
    reqs = [SearchRequest(q, mode=m) for q, m in zip(queries, modes)]
    bp = eng_p.search_batch(reqs)
    br = eng_r.search_batch(reqs)
    for a, b in zip(bp, br):
        assert np.array_equal(a.doc, b.doc) and np.array_equal(a.pos, b.pos)


@pytest.mark.parametrize("kind", ["ranked", "kword", "ranked_kword"])
def test_search_batch_pallas_matches_ref_scored(small_world, kword_queries,
                                                kind):
    """The Pallas kernels (interpret mode here; compiled on a TPU, where
    they are the default) give the ref path's bits on the scoring and
    K-word bucket steps too: min-delta scores, the delta-mask span join,
    postings accounting."""
    from repro.core.kword import MODE_KWORD
    eng_p = AdditionalIndexEngine(small_world["index"], batch_impl="pallas")
    eng_r = small_world["engine"]
    if kind == "ranked":
        queries, modes = _mixed_batch(small_world, n=8, seed=29)
        reqs = [SearchRequest(q, mode=m, rank=True, top_k=5)
                for q, m in zip(queries, modes)]
    else:
        reqs = [SearchRequest(q, mode=MODE_KWORD, window=w,
                              rank=kind == "ranked_kword")
                for q, w, _ in kword_queries[:8]]
    for a, b in zip(eng_p.search_batch(reqs), eng_r.search_batch(reqs)):
        assert _same_result(a, b)
        assert np.array_equal(a.doc_ids, b.doc_ids)
        assert np.array_equal(a.doc_scores, b.doc_scores)
        assert np.array_equal(a.anchor_scores, b.anchor_scores)


def test_search_batch_max_results(small_world):
    eng = small_world["engine"]
    queries, modes = _mixed_batch(small_world, n=6, seed=13)
    batch = eng.search_batch([SearchRequest(q, mode=m, top_k=2)
                              for q, m in zip(queries, modes)])
    for q, m, r in zip(queries, modes, batch):
        want = eng.search(SearchRequest(q, mode=m, top_k=2))
        assert np.array_equal(want.doc, r.doc)
        assert len(r.doc) <= 2


def test_batch_executor_flex_escape_hatch(small_world):
    """Plans exceeding the table caps route through the flexible executor
    with identical results."""
    import repro.core.batch_executor as bx
    eng = small_world["engine"]
    queries, modes = _mixed_batch(small_world, n=8, seed=17)
    be = BatchExecutor(small_world["index"], flex=eng.executor)
    old_cap, old_split = bx.P_CAP, bx.F_SPLIT_CAP
    bx.P_CAP = 1          # every fetch must split per posting...
    bx.F_SPLIT_CAP = 2    # ...and immediately overflows the slots => flex
    try:
        plans = [eng.plan(q, mode=m) for q, m in zip(queries, modes)]
        # every real posting list (length > 2) overflows the split slots
        assert sum(not be._build_tasks(i, p, [])
                   for i, p in enumerate(plans)) >= len(plans) // 2
        got = be.execute_batch(plans)
    finally:
        bx.P_CAP, bx.F_SPLIT_CAP = old_cap, old_split
    for q, m, r in zip(queries, modes, got):
        want = eng.search(SearchRequest(q, mode=m))
        assert _same_result(want, r)


# ---------------------------------------------------------------------------
# fallback boundaries: each escape hatch routes to flex AND matches the
# brute-force oracle; the lifted postings cap stays on the batched path
# ---------------------------------------------------------------------------


def _assert_oracle(small_world, q, m, r):
    positional, doc_level = brute_force_search(
        small_world["corpus"], small_world["index"], q, mode=m)
    if r.doc_only:
        assert set(r.doc.tolist()) == doc_level, (q, m)
    else:
        assert set(zip(r.doc.tolist(), r.pos.tolist())) == positional, (q, m)


def test_boundary_many_and_groups_routes_flex(small_world):
    """> G_CAP AND-groups (an 11-word phrase) must route to flex and still
    match per-query search and the oracle."""
    import repro.core.batch_executor as bx
    corpus = small_world["corpus"]
    eng = small_world["engine"]
    be = BatchExecutor(small_world["index"], flex=eng.executor)
    queries, plans = [], []
    for d in range(corpus.n_docs):
        toks = corpus.doc(d)
        for st in range(0, max(len(toks) - bx.G_CAP - 3, 0), 4):
            q = toks[st:st + bx.G_CAP + 3].tolist()
            plan = eng.plan(q, mode=MODE_PHRASE)
            # stop words become checks, not groups: keep only windows whose
            # plan really carries > G_CAP AND-groups in one subplan
            if any(sp.supported and len(sp.groups) > bx.G_CAP
                   for sp in plan.subplans):
                queries.append(q)
                plans.append(plan)
            if len(queries) == 3:
                break
        if len(queries) == 3:
            break
    assert queries, "no >G_CAP-group windows found"
    assert all(not be._build_tasks(i, p, []) for i, p in enumerate(plans))
    for q, r in zip(queries, be.execute_batch(plans)):
        assert _same_result(eng.search(SearchRequest(q, mode=MODE_PHRASE)), r), q
        _assert_oracle(small_world, q, MODE_PHRASE, r)


def test_boundary_many_fetches_per_group_routes_flex(small_world):
    """> F_CAP unioned form fetches in one group must route to flex (shrunk
    cap: real multi-form groups have 2-4 fetches) and match the oracle."""
    import repro.core.batch_executor as bx
    eng = small_world["engine"]
    be = BatchExecutor(small_world["index"], flex=eng.executor)
    queries, modes = _mixed_batch(small_world, n=12, seed=29)
    plans = [eng.plan(q, mode=m) for q, m in zip(queries, modes)]
    multi = [i for i, p in enumerate(plans)
             if any(len(g.fetches) > 1 for sp in p.subplans if sp.supported
                    for g in sp.groups + sp.fallback_groups)]
    assert multi, "no multi-fetch groups in the workload"
    old = bx.F_CAP
    bx.F_CAP = 1
    try:
        for i in multi:
            assert not be._build_tasks(i, plans[i], [])
        got = be.execute_batch(plans)
    finally:
        bx.F_CAP = old
    for q, m, r in zip(queries, modes, got):
        assert _same_result(eng.search(SearchRequest(q, mode=m)), r), (q, m)
        _assert_oracle(small_world, q, m, r)


def test_boundary_long_fetches_stay_batched(small_world):
    """Fetches longer than P_CAP no longer escape: task-row splitting keeps
    them on the batched path (slots > 1) with oracle-identical results."""
    import repro.core.batch_executor as bx
    eng = small_world["engine"]
    be = BatchExecutor(small_world["index"], flex=eng.executor)
    queries, modes = _mixed_batch(small_world, n=12, seed=31)
    plans = [eng.plan(q, mode=m) for q, m in zip(queries, modes)]
    long_q = [i for i, p in enumerate(plans)
              if any(f.length > 256 for sp in p.subplans if sp.supported
                     for g in sp.groups for f in g.fetches)]
    assert long_q, "no long posting lists in the workload"
    old = bx.P_CAP
    bx.P_CAP = 256
    try:
        tasks: list = []
        assert be._build_tasks(0, plans[long_q[0]], tasks)   # batched, not flex
        assert any(len(g.slots) > 1 for t in tasks for r in t.rows
                   for g in r.groups), "long fetch was not split"
        got = be.execute_batch(plans)
    finally:
        bx.P_CAP = old
    for q, m, r in zip(queries, modes, got):
        assert _same_result(eng.search(SearchRequest(q, mode=m)), r), (q, m)
        _assert_oracle(small_world, q, m, r)


def test_boundary_position_overflow_routes_flex():
    """An index whose positions overflow the 17-bit packed-key field must
    route every plan to flex and still match the brute-force oracle."""
    from repro.core import (CorpusConfig, LexiconConfig, build_all,
                            generate_corpus, make_lexicon_and_analyzer)
    from repro.core.fetch_tables import TABLE_POS_BITS
    lc = LexiconConfig(n_surface=2000, n_base=1500, n_stop=50,
                       n_frequent=200, seed=5)
    lex, ana = make_lexicon_and_analyzer(lc)
    corpus = generate_corpus(lc, CorpusConfig(n_docs=2, mean_doc_len=150_000,
                                              seed=5))
    index = build_all(corpus, lex, ana)
    eng = AdditionalIndexEngine(index)
    be = eng.batch_executor
    assert be.dev.max_pos + 64 > (1 << TABLE_POS_BITS) - 64, \
        "corpus too short to overflow the packed-key field"
    assert be._pos_budget <= 0
    toks = corpus.doc(0)
    queries = [toks[10:13].tolist(), toks[100:104].tolist(),
               toks[140_000:140_003].tolist()]
    plans = [eng.plan(q, mode=MODE_PHRASE) for q in queries]
    assert all(not be._build_tasks(i, p, []) for i, p in enumerate(plans))
    for q, r in zip(queries, be.execute_batch(plans)):
        assert _same_result(eng.search(SearchRequest(q, mode=MODE_PHRASE)), r), q
        _assert_oracle({"corpus": corpus, "index": index}, q, MODE_PHRASE, r)


def _kword_boundary_queries(small_world, k_lo=6, k_hi=9, n=6, seed=41):
    """Contiguous K in [k_lo, k_hi) word windows from indexed docs with a
    device-reach span window — the ISSUE's K=6-8 overflow population."""
    corpus = small_world["corpus"]
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        d = int(rng.integers(corpus.n_docs))
        toks = corpus.doc(d)
        k = int(rng.integers(k_lo, k_hi))
        if len(toks) <= k + 2:
            continue
        st = int(rng.integers(0, len(toks) - k))
        out.append((toks[st:st + k].tolist(), min(k + 1, 15)))
    return out


def test_boundary_kword_many_groups_routes_flex(small_world):
    """K=6-8 kword plans whose cover still carries > G_CAP AND-groups
    (shrunk cap: the multi-key cover compresses real K=8 plans under the
    production cap) must route to flex and stay oracle-identical —
    positional anchors AND postings accounting."""
    import repro.core.batch_executor as bx
    from repro.core import brute_force_kword
    from repro.core.kword import MODE_KWORD
    eng = small_world["engine"]
    corpus, index = small_world["corpus"], small_world["index"]
    be = BatchExecutor(index, flex=eng.executor)
    cases = _kword_boundary_queries(small_world, n=6)
    reqs = [SearchRequest(q, mode=MODE_KWORD, window=w) for q, w in cases]
    plans = [eng.plan_request(r) for r in reqs]
    old = bx.G_CAP
    bx.G_CAP = 3
    try:
        over = [i for i, p in enumerate(plans)
                if any(sp.supported and len(sp.groups) > bx.G_CAP
                       and all(g.fetches for g in sp.groups)
                       for sp in p.subplans)]
        assert len(over) >= 3, "K=6-8 covers never exceeded the shrunk cap"
        for i in over:
            assert not be._build_tasks(i, plans[i], []), cases[i]
        got = be.execute_batch(plans)
    finally:
        bx.G_CAP = old
    for (q, w), req, r in zip(cases, reqs, got):
        assert _same_result(eng.search(req), r), (q, w)
        truth_pos, truth_doc = brute_force_kword(corpus, index, q, w)
        if r.doc_only:
            assert set(r.doc.tolist()) == truth_doc, (q, w)
        else:
            assert set(zip(r.doc.tolist(), r.pos.tolist())) == truth_pos, (q, w)


def test_boundary_kword_default_caps_stay_batched(small_world):
    """The same K=6-8 population at PRODUCTION caps: the multi-key cover
    must compress every plan under G_CAP so it stays on the device path
    (guards cover-bloat regressions), still bit-identical to flex."""
    from repro.core.kword import MODE_KWORD
    eng = small_world["engine"]
    be = BatchExecutor(small_world["index"], flex=eng.executor)
    cases = _kword_boundary_queries(small_world, n=6, seed=43)
    reqs = [SearchRequest(q, mode=MODE_KWORD, window=w) for q, w in cases]
    plans = [eng.plan_request(r) for r in reqs]
    n_batched = sum(bool(be._build_tasks(i, p, []))
                    for i, p in enumerate(plans))
    assert n_batched >= 4, n_batched
    for req, r in zip(reqs, be.execute_batch(plans)):
        assert _same_result(eng.search(req), r), req


@pytest.mark.parametrize("dps", [16, 64])
def test_search_batch_segmented_shards_match(small_world, dps):
    """Shard-segmented gather: cutting the corpus into many small doc shards
    (one row per task x shard) must not change any result bit."""
    eng = AdditionalIndexEngine(small_world["index"], docs_per_shard=dps)
    assert eng.batch_executor.dev.n_shards > 1
    ref = small_world["engine"]
    queries, modes = _mixed_batch(small_world, n=24, seed=19)
    for q, m, got in zip(queries, modes, eng.search_batch(
            [SearchRequest(q, mode=m) for q, m in zip(queries, modes)])):
        assert _same_result(ref.search(SearchRequest(q, mode=m)), got), (q, m, dps)


# ---------------------------------------------------------------------------
# rows-kernel agreement on re-based int32 keys
# ---------------------------------------------------------------------------


# packed-layout cases (seeds from 10): row counts that are not a multiple
# of 8, widths up to one 1024-key tile on each side, and one wide pair that
# keeps the tiled kernel; 70 rows of 1024 keys span three packed blocks
PACKED_CASES = [(n, pa, pb, 10 + i) for i, (n, (pa, pb)) in enumerate(
    (n, w) for n in (1, 7, 9, 13)
    for w in ((128, 128), (128, 512), (384, 1024), (1024, 128),
              (1024, 2048)))] + [(70, 1024, 1024, 30)]
# one case per row count for the twins, cycling the widths
TWIN_CASES = PACKED_CASES[::6] + [PACKED_CASES[-1]]


def _rebased_rows(rng, N, Pa, Pb):
    """Keys shaped like the executor's re-based int32 domain
    (doc_local << 17 | pos): a [N, Pa], b [N, Pb], both unsorted."""
    from repro.core.fetch_tables import TABLE_BIAS, TABLE_POS_BITS
    doc_a = rng.integers(0, 50, (N, Pa))
    doc_b = rng.integers(0, 50, (N, Pb))
    pos_a = rng.integers(0, 400, (N, Pa))
    pos_b = rng.integers(0, 400, (N, Pb))
    a = ((doc_a << TABLE_POS_BITS) | (pos_a + TABLE_BIAS)).astype(np.int32)
    b = ((doc_b << TABLE_POS_BITS) | (pos_b + TABLE_BIAS)).astype(np.int32)
    return a, b


def _dead_rows(a, b_keys, seed):
    """Rows without keys: the older cases blank the last row's b keys; the
    packed cases (seeds from 10) every third row's from row 1, among live
    rows of one block, and, past 9 rows, the a keys of the first half of the
    rows rounded down to 8 (a whole block, where a block holds that many
    rows: 70 rows of 1024 keys pack 32 to a block)."""
    if seed < 10:
        b_keys[-1] = np.iinfo(np.int32).max
        return
    b_keys[1::3] = np.iinfo(np.int32).max
    if len(a) > 9:
        a[:len(a) // 2 // 8 * 8] = np.iinfo(np.int32).max


@pytest.mark.parametrize("N,Pa,Pb,seed", [(4, 256, 256, 0), (9, 512, 1024, 1),
                                          (16, 256, 2048, 2), (1, 128, 128, 3)]
                         + PACKED_CASES)
def test_banded_intersect_rows_matches_ref(N, Pa, Pb, seed):
    """Pallas vs ref on keys shaped like the executor's re-based int32 domain
    (doc_local << 17 | pos), with mixed per-row bands and sentinel padding."""
    rng = np.random.default_rng(seed)
    a, b = _rebased_rows(rng, N, Pa, Pb)
    b = np.sort(b, axis=1)
    a[:, -7:] = np.iinfo(np.int32).max            # sentinel pads
    _dead_rows(a, b, seed)                    # empty (dead) groups
    bands = rng.integers(0, 6, N).astype(np.int32)
    got = ops.banded_intersect_rows(jnp.asarray(a), jnp.asarray(b),
                                    jnp.asarray(bands))
    want = ops.banded_intersect_rows(jnp.asarray(a), jnp.asarray(b),
                                     jnp.asarray(bands), implementation="ref")
    assert bool((got == want).all())
    # sentinel entries never match
    assert not np.asarray(got)[:, -7:].any()


@pytest.mark.parametrize("N,Pa,Pb,seed", [(4, 256, 256, 0), (9, 512, 1024, 1),
                                          (1, 128, 128, 3)] + TWIN_CASES)
def test_banded_min_delta_rows_matches_ref(N, Pa, Pb, seed):
    """Pallas vs ref for the proximity-scoring kernel, on the valid domain:
    band-0 rows carry mixed stored deltas (dist-fetch groups), band>0 rows
    all-zero deltas (full-list groups) — rows sorted by (key, delta)."""
    rng = np.random.default_rng(seed)
    a, bk = _rebased_rows(rng, N, Pa, Pb)
    bands = rng.integers(0, 6, N).astype(np.int32)
    bd = np.where(bands[:, None] == 0,
                  rng.integers(0, 16, (N, Pb)), 0).astype(np.int32)
    order = np.lexsort((bd, bk), axis=-1)
    bk = np.take_along_axis(bk, order, axis=-1)
    bd = np.take_along_axis(bd, order, axis=-1)
    a[:, -5:] = np.iinfo(np.int32).max           # sentinel pads
    _dead_rows(a, bk, seed)                   # empty (dead) groups
    got = ops.banded_min_delta_rows(jnp.asarray(a), jnp.asarray(bk),
                                    jnp.asarray(bd), jnp.asarray(bands))
    want = ops.banded_min_delta_rows(jnp.asarray(a), jnp.asarray(bk),
                                     jnp.asarray(bd), jnp.asarray(bands),
                                     implementation="ref")
    assert bool((got == want).all())
    # the membership bit agrees with the boolean kernel
    member = ops.banded_intersect_rows(jnp.asarray(a), jnp.asarray(bk),
                                       jnp.asarray(bands),
                                       implementation="ref")
    assert bool(((np.asarray(got) < np.iinfo(np.int32).max)
                 == np.asarray(member)).all())
    assert (np.asarray(got)[:, -5:] == np.iinfo(np.int32).max).all()


@pytest.mark.parametrize("N,Pa,Pb,seed", TWIN_CASES)
def test_banded_delta_mask_rows_matches_ref(N, Pa, Pb, seed):
    """Pallas vs ref for the K-word delta-mask kernel: per-row windows up to
    the device cap of 15, mixed within a block, over keys of three docs so
    that masks hold offsets."""
    rng = np.random.default_rng(seed)
    a, b = _rebased_rows(rng, N, Pa, Pb)
    a, b = a % (3 << 17), np.sort(b % (3 << 17), axis=1)
    a[:, -3:] = np.iinfo(np.int32).max           # sentinel pads
    _dead_rows(a, b, seed)                    # empty (dead) groups
    bands = rng.integers(0, 16, N).astype(np.int32)
    got = ops.banded_delta_mask_rows(jnp.asarray(a), jnp.asarray(b),
                                     jnp.asarray(bands))
    want = ops.banded_delta_mask_rows(jnp.asarray(a), jnp.asarray(b),
                                      jnp.asarray(bands),
                                      implementation="ref")
    assert bool((got == want).all())
    assert np.asarray(want).any()
    assert not np.asarray(got)[:, -3:].any()


def test_banded_intersect_rows_band_isolation():
    """Rows with band 0 must not leak band-W semantics from neighbours,
    inside a packed block of eight rows and across two."""
    n = 10
    a = np.tile(np.arange(0, 1280, 10, np.int32), (n, 1))[:, :128]
    b = np.tile((np.arange(0, 1280, 10, np.int32) + 3), (n, 1))[:, :128]
    bands = np.array([0, 5] * (n // 2), np.int32)
    got = np.asarray(ops.banded_intersect_rows(jnp.asarray(a), jnp.asarray(b),
                                               jnp.asarray(bands)))
    assert not got[0::2].any()    # off by 3, band 0 -> no hits
    assert got[1::2].all()        # band 5 covers the offset
