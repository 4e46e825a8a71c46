"""Spans and counters of the served path (`repro/obs.py`, the front door's
queue counters, the batch executor's slab and first-run counts), on the CPU.

A profiler trace recorded around one front-door micro-batch must hold every
program span on the thread that runs its phase, nested as the phases are;
the counters must agree with hand counts of what the path did."""
import gc

import jax
import numpy as np
import pytest

from benchmarks import trace_spans
from repro import obs
from repro.core import AdditionalIndexEngine, SearchRequest
from repro.core.planner import MODE_NEAR, MODE_PHRASE
from repro.serve.front import FrontDoor, FrontDoorConfig, FrontStats

BATCH = 8
CFG = dict(default_deadline_ms=600_000.0, shard_timeout_s=300.0,
           cache_capacity=0)

DISPATCHER = ["repro.front.batch", "repro.front.coalesce",
              "repro.front.plan", "repro.front.execute", "repro.front.merge"]
SHARD = ["repro.engine.search_batch", "repro.engine.plan",
         "repro.batch.rows", "repro.batch.tensorize", "repro.batch.transfer",
         "repro.batch.step", "repro.batch.fetch", "repro.batch.first_run",
         "repro.batch.scatter", "repro.batch.merge"]


def _requests(corpus, n, seed=3, mode=MODE_PHRASE):
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        toks = np.asarray(corpus.doc(int(rng.integers(corpus.n_docs))))
        if len(toks) < 12:
            continue
        st = int(rng.integers(0, len(toks) - 8))
        words = toks[st:st + 6:2] if mode == MODE_NEAR else toks[st:st + 3]
        out.append(SearchRequest(tuple(int(x) for x in words), mode=mode))
    return out


@pytest.fixture
def gc_traced():
    """The collection hook, removed again after the test."""
    try:
        yield obs.trace_gc()
    finally:
        if obs._on_gc in gc.callbacks:
            gc.callbacks.remove(obs._on_gc)


@pytest.fixture(scope="module")
def traced_batch(small_world, tmp_path_factory):
    """One micro-batch of BATCH requests through a fresh front door (so its
    executor runs each step key for the first time) and a collection on
    this thread, under the profiler: the program's spans, read back."""
    tdir = str(tmp_path_factory.mktemp("trace"))
    front = FrontDoor(small_world["index"], cfg=FrontDoorConfig(
        max_batch=BATCH, batch_window_ms=2_000.0, **CFG))
    reqs = _requests(small_world["corpus"], BATCH)
    installed = obs._on_gc not in gc.callbacks
    obs.trace_gc()
    try:
        jax.profiler.start_trace(tdir)
        try:
            resps = front.search_batch(reqs)
            gc.collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        front.close()
        if installed:
            gc.callbacks.remove(obs._on_gc)
    from bench.lib import trace as btrace
    spans = trace_spans.program_spans(btrace.find_xplane(tdir))
    return spans, resps, front


def test_every_span_is_recorded(traced_batch):
    spans, resps, front = traced_batch
    assert all(r.status == "SERVED_EXACT" for r in resps)
    names = {s[0] for s in spans}
    for name in DISPATCHER + SHARD + ["repro.gc"]:
        assert name in names, name
    (batch,) = [s for s in spans if s[0] == "repro.front.batch"]
    assert batch[4] == {"seq": 1, "size": BATCH}
    assert all("generation" in s[4] for s in spans if s[0] == "repro.gc")


def test_spans_sit_on_their_threads(traced_batch):
    spans, _, _ = traced_batch
    lines = {}
    for name, line, *_ in spans:
        lines.setdefault(name, set()).add(line)
    (dispatcher,) = set.union(*(lines[n] for n in DISPATCHER))
    shard = set.union(*(lines[n] for n in SHARD))
    assert dispatcher not in shard
    # the collection ran on this (the test's) thread, which is neither
    assert any(line not in shard | {dispatcher}
               for line in lines["repro.gc"])


def _inside(inner, outer, same_line=True):
    return (outer[2] <= inner[2] and inner[3] <= outer[3]
            and (not same_line or inner[1] == outer[1]))


def test_spans_nest_as_the_phases(traced_batch):
    spans, _, _ = traced_batch

    def of(name):
        return [s for s in spans if s[0] == name]
    (batch,) = of("repro.front.batch")
    for name in DISPATCHER[1:]:
        assert of(name) and all(_inside(s, batch) for s in of(name))
    # each backend call runs (on a shard thread) inside one dispatch
    for call in of("repro.engine.search_batch"):
        assert sum(_inside(call, ex, same_line=False)
                   for ex in of("repro.front.execute")) == 1
    for name in SHARD[1:]:
        for s in of(name):
            assert sum(_inside(s, c)
                       for c in of("repro.engine.search_batch")) == 1, name
    for name in ("repro.batch.step", "repro.batch.fetch"):
        firsts = of("repro.batch.first_run")
        assert any(_inside(s, f) for s in of(name) for f in firsts)


def _chunk_recorder(be):
    """Record every (rows, tables, static) chunk the executor yields."""
    seen = []
    inner = be._bucket_chunks

    def chunks(rows):
        for item in inner(rows):
            seen.append(item)
            yield item
    be._bucket_chunks = chunks
    return seen


def test_slab_stats_count_the_padded_tables(small_world):
    eng = AdditionalIndexEngine(small_world["index"])
    be = eng.batch_executor
    seen = _chunk_recorder(be)
    corpus = small_world["corpus"]
    eng.search_batch(_requests(corpus, 12)
                     + _requests(corpus, 12, seed=5, mode=MODE_NEAR))
    want = {"steps": len(seen), "slab_rows": 0, "live_rows": 0,
            "slab_elems": 0, "live_elems": 0, "banded_rows": 0,
            "packed_rows": 0}
    for part, tj, static in seen:
        T, G, F = tj["start"].shape
        want["slab_rows"] += T
        want["live_rows"] += len(part)
        want["slab_elems"] += T * (F * static["P0"]
                                   + (G - 1) * F * static["P"])
        want["live_elems"] += int(np.asarray(tj["length"]).sum())
        want["banded_rows"] += T * (G - 1)
        if max(F * static["P0"], F * static["P"]) <= 1024:
            want["packed_rows"] += T * (G - 1)
    got = {k: v for k, v in be.slab_stats.items() if k != "first_runs"}
    assert got == want and want["steps"] > 0
    assert 0 < want["live_elems"] < want["slab_elems"]


@pytest.mark.parametrize("floor", [None, 2048])
def test_packed_rows_count_the_narrow_buckets(small_world, monkeypatch,
                                              floor):
    """`packed_rows` counts the banded rows (T * (G-1)) of the bucket steps
    whose rows are at most 1024 keys wide on both sides: every row of the
    default caps' narrow buckets, and none where a row floor of 2048 keys
    widens every bucket past one tile."""
    from repro.core import batch_executor
    if floor is not None:
        monkeypatch.setattr(batch_executor, "P_FLOOR", floor)
    eng = AdditionalIndexEngine(small_world["index"])
    be = eng.batch_executor
    seen = _chunk_recorder(be)
    reqs = (_requests(small_world["corpus"], 8)
            + _requests(small_world["corpus"], 8, seed=5, mode=MODE_NEAR))
    got = eng.search_batch(reqs)
    narrow = 0
    for _, tj, static in seen:
        T, G, F = tj["start"].shape
        if max(F * static["P0"], F * static["P"]) <= 1024:
            narrow += T * (G - 1)
    st = be.slab_stats
    assert st["banded_rows"] > 0
    assert st["packed_rows"] == narrow
    if floor is None:
        assert narrow > 0
    else:
        assert narrow == 0
        ref = small_world["engine"].search_batch(reqs)
        for x, y in zip(got, ref):
            assert np.array_equal(x.doc, y.doc)
            assert np.array_equal(x.pos, y.pos)


def test_first_runs_count_new_step_keys_once(small_world):
    eng = AdditionalIndexEngine(small_world["index"])
    be = eng.batch_executor
    seen = _chunk_recorder(be)
    corpus = small_world["corpus"]
    phrase = _requests(corpus, 4)
    eng.search_batch(phrase)
    keys = {be._step_key(tj, st) for _, tj, st in seen}
    assert be.slab_stats["first_runs"] == len(keys) > 0
    eng.search_batch(phrase)                 # the same keys again
    assert be.slab_stats["first_runs"] == len(keys)
    near = _requests(corpus, 16, seed=9, mode=MODE_NEAR)
    eng.search_batch(near)
    keys |= {be._step_key(tj, st) for _, tj, st in seen}
    assert be.slab_stats["first_runs"] == len(keys)


def test_queue_counters_balance(small_world):
    front = FrontDoor(small_world["index"], cfg=FrontDoorConfig(
        max_batch=4, **CFG))
    try:
        resps = front.search_batch(_requests(small_world["corpus"], 10))
        st = front.stats
        assert st.dequeued == st.submitted == 10
        assert st.shed == 0 and st.batches >= 3
        # each request's queue wait lies inside its latency
        assert 0.0 <= st.queue_wait_s <= sum(r.latency_ms for r in resps) / 1e3
    finally:
        front.close()


def test_front_stats_hold_no_per_request_list():
    assert not any(isinstance(v, (list, tuple))
                   for v in vars(FrontStats()).values())


def test_trace_gc_installs_one_hook(gc_traced):
    assert obs.trace_gc() is gc_traced
    assert gc.callbacks.count(obs._on_gc) == 1
    n, s = gc_traced.collections, gc_traced.pause_s
    gc.collect()
    assert gc_traced.collections == n + 1
    assert gc_traced.pause_s > s


def test_slab_stats_under_concurrent_calls(small_world):
    """One executor called from more threads than cores (as a shard
    dispatcher's retries and replicas may): no count is lost."""
    import os
    import sys
    import threading
    eng = AdditionalIndexEngine(small_world["index"])
    be = eng.batch_executor
    reqs = _requests(small_world["corpus"], 6)
    eng.search_batch(reqs)
    one = dict(be.slab_stats)
    n = min(2 * (os.cpu_count() or 1) + 2, 64)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=eng.search_batch, args=(reqs,))
                   for _ in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120.0)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    for k in ("steps", "slab_rows", "live_rows", "slab_elems", "live_elems"):
        assert be.slab_stats[k] == (n + 1) * one[k], k
    assert be.slab_stats["first_runs"] == one["first_runs"]
