"""The warm-up covers the window: every bucket step that a micro-batch of
pool queries can call is among the steps the warm-up lowers and runs; and
the window's throughput counts each backend call by its share of time in
the window."""
import copy

import numpy as np
import pytest

from bench.lib import harness, spec, traffic


@pytest.fixture(scope="module")
def engine_and_pool():
    from repro.core import SearchRequest
    from repro.serve import ShardBackend
    cfg = copy.deepcopy(spec.config("paper45g-1of512"))
    cfg["corpus"].update(n_docs=16, median_doc_len=6000, max_doc_len=20000)
    mix = spec.mix("rare-bulk")
    mix["pool"] = 96
    lex, forms, corp, index = harness.build_world(cfg, cache=False)
    pool = traffic.make_pool(mix, corp, lex, forms, cfg["data_seed"])
    reqs = [harness.to_request(SearchRequest, q) for q in pool]
    return ShardBackend(index).engine, reqs


@pytest.mark.parametrize("max_batch", [8, 32])
def test_warm_batches_cover_random_micro_batches(engine_and_pool, max_batch):
    engine, reqs = engine_and_pool
    batches, steps = harness.warm_batches(engine, reqs, max_batch)
    assert all(len(b) <= 4 * max_batch and len(set(b)) == 1 for b in batches)
    be = engine.batch_executor
    rng = np.random.default_rng(max_batch)
    for size in list(range(1, max_batch + 1)) * 2:
        ix = rng.choice(len(reqs), size, replace=False)
        got = be.lower_steps([engine.plan_request(reqs[i]) for i in ix],
                             [reqs[i] for i in ix])
        assert set(got) <= set(steps), size


def test_exact_work_prorates_calls_at_the_edges():
    calls = [(0.0, 1.0, [1, 2]),        # half inside
             (1.0, 2.0, [3, 4, 5]),     # inside, one request not exact
             (2.5, 3.5, [6]),           # half inside
             (4.0, 5.0, [7])]           # outside
    exact = {1, 2, 3, 4, 6, 7}
    assert harness.exact_work(calls, exact, 0.5, 3.0) == pytest.approx(
        2 * 0.5 + 2 + 1 * 0.5)
    assert harness.exact_work(calls, exact, 10.0, 11.0) == 0.0
