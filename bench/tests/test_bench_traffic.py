"""The traffic generator: deterministic per seed, the same set of sizes
for every seed, the filter held, and orders that depend on the seed
alone."""
import collections

import numpy as np
import pytest

from bench.lib import corpus as bcorpus, spec, traffic


def _pool(seed, n=60):
    cfg = spec.config("paper45g-1of512")
    cfg["corpus"]["n_docs"] = 6
    lex = bcorpus.lexicon_from(cfg, seed)
    forms = bcorpus.draw_forms(lex)
    corp = bcorpus.corpus_from(cfg, lex, forms, seed)
    mix = spec.mix("rare-bulk")
    mix["pool"] = n
    return traffic.make_pool(mix, corp, lex, forms, seed), lex, forms


def test_pool_is_deterministic_per_seed():
    a, _, _ = _pool(3)
    b, _, _ = _pool(3)
    c, _, _ = _pool(4)
    assert a == b
    assert a != c


@pytest.mark.parametrize("seeds", [(5, 6), (7, 2 ** 31 + 5)])
def test_every_seed_gets_the_same_sizes(seeds):
    def sizes(pool):
        return collections.Counter((q.mode, len(q.words)) for q in pool)
    assert sizes(_pool(seeds[0])[0]) == sizes(_pool(seeds[1])[0])


def test_filters_hold():
    rare, lex, forms = _pool(7)
    for q in rare:
        assert all(t == {2} for t in traffic.word_tiers(lex, forms, q.words))
    assert {q.mode for q in rare} == {"phrase", "near"}
    assert {len(q.words) for q in rare} == {3, 4, 5}


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError):
        traffic.make_pool({"pool": 2, "kinds": [{"gen": "x", "share": 1}]},
                          None, None, None, 1)


def test_pool_order_spreads_evenly():
    o = traffic.pool_order(16, 40, 9)
    counts = np.bincount(o, minlength=16)
    assert counts.max() - counts.min() <= 1
    assert not np.array_equal(o, traffic.pool_order(16, 40, 2 ** 31 + 9))
