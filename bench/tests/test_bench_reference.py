"""The plain reference against the program's brute-force oracles
(`core/engine.py`) on a small corpus, for the queries the mixes send and for
the cases the paper's stop- and frequent-word semantics single out.  The
reference itself imports nothing of the program; this test does, to show
that both state the same semantics."""
import copy
import hashlib
import json

import numpy as np
import pytest

from bench.lib import corpus as bcorpus, spec, traffic
from bench.lib.reference import Reference, split_parts

STOP, FREQUENT, ORDINARY = 0, 1, 2


@pytest.fixture(scope="module")
def world():
    from repro.core import (IndexParams, LexiconConfig, build_all,
                            make_lexicon_and_analyzer)
    from repro.core.corpus import Corpus
    cfg = spec.config("paper45g-1of512")
    cfg["corpus"].update(n_docs=12, median_doc_len=1500, max_doc_len=4000)
    seed = 11
    lex = bcorpus.lexicon_from(cfg, seed)
    forms = bcorpus.draw_forms(lex)
    corp = bcorpus.corpus_from(cfg, lex, forms, seed)
    plex, pana = make_lexicon_and_analyzer(LexiconConfig(seed=seed))
    pcorp = Corpus(doc_offsets=corp.doc_offsets, tokens=corp.tokens)
    index = build_all(pcorp, plex, pana, IndexParams(**cfg["index"]))
    ref = Reference(corp, lex, forms, cfg["index"])
    return cfg, lex, forms, corp, pcorp, index, ref


def test_analyzer_copy_matches_program(world):
    _cfg, _lex, forms, _c, _pc, index, ref = world
    np.testing.assert_array_equal(index.analyzer.primary, forms.primary)
    np.testing.assert_array_equal(index.analyzer.secondary, forms.secondary)
    np.testing.assert_array_equal(index.base_occ_counts(), ref.occ_count)


def _check(world, pool):
    """Every query's reference answer equals the oracles'; returns the
    answers."""
    from repro.core.engine import brute_force_search
    _cfg, _lex, _forms, _corp, pcorp, index, ref = world
    out = []
    for q in pool:
        ans = ref.answer(q)
        got = {(int(c >> 32), int(c & 0xFFFFFFFF)) for c in ans.codes}
        pos, docs = brute_force_search(pcorp, index, q.words, mode=q.mode)
        assert got == pos, q
        assert ans.doc_level == docs, q
        out.append(ans)
    return out


def _mix(name, mode, pool):
    """`rare-bulk`'s kinds of one mode; `open`: the same with no filter,
    the paper's procedure over every window."""
    mix = copy.deepcopy(spec.mix("rare-bulk"))
    mix["pool"] = pool
    mix["kinds"] = [k for k in mix["kinds"] if k["mode"] == mode]
    if name == "open":
        for k in mix["kinds"]:
            del k["filter"]
    return mix


def _tiers(world, w):
    _cfg, lex, forms, *_ = world
    return traffic.word_tiers(lex, forms, [w])[0]


def _windows(world, n, step, keep, limit):
    """Up to `limit` word tuples of `n` tokens `step` apart, read off the
    corpus inside one document, for which `keep(words)` holds."""
    _cfg, _lex, _forms, corp, *_ = world
    out = []
    for d in range(corp.n_docs):
        toks = corp.doc(d).tolist()
        for st in range(0, len(toks) - step * (n - 1)):
            words = tuple(toks[st:st + step * n:step])
            if keep(words) and words not in out:
                out.append(words)
                if len(out) == limit:
                    return out
    return out


@pytest.mark.parametrize("mix", ["rare", "open"])
@pytest.mark.parametrize("mode", ["phrase", "near"])
@pytest.mark.parametrize("seed", [5, 6])
def test_reference_matches_program_oracles(world, mix, mode, seed):
    _cfg, lex, forms, corp, _pc, _ix, _ref = world
    pool = traffic.make_pool(_mix(mix, mode, 32 if mix == "open" else 24),
                             corp, lex, forms, seed)
    _check(world, pool)
    if mix == "open":
        with_stop_or_frequent = sum(
            any(t != {ORDINARY} for t in traffic.word_tiers(lex, forms,
                                                             q.words))
            for q in pool)
        assert with_stop_or_frequent >= 25


def test_reference_matches_oracles_off_the_source(world):
    """Queries of ordinary words that need not occur together: answers
    empty, doc-level sets of every size."""
    _cfg, lex, forms, corp, _pc, _ix, ref = world
    rng = np.random.default_rng(3)
    words = [w for w in range(lex.n_surface) if ref.occ_count[
        forms.primary[w]] > 0 and forms.secondary[w] < 0
        and forms.primary[w] >= lex.n_stop + lex.n_frequent][:400]
    pool = [traffic.Query(tuple(int(x) for x in rng.choice(words, n)), mode)
            for n in (2, 3) for mode in ("phrase", "near") for _ in range(6)]
    _check(world, pool)


@pytest.mark.parametrize("mode", ["phrase", "near"])
@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 6, 7])
def test_all_stop_windows(world, mode, n):
    """Windows of stop words only, read off the corpus, in order and
    reversed: the stop-phrase semantics (order-free multisets, parts past
    `max_len`, nothing under `min_len`) in both modes.  A window in order
    is its own answer; reversed, it is where no part crosses the middle."""
    cfg = world[0]
    found = _windows(world, n, 1, lambda ws: all(
        STOP in _tiers(world, w) for w in ws), 3)
    assert found
    pool = [traffic.Query(ws, mode) for ws in found]
    pool += [traffic.Query(ws[::-1], mode) for ws in found]
    answers = _check(world, pool)
    if n >= cfg["index"]["min_len"]:
        assert all(len(a.codes) for a in answers[:len(found)])
    else:
        assert not any(len(a.codes) or a.doc_level for a in answers)


@pytest.mark.parametrize("mode", ["phrase", "near"])
@pytest.mark.parametrize("split", [(STOP, FREQUENT), (STOP, ORDINARY),
                                   (FREQUENT, ORDINARY)])
def test_word_with_forms_in_two_tiers(world, mode, split):
    """A word whose two forms fall in different tiers splits the query
    into subqueries whose answers are united."""
    step = 1 if mode == "phrase" else 2
    found = _windows(world, 3, step, lambda ws: any(
        _tiers(world, w) == set(split) for w in ws), 6)
    assert len(found) == 6
    _check(world, [traffic.Query(ws, mode) for ws in found])


@pytest.mark.parametrize("mode", ["phrase", "near"])
@pytest.mark.parametrize("n", [3, 4, 5])
def test_repeated_frequent_word(world, mode, n):
    """A frequent-tier word twice in one window: the oracle is the truth
    here (the served program is known to answer some of these wrong)."""
    step = 1 if mode == "phrase" else 2

    def repeats(ws):
        freq = [w for w in ws if FREQUENT in _tiers(world, w)]
        return len(freq) > len(set(freq))

    found = _windows(world, n, step, repeats, 4)
    assert found
    _check(world, [traffic.Query(ws, mode) for ws in found])


@pytest.mark.parametrize("mode", ["phrase", "near"])
@pytest.mark.parametrize("stop_tiers", [{STOP}, {STOP, ORDINARY}])
def test_stop_queries_that_match_nothing(world, mode, stop_tiers):
    """A stop word with two ordinary words that no document holds
    together: no anchor and no doc-level answer in either mode."""
    _cfg, lex, forms, corp, _pc, _ix, ref = world
    single = {}
    for d in range(corp.n_docs):
        for w in np.unique(corp.doc(d)).tolist():
            single[w] = None if w in single else d
    lone = [w for w, d in single.items() if d is not None
            and _tiers(world, w) == {ORDINARY}]
    stops = [w for w in range(lex.n_surface)
             if _tiers(world, w) == stop_tiers][:4]
    pool = []
    for i, s in enumerate(stops):
        a = lone[i]
        b = next(w for w in lone if single[w] != single[a])
        pool.append(traffic.Query((a, s, b), mode))
    answers = _check(world, pool)
    assert len(pool) == 4
    assert not any(len(x.codes) or x.doc_level for x in answers)


@pytest.mark.parametrize("n,parts", [
    (2, [(0, 2)]), (5, [(0, 5)]), (6, [(0, 4), (4, 2)]),
    (7, [(0, 5), (5, 2)]), (11, [(0, 5), (5, 4), (9, 2)])])
def test_split_parts_matches_the_planner(n, parts):
    from repro.core.planner import split_query_parts
    assert split_parts(n, 2, 5) == parts == split_query_parts(n, 2, 5)


# sha256 of the 256 queries `rare-bulk` sends at `paper45g-1of512`'s
# data_seed, as JSON [[mode, [words]], ...] in pool order
RARE_BULK_POOL = ("682cc8f231affda4ae7d5024048cade6"
                  "ddeec34b6b3819e1aadb64f2a97cf286")


def test_rare_bulk_pool_is_pinned():
    """The traffic of `paper45g.rare-bulk`, at the configuration's full
    size, is the pool its accepted measurements were taken on."""
    cfg = spec.config("paper45g-1of512")
    seed = cfg["data_seed"]
    lex = bcorpus.lexicon_from(cfg, seed)
    forms = bcorpus.draw_forms(lex)
    corp = bcorpus.corpus_from(cfg, lex, forms, seed)
    pool = traffic.make_pool(spec.mix("rare-bulk"), corp, lex, forms, seed)
    blob = json.dumps([[q.mode, [int(w) for w in q.words]] for q in pool])
    assert hashlib.sha256(blob.encode()).hexdigest() == RARE_BULK_POOL
