"""The plain reference against the program's brute-force oracles
(`core/engine.py`) on a small corpus, for the queries the mixes send.  The
reference itself imports nothing of the program; this test does, to show
that both state the same semantics."""
import numpy as np
import pytest

from bench.lib import corpus as bcorpus, spec, traffic
from bench.lib.reference import Reference


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
    from repro.core.engine import brute_force_search
    _cfg, _lex, _forms, _corp, pcorp, index, ref = world
    for q in pool:
        ans = ref.answer(q)
        got = {(int(c >> 32), int(c & 0xFFFFFFFF)) for c in ans.codes}
        pos, docs = brute_force_search(pcorp, index, q.words, mode=q.mode)
        assert got == pos, q
        assert ans.doc_level == docs, q


@pytest.mark.parametrize("mode", ["phrase", "near"])
@pytest.mark.parametrize("seed", [5, 6])
def test_reference_matches_program_oracles(world, mode, seed):
    _cfg, lex, forms, corp, _pc, _ix, _ref = world
    mix = spec.mix("rare-bulk")
    mix["pool"] = 24
    mix["kinds"] = [k for k in mix["kinds"] if k["mode"] == mode]
    _check(world, traffic.make_pool(mix, corp, lex, forms, seed))


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


def test_reference_refuses_stop_and_frequent_words(world):
    _cfg, lex, forms, _c, _pc, _ix, ref = world
    stop = int(np.nonzero(forms.primary < lex.n_stop)[0][0])
    with pytest.raises(ValueError):
        ref.answer(traffic.Query((stop, stop + 1), "phrase"))
