"""The one traffic generator.  A mix file (`bench/traffic/<mix>.json`) names
the kinds of query in its pool with their shares, the pool's size, how the
requests arrive and the front door's settings; this module turns it and a
seed into a pool of requests and the order in which they are sent.

Query kind `paper`: the paper's procedure (arXiv:1801.09079, STRUCTURE OF
SEARCH EXPERIMENTS): a random document, 3-5 words; `phrase` takes
consecutive words (2.1), `near` every other word (2.2).  `filter: rare`
keeps only windows of ordinary words (no basic form of any word is a stop
or frequent lemma).  Every query is sampled from an indexed document, so
each has its source as an answer.

Every pool gets the same set of sizes (3, 4, 5 words in turn per kind);
sampling copies `paper_query_stream` of the program's
`benchmarks/common.py`.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib.corpus import Corpus, Forms, Lexicon, tier_of

ORDINARY = 2


@dataclasses.dataclass(frozen=True)
class Query:
    """One pool entry, independent of the program's request type."""
    words: tuple
    mode: str                    # "phrase" | "near"


def word_tiers(lex: Lexicon, forms: Forms, words) -> list:
    """The set of tiers among each word's basic forms."""
    out = []
    for w in words:
        fs = [forms.primary[w]] + ([forms.secondary[w]]
                                   if forms.secondary[w] >= 0 else [])
        out.append({int(t) for t in tier_of(lex, fs)})
    return out


FILTERS = {
    "rare": lambda tiers: all(t == {ORDINARY} for t in tiers),
}


def _paper_query(rng, corpus: Corpus, lex, forms, n: int, mode: str,
                 filt: str | None) -> tuple:
    step = 1 if mode == "phrase" else 2
    for _ in range(100_000):
        d = int(rng.integers(corpus.n_docs))
        toks = corpus.doc(d)
        if len(toks) < 2 * n + 2:
            continue
        st = int(rng.integers(0, len(toks) - 2 * n))
        words = toks[st:st + step * n:step].tolist()
        if filt is None or FILTERS[filt](word_tiers(lex, forms, words)):
            return tuple(words)
    raise RuntimeError(f"no {filt} window of {n} words found")


def make_pool(mix: dict, corpus: Corpus, lex: Lexicon, forms: Forms,
              seed: int) -> list[Query]:
    """`mix["pool"]` queries; each kind gets its share (rounded, the last
    kind takes the rest), sizes cycle within a kind, and the pool is
    shuffled by the seed."""
    rng = np.random.default_rng([seed, 0x7AFF])
    n = int(mix["pool"])
    kinds = mix["kinds"]
    total = sum(k["share"] for k in kinds)
    counts = [int(round(n * k["share"] / total)) for k in kinds[:-1]]
    counts.append(n - sum(counts))
    pool = []
    for kind, count in zip(kinds, counts):
        if kind["gen"] != "paper":
            raise ValueError(f"unknown query kind {kind['gen']!r}")
        for i in range(count):
            words = _paper_query(rng, corpus, lex, forms, 3 + i % 3,
                                 kind["mode"], kind.get("filter"))
            pool.append(Query(words, kind["mode"]))
    order = rng.permutation(len(pool))
    return [pool[i] for i in order]


def pool_order(n_pool: int, n: int, seed: int) -> np.ndarray:
    """Which pool entry the i-th request sends: seeded passes over the whole
    pool, so every entry is sent as evenly as the count allows."""
    rng = np.random.default_rng([seed, 0x0D0E])
    reps = -(-n // n_pool)
    return np.concatenate([rng.permutation(n_pool) for _ in range(reps)])[:n]
