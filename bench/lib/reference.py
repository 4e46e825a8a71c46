"""The plain reference: what a query should return, computed straight from
the token stream with no index of the program's.

Semantics of a phrase or near query whose words are all ordinary (no basic
form is a stop or frequent lemma), as `core/engine.py`'s brute-force
oracles state them (this file imports nothing of the program):

* A word stands for its basic forms: it occurs wherever a token has one of
  them.
* Phrase: every word at its offset from the anchor, inside one document;
  the anchor is the first word's position.
* Near: the pivot, the word whose forms occur least often in the whole
  corpus (the first such word on a tie), is the anchor; every other word
  occurs within `near_window` of it in the same document.
* Doc level (the fallback's truth): the documents that hold every word.

Queries with a stop or frequent form are refused: their semantics (tier
splits, stop-phrase windows) are not restated here.

`Reference(..., pos_dtype=np.int16)` gives the control: the same
computation with in-document positions held one precision below the int32
the configuration states.
"""
from __future__ import annotations

import numpy as np

from bench.lib.corpus import Corpus, Forms, Lexicon, tier_of

ORDINARY = 2


class Answer:
    """Reference answer: sorted unique anchor codes (doc << 32 | pos) and
    the doc-level set."""

    def __init__(self, codes, doc_level):
        self.codes = codes
        self.doc_level = doc_level


def anchor_codes(doc, pos) -> np.ndarray:
    return (np.asarray(doc, np.int64) << 32) + np.asarray(pos, np.int64)


class Reference:
    def __init__(self, corpus: Corpus, lex: Lexicon, forms: Forms,
                 params: dict, pos_dtype=np.int32):
        self.lex = lex
        self.forms = forms
        self.near_window = int(params["near_window"])
        self.pos_dtype = pos_dtype
        T = corpus.n_tokens
        self.T = T
        prim = forms.primary[corpus.tokens].astype(np.int64)
        sec = forms.secondary[corpus.tokens].astype(np.int64)
        lengths = np.diff(corpus.doc_offsets)
        self.doc_of = np.repeat(np.arange(corpus.n_docs, dtype=np.int64),
                                lengths)
        self.doc_start = corpus.doc_offsets[:-1].astype(np.int64)
        self.doc_end = corpus.doc_offsets[1:].astype(np.int64)
        # every (form, token) pair, sorted by form then token
        has2 = sec >= 0
        tok = np.arange(T, dtype=np.int64)
        key = np.concatenate([prim * T + tok, sec[has2] * T + tok[has2]])
        key.sort()
        self.occ_tok = key % T
        self.occ_off = np.searchsorted(key // T, np.arange(lex.n_base + 1))
        self.occ_count = np.diff(self.occ_off)

    def word_forms(self, w: int) -> list:
        fs = [int(self.forms.primary[w])]
        if self.forms.secondary[w] >= 0:
            fs.append(int(self.forms.secondary[w]))
        if any(int(t) != ORDINARY for t in tier_of(self.lex, fs)):
            raise ValueError(f"word {w} has a stop or frequent form; the "
                             "reference covers ordinary words only")
        return fs

    def occurrences(self, forms) -> np.ndarray:
        """Sorted token indices holding any of `forms`."""
        parts = [self.occ_tok[self.occ_off[f]:self.occ_off[f + 1]]
                 for f in forms]
        if len(parts) == 1:
            return parts[0]
        return np.unique(np.concatenate(parts))

    def pos(self, t) -> np.ndarray:
        p = t - self.doc_start[self.doc_of[t]]
        return p.astype(self.pos_dtype).astype(np.int64)

    def _phrase(self, occs) -> np.ndarray:
        n = len(occs)
        t = occs[0]
        t = t[t + n - 1 < self.T]
        for i in range(1, n):
            o = occs[i]
            if not len(o):
                return t[:0]
            j = np.minimum(np.searchsorted(o, t + i), len(o) - 1)
            t = t[o[j] == t + i]
        return t[self.doc_of[t] == self.doc_of[t + n - 1]]

    def _near(self, occs, pivot) -> np.ndarray:
        p = occs[pivot]
        d = self.doc_of[p]
        lo = np.maximum(p - self.near_window, self.doc_start[d])
        hi = np.minimum(p + self.near_window, self.doc_end[d] - 1)
        ok = np.ones(len(p), bool)
        for i, occ in enumerate(occs):
            if i != pivot:
                ok &= (np.searchsorted(occ, lo, "left")
                       < np.searchsorted(occ, hi, "right"))
        return p[ok]

    def answer(self, q) -> Answer:
        """`q`: words and mode (bench.lib.traffic.Query)."""
        slots = [self.word_forms(w) for w in q.words]
        occs = [self.occurrences(fs) for fs in slots]
        if q.mode == "phrase":
            t = self._phrase(occs)
        elif q.mode == "near":
            counts = [sum(int(self.occ_count[f]) for f in fs) for fs in slots]
            t = self._near(occs, counts.index(min(counts)))
        else:
            raise ValueError(f"mode {q.mode!r} is not covered")
        codes = np.unique(anchor_codes(self.doc_of[t], self.pos(t)))
        docs = None
        for occ in occs:
            ds = set(np.unique(self.doc_of[occ]).tolist())
            docs = ds if docs is None else docs & ds
        return Answer(codes, docs or set())
