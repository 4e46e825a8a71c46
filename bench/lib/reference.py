"""The plain reference: what a query should return, computed straight from
the token stream with no index of the program's.

The paper's semantics (arXiv:1801.09079), as `core/engine.py`'s
`brute_force_search` states them; this file imports nothing of the program:

* A word stands for its basic forms: it occurs wherever a token has one of
  them.  Basic-form ids are frequency ranks, so a form's tier is its rank
  band: 0 stop, 1 frequent, 2 ordinary (`corpus.tier_of`).
* Tier splits: each word's forms are grouped by tier; each element of the
  product of the words' groups is one subquery, and a query's positional
  answer is the union of its subqueries' answers.
* A subquery whose every slot is a stop group (the stop-phrase index),
  with at least `min_len` slots, in either mode: it is cut into parts of
  `min_len` to `max_len` slots (`split_parts`); a window of a part's length
  inside one document matches the part where one stop form per token and
  one form per slot give equal sorted multisets (word order does not
  count); its anchor is the window's start less the part's start; the
  parts' anchor sets are intersected.  It has no doc-level answer.
* Any other subquery, phrase mode: every slot's forms at its offset from
  the anchor, inside one document.
* Any other subquery, near mode: the pivot (`pivot`) is the anchor; every
  other slot, stop slots too, occurs within `near_window` of it in the
  same document.
* Doc level (the fallback's truth): for each subquery with a non-stop
  slot, the documents that hold every non-stop slot; their union.

All matching is done on global token indices with sorted occurrence
lists and `searchsorted`; anchors become (document, position) codes last.

`Reference(..., pos_dtype=np.int16)` gives the control: the same
computation with in-document positions held one precision below the int32
the configuration states.
"""
from __future__ import annotations

import itertools

import numpy as np

from bench.lib.corpus import Corpus, Forms, Lexicon, tier_of

STOP, FREQUENT, ORDINARY = 0, 1, 2


class Answer:
    """Reference answer: sorted unique anchor codes (doc << 32 | pos) and
    the doc-level set."""

    def __init__(self, codes, doc_level):
        self.codes = codes
        self.doc_level = doc_level


def anchor_codes(doc, pos) -> np.ndarray:
    return (np.asarray(doc, np.int64) << 32) + np.asarray(pos, np.int64)


def split_parts(n: int, min_len: int, max_len: int) -> list:
    """(start, length) parts of an n-slot stop phrase, each of `min_len` to
    `max_len` slots, covering every slot; a tail too short overlaps
    backwards.  The rule of the program's `planner.split_query_parts`."""
    parts, i = [], 0
    while i < n:
        L = min(max_len, n - i)
        if L < min_len:
            parts.append((n - min_len, min_len))
            break
        rem = n - i - L
        if 0 < rem < min_len:
            L = max(L - (min_len - rem), min_len)
        parts.append((i, L))
        i += L
    return parts


def pivot(sub: tuple, counts: list) -> int:
    """The near anchor of a subquery with a non-stop slot: its rarest
    ordinary slot, else its rarest frequent one, by the total occurrences
    of the slot's forms; the first on a tie.  The rule of the program's
    `planner.pick_pivot`."""
    tiers = [t for t, _ in sub]
    tier = ORDINARY if ORDINARY in tiers else FREQUENT
    return min((i for i, t in enumerate(tiers) if t == tier),
               key=counts.__getitem__)


def _member(occ: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Which of `t` are in the sorted array `occ`."""
    if not len(occ):
        return np.zeros(len(t), bool)
    j = np.minimum(np.searchsorted(occ, t), len(occ) - 1)
    return occ[j] == t


class Reference:
    def __init__(self, corpus: Corpus, lex: Lexicon, forms: Forms,
                 params: dict, pos_dtype=np.int32):
        self.lex = lex
        self.forms = forms
        self.near_window = int(params["near_window"])
        self.min_len = int(params["min_len"])
        self.max_len = int(params["max_len"])
        self.pos_dtype = pos_dtype
        T = corpus.n_tokens
        self.T = T
        self.prim = forms.primary[corpus.tokens]
        self.sec = forms.secondary[corpus.tokens]
        prim = self.prim.astype(np.int64)
        sec = self.sec.astype(np.int64)
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

    def slot_groups(self, w: int) -> list:
        """A word's basic forms grouped by tier: [(tier, forms)], by tier."""
        fs = [int(self.forms.primary[w])]
        if self.forms.secondary[w] >= 0:
            fs.append(int(self.forms.secondary[w]))
        groups: dict = {}
        for f, t in zip(fs, tier_of(self.lex, fs)):
            groups.setdefault(int(t), []).append(f)
        return sorted((t, tuple(g)) for t, g in groups.items())

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
        """Anchors with slot i's forms at anchor + i, in one document;
        candidates come from the slot with the fewest occurrences."""
        n = len(occs)
        r = min(range(n), key=lambda i: len(occs[i]))
        a = occs[r] - r
        a = a[(a >= 0) & (a + n - 1 < self.T)]
        for i, occ in enumerate(occs):
            if i != r:
                a = a[_member(occ, a + i)]
        return a[self.doc_of[a] == self.doc_of[a + n - 1]]

    def _near(self, occs, piv: int) -> np.ndarray:
        p = occs[piv]
        d = self.doc_of[p]
        lo = np.maximum(p - self.near_window, self.doc_start[d])
        hi = np.minimum(p + self.near_window, self.doc_end[d] - 1)
        ok = np.ones(len(p), bool)
        for i, occ in enumerate(occs):
            if i != piv:
                ok &= (np.searchsorted(occ, lo, "left")
                       < np.searchsorted(occ, hi, "right"))
        return p[ok]

    def _stop_part(self, groups) -> np.ndarray:
        """Starts of the windows of len(groups) tokens inside one document
        that match the slots' stop forms as a multiset.  Only tokens that
        hold one of those forms can take part in a match."""
        L = len(groups)
        used = sorted({f for g in groups for f in g})
        occ = self.occurrences(used)
        t = occ[occ + L - 1 < self.T]
        for i in range(1, L):
            t = t[_member(occ, t + i)]
        t = t[self.doc_of[t] == self.doc_of[t + L - 1]]
        if not len(t):
            return t
        base = self.lex.n_stop
        if base ** L >= 1 << 63:
            raise ValueError(f"{L} stop forms of {base} overflow the key")

        def encode(chosen):             # a sorted multiset as one integer
            s = np.sort(chosen, axis=-1)
            return (s * base ** np.arange(L, dtype=np.int64)).sum(-1)

        want = encode(np.array(list(itertools.product(*groups)), np.int64))
        win = t[:, None] + np.arange(L)
        cand = []                       # each token's forms among `used`
        for col in (self.prim[win], self.sec[win]):
            col = col.astype(np.int64)
            cand.append(np.where(np.isin(col, used), col, -1))
        hit = np.zeros(len(t), bool)
        for pick in itertools.product((0, 1), repeat=L):
            chosen = np.where(np.array(pick, bool), cand[1], cand[0])
            ok = (chosen >= 0).all(-1)
            hit |= ok & np.isin(encode(np.maximum(chosen, 0)), want)
        return t[hit]

    def _stop(self, sub) -> np.ndarray:
        """Anchors of an all-stop subquery: its parts' anchors intersected;
        a part's anchor is its window's start less the part's start, in the
        window's document."""
        out = None
        for start, L in split_parts(len(sub), self.min_len, self.max_len):
            t = self._stop_part([fs for _, fs in sub[start:start + L]])
            a = t - start
            a = a[a >= self.doc_start[self.doc_of[t]]]
            out = a if out is None else np.intersect1d(out, a)
        return out

    def answer(self, q) -> Answer:
        """`q`: words and mode (bench.lib.traffic.Query)."""
        if q.mode not in ("phrase", "near"):
            raise ValueError(f"mode {q.mode!r} is not covered")
        anchors, docs = [], set()
        for sub in itertools.product(*map(self.slot_groups, q.words)):
            if all(t == STOP for t, _ in sub):
                if len(sub) >= self.min_len:
                    anchors.append(self._stop(sub))
                continue
            occs = [self.occurrences(fs) for _, fs in sub]
            if q.mode == "phrase":
                anchors.append(self._phrase(occs))
            else:
                counts = [int(self.occ_count[list(fs)].sum())
                          for _, fs in sub]
                anchors.append(self._near(occs, pivot(sub, counts)))
            held = None
            for (tier, _), occ in zip(sub, occs):
                if tier != STOP:
                    ds = set(np.unique(self.doc_of[occ]).tolist())
                    held = ds if held is None else held & ds
            docs |= held
        t = np.concatenate(anchors) if anchors else np.zeros(0, np.int64)
        codes = np.unique(anchor_codes(self.doc_of[t], self.pos(t)))
        return Answer(codes, docs)
