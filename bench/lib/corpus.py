"""The benchmark's own draw of a deployment's data: lexicon tiers, the
morphological analyzer's surface -> basic-form map, and the token stream.

A copy of the draw in the program's `core/lexicon.py`, `core/analyzer.py`
and `core/corpus.py`, kept here so that a later change to the program
cannot move the data the benchmark measures on.  One addition: a cap on a
document's length (`max_doc_len`), applied after the log-normal draw, so a
configuration can truncate the doc-length tail and say so.

Everything is numpy and is made from the seed alone.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Lexicon:
    n_surface: int
    n_base: int
    n_stop: int
    n_frequent: int
    multi_form_frac: float
    zipf_s: float
    seed: int


@dataclasses.dataclass
class Forms:
    """Surface id -> basic forms: `primary` [n_surface], `secondary`
    [n_surface] (-1 where a surface has one form)."""
    primary: np.ndarray
    secondary: np.ndarray


def draw_forms(lex: Lexicon) -> Forms:
    """The analyzer's map: primary form by Zipf rank, a second form for
    `multi_form_frac` of surfaces at a log-uniform rank."""
    rng = np.random.default_rng(lex.seed + 0xA11A)
    n_s, n_b = lex.n_surface, lex.n_base
    primary = (np.arange(n_s, dtype=np.int64) * n_b // n_s).astype(np.int32)
    has_second = rng.random(n_s) < lex.multi_form_frac
    log_rank = rng.uniform(0.0, np.log(n_b), size=n_s)
    secondary = np.exp(log_rank).astype(np.int32) % n_b
    has_second &= secondary != primary
    return Forms(primary=primary,
                 secondary=np.where(has_second, secondary, -1).astype(np.int32))


def tier_of(lex: Lexicon, forms: np.ndarray) -> np.ndarray:
    """0 stop, 1 frequent, 2 ordinary (basic-form ids are frequency ranks)."""
    forms = np.asarray(forms)
    return np.where(forms < lex.n_stop, 0,
                    np.where(forms < lex.n_stop + lex.n_frequent, 1, 2))


def zipf_probs(n: int, s: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-s)
    return p / p.sum()


@dataclasses.dataclass
class Corpus:
    doc_offsets: np.ndarray        # [n_docs + 1] int64
    tokens: np.ndarray             # [T] int32 surface ids

    @property
    def n_docs(self) -> int:
        return len(self.doc_offsets) - 1

    @property
    def n_tokens(self) -> int:
        return int(self.doc_offsets[-1])

    def doc(self, i: int) -> np.ndarray:
        return self.tokens[self.doc_offsets[i]:self.doc_offsets[i + 1]]


def draw_corpus(lex: Lexicon, forms: Forms, *, n_docs: int,
                median_doc_len: float, sigma_doc_len: float,
                max_doc_len: int, burstiness: float, stop_mass: float,
                seed: int) -> Corpus:
    """Zipf tokens over the surface vocabulary, re-weighted so that the
    expected share of tokens with a stop form is `stop_mass`; log-normal
    document lengths (median `median_doc_len`) capped at `max_doc_len`;
    in-document re-use of a recent token with probability `burstiness`."""
    rng = np.random.default_rng(seed + 0xC0)
    probs = zipf_probs(lex.n_surface, lex.zipf_s)
    stop = (forms.primary < lex.n_stop) | (
        (forms.secondary >= 0) & (forms.secondary < lex.n_stop))
    q = float(probs[stop].sum())
    t = float(stop_mass)
    alpha = t * (1.0 - q) / (q * (1.0 - t))
    probs = np.where(stop, probs * alpha, probs)
    probs = probs / probs.sum()

    lengths = rng.lognormal(np.log(median_doc_len), sigma_doc_len, n_docs)
    lengths = np.minimum(np.maximum(lengths.astype(np.int64), 8), max_doc_len)
    doc_offsets = np.zeros(n_docs + 1, dtype=np.int64)
    np.cumsum(lengths, out=doc_offsets[1:])
    total = int(doc_offsets[-1])
    cdf = np.cumsum(probs)
    tokens = np.searchsorted(cdf, rng.random(total)).astype(np.int32)
    np.minimum(tokens, lex.n_surface - 1, out=tokens)
    if burstiness > 0:
        lag = rng.integers(1, 64, size=total)
        src = np.maximum(np.arange(total) - lag, 0)
        doc_of = np.repeat(np.arange(n_docs), lengths)
        take = (rng.random(total) < burstiness) & (doc_of[src] == doc_of)
        tokens[take] = tokens[src[take]]
    return Corpus(doc_offsets=doc_offsets, tokens=tokens)


def lexicon_from(cfg: dict, seed: int) -> Lexicon:
    c = cfg["lexicon"]
    return Lexicon(n_surface=c["n_surface"], n_base=c["n_base"],
                   n_stop=c["n_stop"], n_frequent=c["n_frequent"],
                   multi_form_frac=c["multi_form_frac"], zipf_s=c["zipf_s"],
                   seed=seed)


def corpus_from(cfg: dict, lex: Lexicon, forms: Forms, seed: int) -> Corpus:
    c = cfg["corpus"]
    return draw_corpus(lex, forms, n_docs=c["n_docs"],
                       median_doc_len=c["median_doc_len"],
                       sigma_doc_len=c["sigma_doc_len"],
                       max_doc_len=c["max_doc_len"],
                       burstiness=c["burstiness"], stop_mass=c["stop_mass"],
                       seed=seed)
