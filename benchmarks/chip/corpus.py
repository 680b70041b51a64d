"""The benchmark's own corpus generator and query samplers.

Copies of ``generate_corpus``, ``sample_stop_queries`` and
``sample_mixed_queries`` of the program's ``data/corpus.py``, kept here
so that no later change to the program can move the yardstick. They
draw the same numbers from the same generator calls, so for one seed
they give the same corpus and the same queries as the originals
(``tests/bench`` checks this); the samplers take a document's window
as a slice of the (doc, pos)-sorted table instead of masking the whole
table, which makes them fast at millions of tokens.

The corpus is returned as plain arrays (:class:`Corpus`); the harness
turns them into the program's own input types, and the reference reads
them directly.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Corpus:
    """A Zipf corpus as a flat (doc, pos, lemma) table sorted by
    (doc, pos), one lemma per position. Lemma ids are frequency ranks."""

    doc_ids: np.ndarray      # int32 (T,)
    positions: np.ndarray    # int32 (T,)
    lemma_ids: np.ndarray    # int32 (T,)
    doc_lengths: np.ndarray  # int32 (n_docs,)
    counts: np.ndarray       # int64 (n_seen,) occurrences per lemma id
    doc_freqs: np.ndarray    # int64 (n_seen,) documents per lemma id
    sw_count: int            # ids below are stop lemmas
    fu_count: int            # the next ids are frequently used lemmas

    @property
    def n_docs(self) -> int:
        return int(self.doc_lengths.size)

    @property
    def n_rows(self) -> int:
        return int(self.doc_ids.size)

    @property
    def doc_starts(self) -> np.ndarray:
        """Row index of each document's first token (n_docs + 1,)."""
        out = np.zeros(self.n_docs + 1, np.int64)
        np.cumsum(self.doc_lengths, out=out[1:])
        return out


def zipf_probs(vocab_size: int, alpha: float = 1.1, shift: float = 2.7) -> np.ndarray:
    """Zipf-Mandelbrot pmf over ranks 0..vocab_size-1."""
    ranks = np.arange(vocab_size, dtype=np.float64)
    w = 1.0 / np.power(ranks + shift, alpha)
    return w / w.sum()


def generate_corpus(n_docs: int, mean_doc_len: int, vocab_size: int,
                    alpha: float, sw_count: int, fu_count: int,
                    seed: int) -> Corpus:
    """Zipf corpus with lemma ids re-ranked by observed frequency, so id
    == FL-number holds exactly. SWCount and FUCount are capped at a third
    of the lemmas seen, as in the original."""
    rng = np.random.default_rng(seed)
    lengths = np.maximum(8, rng.poisson(mean_doc_len, n_docs)).astype(np.int64)
    total = int(lengths.sum())
    probs = zipf_probs(vocab_size, alpha)
    raw = rng.choice(vocab_size, size=total, p=probs).astype(np.int32)

    counts = np.bincount(raw, minlength=vocab_size)
    order = np.lexsort((np.arange(vocab_size), -counts))  # observed rank -> raw id
    rank_of = np.empty(vocab_size, np.int32)
    rank_of[order] = np.arange(vocab_size, dtype=np.int32)
    tokens = rank_of[raw]

    doc_ids = np.repeat(np.arange(n_docs, dtype=np.int32), lengths)
    positions = np.concatenate([np.arange(n, dtype=np.int32) for n in lengths])

    sorted_counts = counts[order]
    n_seen = int((sorted_counts > 0).sum())
    pair = doc_ids.astype(np.int64) * vocab_size + tokens
    uniq = np.unique(pair)
    dfs = np.bincount((uniq % vocab_size).astype(np.int64), minlength=vocab_size)
    return Corpus(
        doc_ids=doc_ids, positions=positions, lemma_ids=tokens,
        doc_lengths=lengths.astype(np.int32),
        counts=sorted_counts[:n_seen].astype(np.int64),
        doc_freqs=dfs[:n_seen].astype(np.int64),
        sw_count=min(sw_count, n_seen // 3),
        fu_count=min(fu_count, n_seen // 3),
    )


def permute_docs(corpus: Corpus, seed: int) -> Corpus:
    """The same documents in another order: document k of the result is
    document ``order[k]`` of ``corpus`` for a permutation drawn from the
    seed. Every posting row keeps its length, so the work is the same."""
    order = np.random.default_rng(seed).permutation(corpus.n_docs)
    lengths = corpus.doc_lengths[order].astype(np.int64)
    new_starts = np.zeros(corpus.n_docs, np.int64)
    np.cumsum(lengths[:-1], out=new_starts[1:])
    rows = (np.repeat(corpus.doc_starts[:-1][order] - new_starts, lengths)
            + np.arange(int(lengths.sum())))
    return Corpus(
        doc_ids=np.repeat(np.arange(corpus.n_docs, dtype=np.int32), lengths),
        positions=corpus.positions[rows], lemma_ids=corpus.lemma_ids[rows],
        doc_lengths=lengths.astype(np.int32), counts=corpus.counts,
        doc_freqs=corpus.doc_freqs, sw_count=corpus.sw_count,
        fu_count=corpus.fu_count)


def _window(corpus: Corpus, starts: np.ndarray, row: int, window: int) -> np.ndarray:
    """Lemmas of the rows within ``window`` positions of ``row`` in its
    document, in row order (what the original's full-table mask selects)."""
    d = int(corpus.doc_ids[row])
    p = int(corpus.positions[row])
    s = int(starts[d])
    n = int(corpus.doc_lengths[d])
    return corpus.lemma_ids[s + max(0, p - window):s + min(n - 1, p + window) + 1]


def sample_stop_queries(corpus: Corpus, n_queries: int, min_len: int = 3,
                        max_len: int = 5, window: int = 9,
                        seed: int = 0) -> list[list[int]]:
    """QT1 queries (all stop lemmas) of ``min_len``..``max_len`` lemmas,
    drawn from real co-occurrence windows."""
    rng = np.random.default_rng(seed)
    starts = corpus.doc_starts
    sw = corpus.sw_count
    stop_rows = np.nonzero(corpus.lemma_ids < sw)[0]
    queries: list[list[int]] = []
    guard = 0
    while len(queries) < n_queries and guard < n_queries * 50:
        guard += 1
        r = int(rng.choice(stop_rows))
        lems = _window(corpus, starts, r, window)
        lems = lems[lems < sw]
        if lems.size < min_len:
            continue
        L = int(rng.integers(min_len, max_len + 1))
        take = rng.choice(lems.size, size=min(L, lems.size), replace=False)
        q = [int(x) for x in lems[take]]
        if len(q) >= min_len:
            queries.append(q)
    return queries


def sample_typed_queries(corpus: Corpus, n_queries: int, qtype: str,
                         min_len: int = 3, max_len: int = 5, window: int = 9,
                         seed: int = 0) -> list[list[int]]:
    """Queries of one QT class from real co-occurrence windows: ``qt1``
    all stop, ``qt2`` all frequently used, ``qt3`` all ordinary, ``qt4``
    frequently used plus ordinary, ``qt5`` stop plus non-stop."""
    rng = np.random.default_rng(seed)
    starts = corpus.doc_starts
    sw = corpus.sw_count
    fu_hi = sw + corpus.fu_count
    preds = {
        "qt1": lambda l: l < sw,
        "qt2": lambda l: (l >= sw) & (l < fu_hi),
        "qt3": lambda l: l >= fu_hi,
    }
    split = {
        "qt4": (preds["qt2"], lambda l: l >= fu_hi),
        "qt5": (lambda l: l < sw, lambda l: l >= sw),
    }
    seed_pred = split[qtype][0] if qtype in split else preds[qtype]
    seed_rows = np.nonzero(seed_pred(corpus.lemma_ids))[0]
    queries: list[list[int]] = []
    guard = 0
    while len(queries) < n_queries and guard < n_queries * 200 and seed_rows.size:
        guard += 1
        r = int(rng.choice(seed_rows))
        lems = _window(corpus, starts, r, window)
        L = int(rng.integers(min_len, max_len + 1))
        if qtype in split:
            pa, pb = split[qtype]
            a = lems[pa(lems)]
            b = lems[pb(lems)]
            if a.size < 1 or b.size < 1:
                continue
            k_a = int(rng.integers(1, min(L - 1, a.size) + 1))
            k_b = min(L - k_a, int(b.size))
            q = [int(x) for x in rng.choice(a, size=k_a, replace=False)]
            q += [int(x) for x in rng.choice(b, size=k_b, replace=False)]
        else:
            pool = lems[preds[qtype](lems)]
            if pool.size < min_len:
                continue
            take = rng.choice(pool.size, size=min(L, pool.size), replace=False)
            q = [int(x) for x in pool[take]]
        if len(q) >= min_len:
            queries.append(q)
    return queries


def sample_mixed_queries(corpus: Corpus, n_queries: int,
                         kinds: tuple = ("qt1", "qt2", "qt3", "qt4", "qt5"),
                         min_len: int = 3, max_len: int = 5, window: int = 9,
                         seed: int = 0) -> list[list[int]]:
    """Round-robin interleave of per-class samples across ``kinds``."""
    per = -(-n_queries // len(kinds))
    cols = [sample_typed_queries(corpus, per, k, min_len, max_len, window, seed + i)
            for i, k in enumerate(kinds)]
    out: list[list[int]] = []
    for i in range(per):
        for c in cols:
            if i < len(c):
                out.append(c[i])
    return out[:n_queries]
