"""Plain reference for proximity search: the matches of a query, computed
straight from the token table.

It imports nothing of the program and reads no index: every answer is
derived from the (doc, pos, lemma) table the benchmark generated. The
semantics are those the search engine documents for each query type
(paper §1.2 and §2; the engine's module docstrings):

* a *lane* is one anchor occurrence that satisfies every constraint of
  the query; its fragment [start, end] spans the anchor and the
  occurrences chosen for the constraints; its relevance is
  ``idf_sum / (1 + max(end - start - (m - 1), 0))`` with
  ``idf = log1p(n_docs / df)`` and m the query length;
* a QT1 query longer than MaxDistance is split into parts of MaxDistance
  lemmas, each answered alone (paper §4); a part shorter than three
  lemmas is answered as QT3/QT4 are;
* QT1 (all stop lemmas) anchors on the most frequent lemma f and covers
  the rest with (f, s, t) keys; a key holds at an anchor when s and t
  occur at two distinct positions within MaxDistance of it (not at the
  anchor), witnessed by the pair of least |o_s| + |o_t|, ties to the
  pair of smaller offsets; keys join on the anchor;
* QT2 (all frequently used) joins (w, v) intervals -- pairs of
  occurrences within MaxDistance -- on the sparsest key's intervals:
  every other key must have an interval starting within 2·MaxDistance
  of the anchor interval's start (the nearest, ties to the earlier);
* QT3/QT4 anchor on the most frequent lemma; QT5 on the rarest non-stop
  lemma. Each other non-stop lemma of multiplicity r needs its r
  nearest occurrences within MaxDistance (candidates ranked by distance,
  then predecessor j before successor j); each stop lemma of a QT5
  query needs r occurrences within MaxDistance, and only its nearest
  (ties to the left) extends the fragment.

A compiled response lists the top-k lanes by relevance; the scalar
route lists the top-k *distinct* fragments. :func:`check_response`
compares a response with the lanes without depending on how ties are
broken.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Lanes:
    """All matching lanes of one query, in anchor order, with each lane's
    relevance."""

    doc: np.ndarray
    start: np.ndarray
    end: np.ndarray
    score: np.ndarray

    @staticmethod
    def concat(parts: list) -> "Lanes":
        return Lanes(*(np.concatenate([getattr(p, f) for p in parts])
                       for f in ("doc", "start", "end", "score")))


class Reference:
    """Per-query proximity matches over one corpus at one MaxDistance."""

    def __init__(self, corpus, max_distance: int):
        d = self.d = int(max_distance)
        self.sw = corpus.sw_count
        self.fu_hi = corpus.sw_count + corpus.fu_count
        n_docs = corpus.n_docs
        # g = G0 + doc * S + pos: gaps wider than 2d between documents, so
        # no window of this module ever reaches into another document
        self.S = int(corpus.doc_lengths.max()) + 2 * d + 2
        self.G0 = 2 * d + 1
        g = (self.G0 + corpus.doc_ids.astype(np.int64) * self.S
             + corpus.positions.astype(np.int64))
        self.tok = np.full(n_docs * self.S + 2 * self.G0, -1, np.int32)
        self.tok[g] = corpus.lemma_ids
        lem = corpus.lemma_ids
        n_lem = int(lem.max()) + 1 if lem.size else 0
        order = np.argsort(lem, kind="stable")  # rows are in g order already
        self._g_by_lemma = g[order]
        self._lemma_start = np.zeros(n_lem + 1, np.int64)
        np.cumsum(np.bincount(lem, minlength=n_lem), out=self._lemma_start[1:])
        # document frequencies and the relevance weights
        pairs = np.unique(corpus.doc_ids.astype(np.int64) * max(n_lem, 1) + lem)
        df = np.bincount(pairs % max(n_lem, 1), minlength=n_lem)
        self.idf = np.log1p(n_docs / np.maximum(df, 1))

    # -- primitives ----------------------------------------------------------
    def occ(self, lemma: int) -> np.ndarray:
        """Sorted g positions of one lemma."""
        if lemma < 0 or lemma + 1 >= self._lemma_start.size:
            return np.zeros(0, np.int64)
        return self._g_by_lemma[self._lemma_start[lemma]:self._lemma_start[lemma + 1]]

    def count(self, lemma: int) -> int:
        return int(self.occ(lemma).size)

    def qtype(self, ids) -> int:
        kinds = {0 if l < self.sw else 1 if l < self.fu_hi else 2 for l in ids}
        if kinds == {0}:
            return 1
        if kinds == {1}:
            return 2
        if kinds == {2}:
            return 3
        return 4 if 0 not in kinds else 5

    def _lanes(self, anchors, ok, lo, hi, ids) -> Lanes:
        sel = np.nonzero(ok)[0]
        doc = (anchors[sel] - self.G0) // self.S
        base = self.G0 + doc * self.S
        start, end = lo[sel] - base, hi[sel] - base
        excess = np.maximum((end - start) - (len(ids) - 1), 0)
        idf_sum = float(sum(self.idf[l] for l in ids))
        return Lanes(doc, start, end, idf_sum / (1.0 + excess))

    def _nearest_r(self, occ, anchors, r: int):
        """The r nearest occurrences within d of each anchor, candidates
        ranked by distance, then by column (pred 1, succ 1, pred 2, ...).
        Returns (matched, least chosen, greatest chosen)."""
        n = anchors.size
        if occ.size == 0:
            return np.zeros(n, bool), anchors, anchors
        idx = np.searchsorted(occ, anchors)
        cols = []
        for j in range(1, r + 1):
            cols.append(idx - j)
            cols.append(idx + j - 1)
        ci = np.stack(cols, axis=1)
        valid = (ci >= 0) & (ci < occ.size)
        cand = np.where(valid, occ[np.clip(ci, 0, occ.size - 1)], 0)
        dist = np.abs(cand - anchors[:, None]).astype(np.float64)
        dist[~valid | (dist > self.d)] = np.inf
        pick = np.argsort(dist, axis=1, kind="stable")[:, :r]
        pd = np.take_along_axis(dist, pick, axis=1)
        pc = np.take_along_axis(cand, pick, axis=1)
        pc = np.where(np.isfinite(pd), pc, anchors[:, None])
        return np.isfinite(pd[:, r - 1]), pc.min(axis=1), pc.max(axis=1)

    # -- query types ---------------------------------------------------------
    def _qt1(self, ids) -> Lanes:
        d = self.d
        s_ids = sorted(ids)
        f = s_ids[0]
        keys = fst_keys(s_ids)
        anchors = self.occ(f)
        offs = np.array([o for o in range(-d, d + 1) if o != 0], np.int64)
        ok = np.ones(anchors.size, bool)
        lo = anchors.copy()
        hi = anchors.copy()
        win = self.tok[anchors[:, None] + offs[None, :]]
        for _, s, t in keys:
            cand = np.nonzero((win == s).any(axis=1) & (win == t).any(axis=1))[0]
            w = win[cand]
            best = np.full(cand.size, np.iinfo(np.int64).max)
            b_lo = np.zeros(cand.size, np.int64)
            b_hi = np.zeros(cand.size, np.int64)
            for i1 in range(offs.size):
                a = w[:, i1]
                for i2 in range(i1 + 1, offs.size):
                    b = w[:, i2]
                    hit = ((a == s) & (b == t)) | ((a == t) & (b == s))
                    cost = abs(int(offs[i1])) + abs(int(offs[i2]))
                    upd = hit & (cost < best)
                    best[upd] = cost
                    b_lo[upd] = min(int(offs[i1]), 0)
                    b_hi[upd] = max(int(offs[i2]), 0)
            found = best < np.iinfo(np.int64).max
            key_ok = np.zeros(anchors.size, bool)
            key_ok[cand[found]] = True
            ok &= key_ok
            rows = cand[found]
            lo[rows] = np.minimum(lo[rows], anchors[rows] + b_lo[found])
            hi[rows] = np.maximum(hi[rows], anchors[rows] + b_hi[found])
        return self._lanes(anchors, ok, lo, hi, ids)

    def _intervals(self, x: int, y: int):
        """(w, v) intervals of one key: every pair of occurrences of x and
        y at distinct positions at most d apart, sorted by (lo, hi)."""
        ox = self.occ(x)
        los, his = [], []
        for o in range(1, self.d + 1):
            for sign in ((1,) if x == y else (1, -1)):
                p = ox + sign * o
                m = self.tok[p] == y
                los.append(np.minimum(ox[m], p[m]))
                his.append(np.maximum(ox[m], p[m]))
        lo = np.concatenate(los) if los else np.zeros(0, np.int64)
        hi = np.concatenate(his) if his else np.zeros(0, np.int64)
        order = np.lexsort((hi, lo))
        return lo[order], hi[order]

    def _qt2(self, ids) -> Lanes:
        s_ids = sorted(ids)
        keys = [(s_ids[i], s_ids[i + 1]) for i in range(0, len(s_ids) - 1, 2)]
        if len(s_ids) % 2 == 1:
            keys.append((s_ids[0], s_ids[-1]))
        lists = [self._intervals(x, y) for x, y in keys]
        order = np.argsort([lo.size for lo, _ in lists], kind="stable")
        lists = [lists[i] for i in order]
        a_lo, a_hi = lists[0]
        ok = np.ones(a_lo.size, bool)
        lo, hi = a_lo.copy(), a_hi.copy()
        sep = 2 * self.d
        for b_lo, b_hi in lists[1:]:
            if b_lo.size == 0:
                ok[:] = False
                break
            idx = np.searchsorted(b_lo, a_lo)
            prev = b_lo[np.clip(idx - 1, 0, b_lo.size - 1)]
            nxt = b_lo[np.clip(idx, 0, b_lo.size - 1)]
            inf = np.iinfo(np.int64).max
            d_prev = np.where((idx >= 1) & (a_lo - prev <= sep), a_lo - prev, inf)
            d_next = np.where((idx < b_lo.size) & (nxt - a_lo <= sep), nxt - a_lo, inf)
            take_prev = d_prev <= d_next
            matched = np.minimum(d_prev, d_next) < inf
            val = np.where(take_prev, prev, nxt)
            j = np.clip(np.searchsorted(b_lo, val), 0, b_lo.size - 1)
            ok &= matched
            lo = np.where(matched, np.minimum(lo, val), lo)
            hi = np.where(matched, np.maximum(hi, b_hi[j]), hi)
        return self._lanes(lo, ok, lo, hi, ids)

    def _window(self, ids, anchor: int, nonstop: list, stops: list) -> Lanes:
        """Anchor occurrences against non-stop r-nearest constraints and
        QT5 stop constraints."""
        anchors = self.occ(anchor)
        ok = np.ones(anchors.size, bool)
        lo = anchors.copy()
        hi = anchors.copy()
        mult: dict[int, int] = {}
        for l in nonstop:
            mult[l] = mult.get(l, 0) + 1
        for l, r in mult.items():
            if l == anchor and r == 1:
                continue
            matched, mn, mx = self._nearest_r(self.occ(l), anchors, r)
            ok &= matched
            lo = np.where(matched, np.minimum(lo, mn), lo)
            hi = np.where(matched, np.maximum(hi, mx), hi)
        smult: dict[int, int] = {}
        for l in stops:
            smult[l] = smult.get(l, 0) + 1
        d = self.d
        for sid, r in smult.items():
            occ = self.occ(sid)
            if occ.size == 0:
                ok[:] = False
                continue
            cnt = (np.searchsorted(occ, anchors + d, side="right")
                   - np.searchsorted(occ, anchors - d, side="left"))
            ok &= cnt >= r
            idx = np.searchsorted(occ, anchors)
            prev = occ[np.clip(idx - 1, 0, occ.size - 1)]
            nxt = occ[np.clip(idx, 0, occ.size - 1)]
            has_prev = (idx >= 1) & (anchors - prev <= d)
            has_next = (idx < occ.size) & (nxt - anchors <= d)
            use_prev = has_prev & (~has_next | (anchors - prev <= nxt - anchors))
            ext = np.where(use_prev, prev - anchors,
                           np.where(has_next, nxt - anchors, 0))
            lo = np.minimum(lo, anchors + np.minimum(ext, 0))
            hi = np.maximum(hi, anchors + np.maximum(ext, 0))
        return self._lanes(anchors, ok, lo, hi, ids)

    def lanes(self, ids) -> Lanes:
        """Every matching lane of the query ``ids`` (lemma ids)."""
        ids = [int(x) for x in ids]
        qt = self.qtype(ids)
        if qt == 1:
            if len(ids) > self.d:
                # paper §4: split into parts of MaxDistance lemmas
                return Lanes.concat([self.lanes(ids[i:i + self.d])
                                     for i in range(0, len(ids), self.d)])
            if len(ids) < 3:
                return self._window(ids, min(ids), ids, [])
            return self._qt1(ids)
        if qt == 2:
            return self._qt2(ids)
        if qt in (3, 4):
            return self._window(ids, min(ids), ids, [])
        nonstop = [l for l in ids if l >= self.sw]
        stops = [l for l in ids if l < self.sw]
        anchor = min(sorted(set(nonstop)), key=lambda l: (self.count(l), l))
        return self._window(ids, anchor, nonstop, stops)


def fst_keys(sorted_ids: list) -> list:
    """The (f, s, t) keys covering a QT1 query (paper §2.2): f the most
    frequent lemma; the rest paired with itself first, distinct leftovers
    in ascending pairs, an odd last leftover with the first lemma already
    covered (or itself)."""
    f = sorted_ids[0]
    rest = sorted_ids[1:] or [f]
    mult: dict[int, int] = {}
    for l in rest:
        mult[l] = mult.get(l, 0) + 1
    pairs, left = [], []
    for l in sorted(mult):
        pairs.extend([(l, l)] * (mult[l] // 2))
        if mult[l] % 2:
            left.append(l)
    for i in range(0, len(left) - 1, 2):
        pairs.append((left[i], left[i + 1]))
    if len(left) % 2:
        last = left[-1]
        covered = [l for p in pairs for l in p if l != last]
        partner = covered[0] if covered else last
        pairs.append((min(partner, last), max(partner, last)))
    keys = []
    for s, t in pairs:
        if (f, s, t) not in keys:
            keys.append((f, s, t))
    return keys


def check_response(lanes: Lanes, results: dict, top_k: int, distinct: bool,
                   score_rtol: float = 1e-5) -> str | None:
    """Compare one response's results with the reference lanes.

    A top-k answer is right when it has min(top_k, n) entries, its
    relevances are the k best of the n (whatever order ties took), and
    each (doc, start, end) is a matching fragment -- as often as it
    appears among the lanes -- with the reference's relevance. Relevances
    agree within float32 rounding (``score_rtol``). ``distinct`` (the
    scalar route) counts each fragment once. Returns None when right,
    else what differs."""
    frags = list(zip(lanes.doc.tolist(), lanes.start.tolist(), lanes.end.tolist()))
    scores = lanes.score.tolist()
    if distinct:
        seen: dict = {}
        for fr, sc in zip(frags, scores):
            seen.setdefault(fr, sc)
        frags, scores = list(seen), list(seen.values())
    k = min(top_k, len(frags))
    got = list(zip(np.asarray(results["doc"]).tolist(),
                   np.asarray(results["start"]).tolist(),
                   np.asarray(results["end"]).tolist()))
    got_scores = np.asarray(results["score"], np.float64).tolist()
    if len(got) != k:
        return f"{len(got)} results, reference {k} (of {len(frags)} matches)"
    close = lambda a, b: abs(a - b) <= score_rtol * abs(b)  # noqa: E731
    want_top = sorted(scores, reverse=True)[:k]
    got_top = sorted(got_scores, reverse=True)
    if not all(close(a, b) for a, b in zip(got_top, want_top)):
        return f"relevances {got_top[:6]}, reference's best {want_top[:6]}"
    pool: dict = {}
    for fr, sc in zip(frags, scores):
        pool.setdefault(fr, []).append(sc)
    for fr, sc in zip(got, got_scores):
        left = pool.get(fr, [])
        match = next((i for i, want in enumerate(left) if close(sc, want)), None)
        if match is None:
            return f"fragment {fr} with relevance {sc} is not a match"
        left.pop(match)
    return None
