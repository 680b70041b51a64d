"""TPU-adapted batched proximity search (the paper's engine as a jitted,
shardable serve step).

Key re-design vs the CPU engine (DESIGN.md §3):
* postings live in dense, padded int32 device arrays; (doc, pos) pairs are
  packed as g = doc * stride + pos (documents are strided so windows can't
  cross them);
* a batch of B QT1 queries is evaluated at once; each query carries K
  three-component-key posting lists of bucketed length L (padding =
  SENTINEL). K and L are *static* — the compiled step is the response-time
  guarantee;
* Equalize == sorted intersection: key list 0 is the anchor stream; lists
  1..K-1 are joined via vectorized searchsorted membership on every
  backend (the QT1 step has no Pallas path);
* the index is document-sharded over the `model` mesh axis (each shard
  holds a doc range of every posting list); queries are batch-sharded over
  `pod`/`data`. Per-shard top-k results are all-gathered (k entries per
  shard — tiny collective) and reduced to a global top-k.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.index_builder import ProximityIndex
from repro.core.query import qt2_plan, qt34_plan, qt5_plan, select_fst_keys
from repro.kernels.common import SENTINEL
from repro.kernels.nearest_r import window_join

NEG_INF = jnp.float32(-1e30)


# --------------------------------------------------------------------------
# batched single-device primitives
# --------------------------------------------------------------------------
def _membership(g0: jnp.ndarray, gk: jnp.ndarray):
    """Batched membership of g0 rows in gk rows: (B, L) int32 each."""

    def one(g0_row, gk_row):
        idx = jnp.searchsorted(gk_row, g0_row)
        idx_c = jnp.clip(idx, 0, gk_row.shape[0] - 1)
        found = (gk_row[idx_c] == g0_row) & (g0_row != SENTINEL)
        return found, idx_c

    return jax.vmap(one)(g0, gk)


def qt1_join(key_g: jnp.ndarray, key_lo: jnp.ndarray, key_hi: jnp.ndarray):
    """Join K key posting lists on the anchor stream (list 0).

    key_g/lo/hi: (B, K, L) int32. Returns (valid, lo, hi) each (B, L),
    aligned with the anchor list."""
    K = key_g.shape[1]
    g0 = key_g[:, 0]
    valid = g0 != SENTINEL
    lo = key_lo[:, 0]
    hi = key_hi[:, 0]
    for k in range(1, K):
        found, idx = _membership(g0, key_g[:, k])
        valid &= found
        lo_k = jnp.take_along_axis(key_lo[:, k], idx, axis=1)
        hi_k = jnp.take_along_axis(key_hi[:, k], idx, axis=1)
        lo = jnp.where(found, jnp.minimum(lo, lo_k), lo)
        hi = jnp.where(found, jnp.maximum(hi, hi_k), hi)
    return valid, lo, hi


def qt1_score(valid, lo, hi, idf_sum, span_adjust):
    span_excess = jnp.maximum((hi - lo) - span_adjust[:, None], 0)
    return jnp.where(valid, idf_sum[:, None] / (1.0 + span_excess.astype(jnp.float32)), NEG_INF)


def qt1_topk(score, g_anchor, lo, hi, k: int):
    # top_k is an upper bound on results: a row shorter than k returns
    # all of its lanes (invalid ones carry NEG_INF and decode drops them)
    top_s, top_i = jax.lax.top_k(score, min(k, score.shape[-1]))
    take = lambda x: jnp.take_along_axis(x, top_i, axis=1)
    return top_s, take(g_anchor), take(lo), take(hi)


# --------------------------------------------------------------------------
# (w,v)-key / NSW joins (QT2 and QT5)
# --------------------------------------------------------------------------
BIG_DIST = jnp.int32(2**31 - 1)  # "no candidate" distance (> any max_sep)


def _nearest1(b_rows, centers, max_sep: int):
    """Batched nearest-value lookup: for each center, the closest value of
    the sorted row b within max_sep. (B, L) int32 each, SENTINEL-padded.
    Ties prefer the predecessor (the CPU engine's candidate column order
    [idx-1, idx] under a stable sort). Returns (matched, value, first_idx)
    where first_idx is the value's *first* occurrence in b — the CPU
    engine recovers the partner interval's end via searchsorted on starts,
    which lands on the first duplicate."""
    Lb = b_rows.shape[-1]

    def one(b_row, c_row):
        idx = jnp.searchsorted(b_row, c_row)
        prev = b_row[jnp.clip(idx - 1, 0, Lb - 1)]
        nxt = b_row[jnp.clip(idx, 0, Lb - 1)]
        d_prev = jnp.where((idx >= 1) & (prev != SENTINEL), c_row - prev, BIG_DIST)
        d_next = jnp.where((idx < Lb) & (nxt != SENTINEL), nxt - c_row, BIG_DIST)
        d_prev = jnp.where(d_prev <= max_sep, d_prev, BIG_DIST)
        d_next = jnp.where(d_next <= max_sep, d_next, BIG_DIST)
        take_prev = d_prev <= d_next
        matched = jnp.where(take_prev, d_prev, d_next) <= max_sep
        val = jnp.where(matched, jnp.where(take_prev, prev, nxt), c_row)
        first = jnp.clip(jnp.searchsorted(b_row, val), 0, Lb - 1)
        return matched, val, first

    return jax.vmap(one)(b_rows, centers)


def qt2_join(wv_lo, wv_hi, n_keys, max_sep: int):
    """Join K (w,v)-interval lists on the anchor list (list 0 — the host
    packers order lists sparsest-first, mirroring the CPU engine's anchor
    choice). wv_lo/wv_hi: (B, K, L) int32 sorted by lo, SENTINEL-padded;
    n_keys: (B,) int32 — lists k >= n_keys[b] are padding and do not
    constrain. For every anchor interval each other list must contribute
    an interval starting within max_sep (= 2*MaxDistance); the nearest
    such interval extends the fragment. Returns (valid, lo, hi) aligned
    with the anchor list."""
    K = wv_lo.shape[1]
    a_lo = wv_lo[:, 0]
    valid = a_lo != SENTINEL
    lo = a_lo
    hi = wv_hi[:, 0]
    for k in range(1, K):
        m, val, j = _nearest1(wv_lo[:, k], a_lo, max_sep)
        b_hi = jnp.take_along_axis(wv_hi[:, k], j, axis=1)
        active = (jnp.int32(k) < n_keys)[:, None]
        valid &= m | ~active
        upd = active & m
        lo = jnp.where(upd, jnp.minimum(lo, val), lo)
        hi = jnp.where(upd, jnp.maximum(hi, b_hi), hi)
    return valid, lo, hi


def _nearest_r_multi(b_rows, centers, max_sep: int, r, r_max: int):
    """Batched r-nearest membership (device twin of search.py's
    ``_nearest_r``): for each center, whether the sorted row b holds r
    distinct values within max_sep, plus the min/max of the r nearest.
    r: (B,) traced multiplicity (r == 0 rows are ignored by the caller).
    Candidate columns mirror the CPU order [idx-1, idx, idx-2, idx+1, …]
    and the sort is stable, so tie-breaking matches numpy's insertion
    sort at these widths (2*r_max <= 16)."""
    Lb = b_rows.shape[-1]
    jcol = np.arange(2 * r_max) // 2  # candidate ring index per column

    def one(b_row, c_row, r1):
        idx = jnp.searchsorted(b_row, c_row)
        cols = []
        for j in range(1, r_max + 1):
            cols.append(idx - j)
            cols.append(idx + (j - 1))
        ci = jnp.stack(cols, axis=1)
        ok = (ci >= 0) & (ci < Lb)
        cand = jnp.where(ok, b_row[jnp.clip(ci, 0, Lb - 1)], 0)
        ok &= cand != SENTINEL
        dist = jnp.abs(cand - c_row[:, None])
        ok &= dist <= max_sep
        ok &= jnp.asarray(jcol)[None, :] < r1
        dist = jnp.where(ok, dist, BIG_DIST)
        order = jnp.argsort(dist, axis=1)
        d_sorted = jnp.take_along_axis(dist, order, axis=1)
        c_sorted = jnp.take_along_axis(cand, order, axis=1)
        matched = jnp.take(d_sorted, jnp.clip(r1 - 1, 0, 2 * r_max - 1), axis=1) <= max_sep
        keep = (jnp.arange(2 * r_max)[None, :] < r1) & (d_sorted <= max_sep)
        chosen = jnp.where(keep, c_sorted, c_row[:, None])
        return matched, chosen.min(axis=1), chosen.max(axis=1)

    return jax.vmap(one)(b_rows, centers, r)


def qt34_join(a_g, ns_g, ns_r, max_sep: int, r_max: int,
              use_pallas: bool = False, interpret: bool = False):
    """Ordinary-window join (QT3/QT4, DESIGN.md §13): the anchor lemma's
    ordinary posting row against the other lemmas' ordinary rows — for
    each anchor posting, every other row must hold r distinct positions
    within MaxDistance (r = the lemma's query multiplicity, traced per
    key, r <= static r_max); the r nearest extend the fragment. This is
    the device twin of ``search.ProximitySearchEngine._ordinary_window``
    and exactly the non-stop half of the QT5 join, which reuses it.
    Keys with r == 0 are padding and do not constrain. a_g: (B, L);
    ns_g: (B, Kn, L); ns_r: (B, Kn). Returns (valid, lo, hi) aligned
    with the anchor row.

    Delegates to ``kernels.nearest_r.window_join`` (DESIGN.md §16): the
    sort-free counting join over all keys at once by default, the
    Pallas fused kernel with ``use_pallas=True``. Both are bit-identical
    to the historical per-key argsort loop over ``_nearest_r_multi``
    (kept above as the documented device twin and test oracle)."""
    return window_join(a_g, ns_g, ns_r, max_sep=max_sep, r_max=r_max,
                       use_pallas=use_pallas, interpret=interpret)


def qt5_join(a_g, ns_g, ns_r, st_cnt, st_ext, st_r, max_sep: int, r_max: int,
             use_pallas: bool = False, interpret: bool = False):
    """Join the QT5 anchor (rarest non-stop lemma) posting row against
    the other non-stop rows (the ordinary-window join of
    :func:`qt34_join`) and the per-(anchor, stop-lemma) NSW aggregate
    rows (neighbor count >= r plus nearest-offset fragment extension —
    no stop-lemma posting list is ever materialized, the paper's point).
    Keys with r == 0 are padding. a_g: (B, L); ns_g: (B, Kn, L);
    st_cnt/st_ext: (B, Ks, L) aligned with the anchor row. The stop
    constraints fold into the same fused ``window_join`` pass (Pallas:
    into the same kernel), preserving the qt34/qt5 step sharing."""
    return window_join(a_g, ns_g, ns_r, st_cnt, st_ext, st_r,
                       max_sep=max_sep, r_max=r_max, use_pallas=use_pallas,
                       interpret=interpret)


# --------------------------------------------------------------------------
# sharded serve step
# --------------------------------------------------------------------------
def make_qt1_serve_step(mesh, top_k: int = 16):
    """Build the jitted, mesh-sharded QT1 serve step (lax searchsorted
    membership join; no Pallas kernel).

    Sharding: batch over pod+data axes, posting length (doc ranges) over
    model. The all-gather moves only K' = top_k entries per shard."""
    has_pod = "pod" in mesh.axis_names
    batch_axes = ("pod", "data") if has_pod else ("data",)

    def local_step(key_g, key_lo, key_hi, idf_sum, span_adjust):
        valid, lo, hi = qt1_join(key_g, key_lo, key_hi)
        score = qt1_score(valid, lo, hi, idf_sum, span_adjust)
        s, g, l, h = qt1_topk(score, key_g[:, 0], lo, hi, top_k)
        # gather per-shard top-k across the doc-sharded axis
        s_all = jax.lax.all_gather(s, "model", axis=1, tiled=True)
        g_all = jax.lax.all_gather(g, "model", axis=1, tiled=True)
        l_all = jax.lax.all_gather(l, "model", axis=1, tiled=True)
        h_all = jax.lax.all_gather(h, "model", axis=1, tiled=True)
        return qt1_topk(s_all, g_all, l_all, h_all, top_k)

    batch_spec = P(batch_axes, None, "model")
    vec_spec = P(batch_axes)
    out_spec = P(batch_axes, None)
    step = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(batch_spec, batch_spec, batch_spec, vec_spec, vec_spec),
        out_specs=(out_spec, out_spec, out_spec, out_spec),
        check_vma=False,
    )
    in_shardings = (
        NamedSharding(mesh, batch_spec),
        NamedSharding(mesh, batch_spec),
        NamedSharding(mesh, batch_spec),
        NamedSharding(mesh, vec_spec),
        NamedSharding(mesh, vec_spec),
    )
    out_shardings = tuple(NamedSharding(mesh, out_spec) for _ in range(4))
    return jax.jit(step, in_shardings=in_shardings, out_shardings=out_shardings)


def make_qt1_serve_step_compressed(mesh, top_k: int = 16, delta_g: bool = True):
    """Beyond-paper §Perf optimization of the serve step: the posting
    payload is compressed in HBM and decompressed on the fly.

    * fragment bounds ride as uint8 offsets from the anchor (|off| <=
      MaxDistance, which must be <= 254 — 255 marks padding; checked at
      engine construction) instead of two int32 streams;
    * with delta_g, anchor keys are block-delta-coded: one int32 base per
      64-posting block + uint16 in-block deltas (doc strides bound the
      in-block range; blocks with wider span fall back via the packer).

    Bytes/posting: 12 -> 6 (offsets) -> 4 (offsets + delta16). The join is
    unchanged — reconstruction is elementwise and fuses into it.
    """
    has_pod = "pod" in mesh.axis_names
    batch_axes = ("pod", "data") if has_pod else ("data",)
    BLK = 64

    def local_step(key_base, key_delta, key_lo_off, key_hi_off, idf_sum, span_adjust):
        if delta_g:
            # (B,K,nb) int32 base + (B,K,L) uint16 deltas -> int32 keys
            base = jnp.repeat(key_base, BLK, axis=2)
            key_g = base + key_delta.astype(jnp.int32)
        else:
            key_g = key_delta
        lo = key_g - key_lo_off.astype(jnp.int32)
        hi = key_g + key_hi_off.astype(jnp.int32)
        # SENTINEL-preservation: padding slots are marked by lo_off==255
        pad = key_lo_off == 255
        key_g = jnp.where(pad, SENTINEL, key_g)
        valid, lo, hi = qt1_join(key_g, lo, hi)
        score = qt1_score(valid, lo, hi, idf_sum, span_adjust)
        s, g, l, h = qt1_topk(score, key_g[:, 0], lo, hi, top_k)
        s_all = jax.lax.all_gather(s, "model", axis=1, tiled=True)
        g_all = jax.lax.all_gather(g, "model", axis=1, tiled=True)
        l_all = jax.lax.all_gather(l, "model", axis=1, tiled=True)
        h_all = jax.lax.all_gather(h, "model", axis=1, tiled=True)
        return qt1_topk(s_all, g_all, l_all, h_all, top_k)

    batch_spec = P(batch_axes, None, "model")
    # offsets-only: the dummy (B,K,1) base cannot shard its unit dim
    base_spec = batch_spec if delta_g else P(batch_axes, None, None)
    vec_spec = P(batch_axes)
    out_spec = P(batch_axes, None)
    step = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(base_spec, batch_spec, batch_spec, batch_spec, vec_spec, vec_spec),
        out_specs=(out_spec,) * 4,
        check_vma=False,
    )
    shards = lambda spec: NamedSharding(mesh, spec)
    return jax.jit(
        step,
        in_shardings=(shards(base_spec), shards(batch_spec), shards(batch_spec),
                      shards(batch_spec), shards(vec_spec), shards(vec_spec)),
        out_shardings=(shards(out_spec),) * 4,
    )


def make_wv_serve_step(mesh, qtype: str, top_k: int = 16, payload: str = "raw",
                       max_distance: int = 5, r_max: int = 4,
                       use_pallas: bool = False, interpret: bool = False):
    """Build the jitted, mesh-sharded QT2/QT3/QT4/QT5 serve step — the
    (w,v)-key / ordinary-window / NSW analogue of
    :func:`make_qt1_serve_step` (DESIGN.md §12-§13). One factory covers
    all non-QT1 query types (``"qt34"`` serves both QT3 and QT4: their
    evaluation is identical, only the lemma classes differ) and all
    three payload formats so the sharding/all-gather plumbing exists
    once:

    * ``payload="raw"``     — int32 rows as packed by pack_qt2_batch /
      pack_qt34_batch / pack_qt5_batch;
    * ``payload="delta"``   — block-delta16-coded anchor streams
      (4 B/posting class, like the QT1 compressed step);
    * ``payload="offsets"`` — int32 anchor streams + uint8 side channels
      (the fallback when a 64-posting block's span overflows uint16;
      for qt34 — whose payload is g rows only — it equals "raw" and
      exists so the engine's per-format step naming stays uniform).

    The joins are payload-independent: compressed payloads are
    reconstructed elementwise and fuse into them. ``use_pallas``
    (qt34/qt5 only) routes the window join through the fused Pallas
    nearest-r kernel, compiled for the TPU; ``interpret=True`` runs it
    in the Pallas interpreter instead, the only way it runs on a CPU
    mesh. The default lax counting join runs everywhere (DESIGN.md
    §16)."""
    assert qtype in ("qt2", "qt34", "qt5")
    assert payload in ("raw", "delta", "offsets")
    has_pod = "pod" in mesh.axis_names
    batch_axes = ("pod", "data") if has_pod else ("data",)

    def finish(score, g, lo, hi):
        s, g1, l1, h1 = qt1_topk(score, g, lo, hi, top_k)
        s_all = jax.lax.all_gather(s, "model", axis=1, tiled=True)
        g_all = jax.lax.all_gather(g1, "model", axis=1, tiled=True)
        l_all = jax.lax.all_gather(l1, "model", axis=1, tiled=True)
        h_all = jax.lax.all_gather(h1, "model", axis=1, tiled=True)
        return qt1_topk(s_all, g_all, l_all, h_all, top_k)

    row = P(batch_axes, None, "model")  # (B, K, L) posting rows
    arow = P(batch_axes, "model")       # (B, L) anchor rows
    vec = P(batch_axes)                 # (B,) per-query scalars
    kvec = P(batch_axes, None)          # (B, K) per-key scalars
    out = P(batch_axes, None)

    if qtype == "qt2":
        sep = 2 * max_distance

        def join_finish(wv_lo, wv_hi, n_keys, idf_sum, span_adjust):
            valid, lo, hi = qt2_join(wv_lo, wv_hi, n_keys, sep)
            score = qt1_score(valid, lo, hi, idf_sum, span_adjust)
            # the CPU engine derives the doc from lo, so lo doubles as g
            return finish(score, lo, lo, hi)

        if payload == "raw":
            local_step = join_finish
            in_specs = (row, row, vec, vec, vec)
        elif payload == "delta":
            def local_step(base, delta, width, n_keys, idf_sum, span_adjust):
                lo = jnp.repeat(base, BLK, axis=2) + delta.astype(jnp.int32)
                pad = width == 255
                hi = jnp.where(pad, SENTINEL, lo + width.astype(jnp.int32))
                lo = jnp.where(pad, SENTINEL, lo)
                return join_finish(lo, hi, n_keys, idf_sum, span_adjust)

            in_specs = (row, row, row, vec, vec, vec)
        else:  # offsets
            def local_step(lo, width, n_keys, idf_sum, span_adjust):
                pad = width == 255
                hi = jnp.where(pad, SENTINEL, lo + width.astype(jnp.int32))
                return join_finish(lo, hi, n_keys, idf_sum, span_adjust)

            in_specs = (row, row, vec, vec, vec)
    elif qtype == "qt34":
        sep = max_distance

        def join_finish(a_g, ns_g, ns_r, idf_sum, span_adjust):
            valid, lo, hi = qt34_join(a_g, ns_g, ns_r, sep, r_max,
                                      use_pallas=use_pallas,
                                      interpret=interpret)
            score = qt1_score(valid, lo, hi, idf_sum, span_adjust)
            return finish(score, lo, lo, hi)

        if payload in ("raw", "offsets"):
            local_step = join_finish
            in_specs = (arow, row, kvec, vec, vec)
        else:  # delta
            def local_step(a_base, a_delta, a_pad, ns_base, ns_delta, ns_pad,
                           ns_r, idf_sum, span_adjust):
                a_g = jnp.repeat(a_base, BLK, axis=1) + a_delta.astype(jnp.int32)
                a_g = jnp.where(a_pad == 1, SENTINEL, a_g)
                ns_g = jnp.repeat(ns_base, BLK, axis=2) + ns_delta.astype(jnp.int32)
                ns_g = jnp.where(ns_pad == 1, SENTINEL, ns_g)
                return join_finish(a_g, ns_g, ns_r, idf_sum, span_adjust)

            in_specs = (arow, arow, arow, row, row, row, kvec, vec, vec)
    else:
        sep = max_distance

        def join_finish(a_g, ns_g, ns_r, st_cnt, st_ext, st_r, idf_sum, span_adjust):
            valid, lo, hi = qt5_join(a_g, ns_g, ns_r, st_cnt, st_ext, st_r, sep,
                                     r_max, use_pallas=use_pallas,
                                     interpret=interpret)
            score = qt1_score(valid, lo, hi, idf_sum, span_adjust)
            return finish(score, lo, lo, hi)

        if payload == "raw":
            local_step = join_finish
            in_specs = (arow, row, kvec, row, row, kvec, vec, vec)
        elif payload == "delta":
            def local_step(a_base, a_delta, a_pad, ns_base, ns_delta, ns_pad,
                           ns_r, st_cnt, st_eneg, st_epos, st_r, idf_sum, span_adjust):
                a_g = jnp.repeat(a_base, BLK, axis=1) + a_delta.astype(jnp.int32)
                a_g = jnp.where(a_pad == 1, SENTINEL, a_g)
                ns_g = jnp.repeat(ns_base, BLK, axis=2) + ns_delta.astype(jnp.int32)
                ns_g = jnp.where(ns_pad == 1, SENTINEL, ns_g)
                cnt = st_cnt.astype(jnp.int32)
                ext = st_epos.astype(jnp.int32) - st_eneg.astype(jnp.int32)
                return join_finish(a_g, ns_g, ns_r, cnt, ext, st_r, idf_sum, span_adjust)

            in_specs = (arow, arow, arow, row, row, row, kvec, row, row, row,
                        kvec, vec, vec)
        else:  # offsets
            def local_step(a_g, ns_g, ns_r, st_cnt, st_eneg, st_epos, st_r,
                           idf_sum, span_adjust):
                cnt = st_cnt.astype(jnp.int32)
                ext = st_epos.astype(jnp.int32) - st_eneg.astype(jnp.int32)
                return join_finish(a_g, ns_g, ns_r, cnt, ext, st_r, idf_sum, span_adjust)

            in_specs = (arow, row, kvec, row, row, row, kvec, vec, vec)

    step = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                         out_specs=(out,) * 4, check_vma=False)
    shards = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    return jax.jit(
        step,
        in_shardings=tuple(shards(s) for s in in_specs),
        out_shardings=(shards(out),) * 4,
    )


# --------------------------------------------------------------------------
# compressed payload encoding
# --------------------------------------------------------------------------
BLK = 64  # delta-coding block: one int32 base per BLK postings


def _delta16_blocks(g):
    """Block-delta16 code an int64 key stream (…, L) with SENTINEL pads:
    one int32 base per 64-posting block + uint16 in-block deltas. The
    base is the min over *live* postings, not element 0: with doc_shards
    > 1 a block can straddle a shard-segment boundary and start with
    padding while holding live keys later — anchoring on the min keeps
    every delta non-negative (and minimal). Returns (base, delta, ok);
    ok False when any in-block span overflows uint16."""
    L = g.shape[-1]
    nb = L // BLK
    gb = g.reshape(g.shape[:-1] + (nb, BLK))
    is_pad = gb == np.int64(SENTINEL)
    live_min = np.where(is_pad, np.int64(SENTINEL), gb).min(axis=-1)
    base = np.where(live_min == np.int64(SENTINEL), 0, live_min)
    delta = np.where(is_pad, 0, gb - base[..., None])
    if delta.max(initial=0) >= 2**16:
        return None, None, False
    return (
        base.astype(np.int32),
        delta.reshape(g.shape[:-1] + (L,)).astype(np.uint16),
        True,
    )


def compress_qt1_batch(batch: "QT1Batch", delta_g: bool = True):
    """Pack a QT1Batch into the compressed device format (args for
    make_qt1_serve_step_compressed). Raises if a 64-posting block's key
    span exceeds uint16 (the serving packer then falls back to the
    offsets-only format for that bucket)."""
    g = batch.key_g.astype(np.int64)
    B, K, L = g.shape
    # pads are marked by lo_off == 255 in the compressed format
    lo_off = np.where(batch.key_lo == SENTINEL, 255,
                      np.clip(g - batch.key_lo, 0, 254))
    hi_off = np.where(batch.key_hi == SENTINEL, 0,
                      np.clip(batch.key_hi - g, 0, 254))
    if not delta_g:
        return (
            jnp.zeros((B, K, 1), jnp.int32),
            jnp.asarray(batch.key_g),
            jnp.asarray(lo_off.astype(np.uint8)),
            jnp.asarray(hi_off.astype(np.uint8)),
            jnp.asarray(batch.idf_sum),
            jnp.asarray(batch.span_adjust),
        )
    assert L % BLK == 0
    base, delta, ok = _delta16_blocks(g)
    if not ok:
        raise ValueError("in-block key span exceeds uint16; use offsets format")
    return (
        jnp.asarray(base),
        jnp.asarray(delta),
        jnp.asarray(lo_off.astype(np.uint8)),
        jnp.asarray(hi_off.astype(np.uint8)),
        jnp.asarray(batch.idf_sum),
        jnp.asarray(batch.span_adjust),
    )


# --------------------------------------------------------------------------
# host-side batch packing from a ProximityIndex
# --------------------------------------------------------------------------
@dataclass
class QT1Batch:
    key_g: np.ndarray  # (B, K, L) int32
    key_lo: np.ndarray
    key_hi: np.ndarray
    idf_sum: np.ndarray  # (B,) f32
    span_adjust: np.ndarray  # (B,) f32 == len(query) - 1
    stride: int

    def device_args(self):
        return (
            jnp.asarray(self.key_g),
            jnp.asarray(self.key_lo),
            jnp.asarray(self.key_hi),
            jnp.asarray(self.idf_sum),
            jnp.asarray(self.span_adjust),
        )


def qt1_stride(index) -> int:
    """Document stride of the g = doc * stride + pos packing. Derived only
    from the (immutable) index, so every batch packed against one snapshot
    agrees on it."""
    max_len = int(index.doc_lengths.max()) if index.doc_lengths is not None else 1
    return max_len + index.max_distance + 2


def batch_size_bucket(n: int, cap: int) -> int:
    """Round a batch size up to the next power of two, capped at `cap`.

    The serve step is jit-compiled per (B, K, L) shape; padding B to this
    small ladder means at most log2(cap)+1 compiles per L-bucket instead
    of one silent recompile for every batch size the queue happens to
    produce."""
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


def pack_fst_key_rows(
    index,
    key,
    L: int,
    doc_shards: int = 1,
    stride: int | None = None,
    out=None,
):
    """Derive the padded, range-partitioned device rows for one (f,s,t) key.

    Returns ``(g, lo, hi, present)``: three (L,) int32 rows plus whether
    the key exists in the index. Postings are range-partitioned into
    doc_shards contiguous doc ranges, each padded to L // doc_shards — so
    that sharding the L axis over the mesh's model axis puts aligned doc
    ranges on the same shard (the alignment invariant of the distributed
    join). Rows depend only on (snapshot, key, L, doc_shards): this is the
    unit the serving layer's PackedPostingCache memoizes (DESIGN.md §11).

    With ``out`` (three caller-provided (L,) views, already
    SENTINEL-filled — e.g. slices of the batch arrays) postings are
    written in place and no rows are allocated, keeping the uncached
    packing path copy-free."""
    if stride is None:
        stride = qt1_stride(index)
    assert L % doc_shards == 0
    Ls = L // doc_shards
    if out is None:
        g_row = np.full(L, SENTINEL, np.int32)
        lo_row = np.full(L, SENTINEL, np.int32)
        hi_row = np.full(L, SENTINEL, np.int32)
    else:
        g_row, lo_row, hi_row = out
    if index.fst is None or key not in index.fst:
        return g_row, lo_row, hi_row, False
    docs, pf, o1, o2 = index.read_fst(key)
    g = (docs * stride + pf).astype(np.int64)
    lo = pf + np.minimum(np.minimum(o1, o2), 0) + docs * stride
    hi = pf + np.maximum(np.maximum(o1, o2), 0) + docs * stride
    _fill_partitioned(docs, (g, lo, hi), index.doc_lengths.size, doc_shards,
                      Ls, (g_row, lo_row, hi_row))
    return g_row, lo_row, hi_row, True


def _fill_partitioned(docs, cols, n_docs, doc_shards, Ls, out_rows):
    """Scatter per-posting columns into range-partitioned row segments:
    shard s holds docs in [s*n/S, (s+1)*n/S), each segment padded to Ls
    entries (out_rows come pre-filled with the pad value). Shared by all
    per-key row packers so every payload kind obeys the same alignment
    invariant (aligned doc ranges land on the same model shard)."""
    lo_bound = 0
    for s in range(doc_shards):
        hi_bound = ((s + 1) * n_docs) // doc_shards
        m = (docs >= lo_bound) & (docs < hi_bound)
        seg = min(int(m.sum()), Ls)
        sl = slice(s * Ls, s * Ls + seg)
        for col, row in zip(cols, out_rows):
            row[sl] = col[m][:seg]
        lo_bound = hi_bound


def pack_wv_key_rows(
    index,
    key,
    L: int,
    doc_shards: int = 1,
    stride: int | None = None,
    out=None,
):
    """Padded, range-partitioned interval rows for one (w,v) key.

    Returns ``(lo, hi, present)``: two (L,) int32 rows sorted by lo (the
    CPU engine's QT2 item order — per-doc lo ranges never overlap, so the
    per-shard sort equals the global stable sort) plus whether the key
    exists. Rows depend only on (snapshot, key, L, doc_shards): the unit
    the serving row cache memoizes under kind "wv"."""
    if stride is None:
        stride = qt1_stride(index)
    assert L % doc_shards == 0
    Ls = L // doc_shards
    if out is None:
        lo_row = np.full(L, SENTINEL, np.int32)
        hi_row = np.full(L, SENTINEL, np.int32)
    else:
        lo_row, hi_row = out
    if index.wv is None or key not in index.wv:
        return lo_row, hi_row, False
    docs, pw, off = index.read_wv(key)
    ga = docs.astype(np.int64) * stride + pw
    gb = ga + off
    lo = np.minimum(ga, gb)
    hi = np.maximum(ga, gb)
    order = np.argsort(lo, kind="stable")
    _fill_partitioned(docs[order], (lo[order], hi[order]),
                      index.doc_lengths.size, doc_shards, Ls, (lo_row, hi_row))
    return lo_row, hi_row, True


def pack_ord_key_rows(
    index,
    lemma: int,
    L: int,
    doc_shards: int = 1,
    stride: int | None = None,
    out=None,
):
    """Padded, range-partitioned g row for one lemma's *ordinary* posting
    list (the QT5 anchor / other-non-stop streams). Returns
    ``(g, present)``; present is False when the lemma has no postings
    (the CPU engine's empty-read early-out)."""
    if stride is None:
        stride = qt1_stride(index)
    assert L % doc_shards == 0
    Ls = L // doc_shards
    g_row = np.full(L, SENTINEL, np.int32) if out is None else out[0]
    docs, pos = index.read_ordinary(lemma)
    if docs.size == 0:
        return g_row, False
    g = docs.astype(np.int64) * stride + pos
    _fill_partitioned(docs, (g,), index.doc_lengths.size, doc_shards, Ls, (g_row,))
    return g_row, True


def pack_nsw_key_rows(
    index,
    key,
    L: int,
    doc_shards: int = 1,
    stride: int | None = None,
    out=None,
):
    """NSW aggregate rows for one (anchor lemma, stop lemma) pair,
    aligned with the anchor's ordinary posting row (same order, padding
    and range partition — zeros at pads). key = (anchor, sid). Returns
    ``(cnt, ext, present)``: per-anchor-posting neighbor count within
    MaxDistance and the nearest neighbor offset (ties prefer the
    negative offset, mirroring the CPU engine's stable lexsort over the
    (row, fl, off)-ordered record stream)."""
    if stride is None:
        stride = qt1_stride(index)
    assert L % doc_shards == 0
    Ls = L // doc_shards
    anchor, sid = key
    if out is None:
        cnt_row = np.zeros(L, np.int32)
        ext_row = np.zeros(L, np.int32)
    else:
        cnt_row, ext_row = out
    a_docs, _ = index.read_ordinary(anchor)
    n = int(a_docs.size)
    if n == 0:
        return cnt_row, ext_row, False
    rows, fls, offs = index.nsw.read(anchor)
    keep = np.abs(offs) <= index.max_distance
    sel = keep & (fls == sid)
    r_rows = rows[sel]
    r_offs = offs[sel]
    cnt = np.bincount(r_rows, minlength=n).astype(np.int64)
    order = np.lexsort((np.abs(r_offs), r_rows))
    rr, ro = r_rows[order], r_offs[order]
    first = np.ones(rr.size, bool)
    first[1:] = rr[1:] != rr[:-1]
    ext = np.zeros(n, np.int64)
    ext[rr[first]] = ro[first]
    _fill_partitioned(a_docs, (cnt, ext), index.doc_lengths.size, doc_shards,
                      Ls, (cnt_row, ext_row))
    return cnt_row, ext_row, True


def pack_qt1_batch(
    index: ProximityIndex,
    queries: list[list[int]],
    L: int,
    K: int = 2,
    doc_shards: int = 1,
    cache=None,
    plans: list | None = None,
) -> QT1Batch:
    """Pack QT1 queries into fixed-shape device arrays.

    Per-key row derivation lives in :func:`pack_fst_key_rows`; with
    `cache` (a ``repro.serving.pack_cache.PackedPostingCache``) the rows
    of hot keys are served from memory instead of being re-derived from
    segment reads — packing becomes B*K row copies.

    An empty query is a batch-shape padding slot: its rows stay
    all-SENTINEL and its idf_sum is 0, so it scores NEG_INF everywhere
    and decodes to zero results.

    INVARIANT: doc_shards must equal the serving mesh's model-axis size.
    Each range-partitioned segment is sorted *locally*; the concatenated
    row is not globally sorted, so the searchsorted-based join is only
    correct when each model shard sees exactly one segment."""
    B = len(queries)
    lex = index.lexicon
    stride = qt1_stride(index)
    assert L % doc_shards == 0

    key_g = np.full((B, K, L), SENTINEL, np.int32)
    key_lo = np.full((B, K, L), SENTINEL, np.int32)
    key_hi = np.full((B, K, L), SENTINEL, np.int32)
    idf_sum = np.zeros(B, np.float32)
    span_adj = np.zeros(B, np.float32)

    for qi, q in enumerate(queries):
        if not q:
            continue  # padding slot
        keys = plans[qi] if plans is not None and plans[qi] is not None \
            else select_fst_keys(q)[1]
        keys = (keys + [keys[-1]] * K)[:K]  # pad by repeating (idempotent join)
        span_adj[qi] = len(q) - 1
        any_present = False
        for ki, key in enumerate(keys):
            if cache is not None:
                g_row, lo_row, hi_row, present = cache.get_rows(
                    index, key, L, doc_shards, stride
                )
                if present:
                    key_g[qi, ki] = g_row
                    key_lo[qi, ki] = lo_row
                    key_hi[qi, ki] = hi_row
            else:  # write postings straight into the batch arrays
                _, _, _, present = pack_fst_key_rows(
                    index, key, L, doc_shards, stride,
                    out=(key_g[qi, ki], key_lo[qi, ki], key_hi[qi, ki]),
                )
            any_present = any_present or present
        if any_present:
            idf_sum[qi] = sum(lex.idf(l) for l in q)
    return QT1Batch(key_g, key_lo, key_hi, idf_sum, span_adj, stride)


# --------------------------------------------------------------------------
# QT2/QT5 host-side batch packing
# --------------------------------------------------------------------------
@dataclass
class QT2Batch:
    wv_lo: np.ndarray  # (B, K, L) int32, sorted by lo, SENTINEL-padded
    wv_hi: np.ndarray
    n_keys: np.ndarray  # (B,) int32; lists k >= n_keys[b] are padding
    idf_sum: np.ndarray
    span_adjust: np.ndarray
    stride: int

    def device_args(self):
        return tuple(jnp.asarray(a) for a in (
            self.wv_lo, self.wv_hi, self.n_keys, self.idf_sum, self.span_adjust))


@dataclass
class QT5Batch:
    a_g: np.ndarray  # (B, L) anchor ordinary posting row
    ns_g: np.ndarray  # (B, Kn, L) other non-stop rows
    ns_r: np.ndarray  # (B, Kn) multiplicities (0 = padding)
    st_cnt: np.ndarray  # (B, Ks, L) NSW neighbor counts (anchor-aligned)
    st_ext: np.ndarray  # (B, Ks, L) nearest NSW offsets
    st_r: np.ndarray  # (B, Ks) stop multiplicities (0 = padding)
    idf_sum: np.ndarray
    span_adjust: np.ndarray
    stride: int

    def device_args(self):
        return tuple(jnp.asarray(a) for a in (
            self.a_g, self.ns_g, self.ns_r, self.st_cnt, self.st_ext,
            self.st_r, self.idf_sum, self.span_adjust))


@dataclass
class QT34Batch:
    a_g: np.ndarray  # (B, L) anchor ordinary posting row
    ns_g: np.ndarray  # (B, Kn, L) other ordinary rows
    ns_r: np.ndarray  # (B, Kn) multiplicities (0 = padding)
    idf_sum: np.ndarray
    span_adjust: np.ndarray
    stride: int

    def device_args(self):
        return tuple(jnp.asarray(a) for a in (
            self.a_g, self.ns_g, self.ns_r, self.idf_sum, self.span_adjust))


# the QT2 key ordering moved beside the other per-type plans in
# core/query.py (the serving planner consumes them uniformly); the old
# name stays importable for existing callers
ordered_wv_keys = qt2_plan


def pack_qt2_batch(
    index,
    queries: list[list[int]],
    L: int,
    K: int = 3,
    doc_shards: int = 1,
    cache=None,
    plans: list | None = None,
) -> QT2Batch:
    """Pack QT2 queries into fixed-shape (w,v)-interval device arrays.

    Per-key row derivation lives in :func:`pack_wv_key_rows`; with
    ``cache`` hot-key rows come from the serving row cache (kind "wv").
    Empty queries are batch-padding slots. Same alignment invariant as
    pack_qt1_batch: doc_shards must equal the mesh's model-axis size.

    doc_shards > 1 caveat: the CPU engine's 2*MaxDistance nearest-start
    window can (for d >= 3) reach across a document boundary — an
    artifact of g-space distance exceeding the inter-doc gap of d+3 —
    and therefore across a shard boundary, which the per-shard
    searchsorted join cannot see. Single-shard serving (the tested
    configuration) is exactly equivalent; sharded QT2 serving misses
    only those cross-document artifacts. QT1 (exact g equality) and QT5
    (window = d < inter-doc gap) have no such boundary cases."""
    B = len(queries)
    lex = index.lexicon
    stride = qt1_stride(index)
    assert L % doc_shards == 0
    wv_lo = np.full((B, K, L), SENTINEL, np.int32)
    wv_hi = np.full((B, K, L), SENTINEL, np.int32)
    n_keys = np.zeros(B, np.int32)
    idf_sum = np.zeros(B, np.float32)
    span_adj = np.zeros(B, np.float32)
    for qi, q in enumerate(queries):
        if not q:
            continue  # padding slot
        keys = (plans[qi] if plans is not None and plans[qi] is not None
                else ordered_wv_keys(index, q)[0])[:K]
        n_keys[qi] = len(keys)
        span_adj[qi] = len(q) - 1
        any_present = False
        for ki, key in enumerate(keys):
            if cache is not None:
                lo_row, hi_row, present = cache.get(index, "wv", key, L,
                                                    doc_shards, stride)
                if present:
                    wv_lo[qi, ki] = lo_row
                    wv_hi[qi, ki] = hi_row
            else:
                _, _, present = pack_wv_key_rows(
                    index, key, L, doc_shards, stride,
                    out=(wv_lo[qi, ki], wv_hi[qi, ki]),
                )
            any_present = any_present or present
        if any_present:
            idf_sum[qi] = sum(lex.idf(l) for l in q)
    return QT2Batch(wv_lo, wv_hi, n_keys, idf_sum, span_adj, stride)


def pack_qt5_batch(
    index,
    queries: list[list[int]],
    L: int,
    Kn: int = 3,
    Ks: int = 3,
    doc_shards: int = 1,
    cache=None,
    plans: list | None = None,
) -> QT5Batch:
    """Pack QT5 queries: anchor + other non-stop ordinary rows (kind
    "ord") and per-(anchor, stop-lemma) NSW aggregate rows (kind "nsw").
    The serving router guarantees the per-query constraint counts fit
    (Kn, Ks) and multiplicities fit the step's r_max; longer queries take
    the CPU fallback."""
    B = len(queries)
    lex = index.lexicon
    stride = qt1_stride(index)
    assert L % doc_shards == 0
    a_g = np.full((B, L), SENTINEL, np.int32)
    ns_g = np.full((B, Kn, L), SENTINEL, np.int32)
    ns_r = np.zeros((B, Kn), np.int32)
    st_cnt = np.zeros((B, Ks, L), np.int32)
    st_ext = np.zeros((B, Ks, L), np.int32)
    st_r = np.zeros((B, Ks), np.int32)
    idf_sum = np.zeros(B, np.float32)
    span_adj = np.zeros(B, np.float32)
    for qi, q in enumerate(queries):
        if not q:
            continue  # padding slot
        plan = (plans[qi] if plans is not None and plans[qi] is not None
                else qt5_plan(index, q))
        if plan is None:
            continue  # degenerate; the router sends these to the CPU
        anchor, others, stops, _ = plan
        span_adj[qi] = len(q) - 1
        if cache is not None:
            g_row, present = cache.get(index, "ord", anchor, L, doc_shards, stride)
            if present:
                a_g[qi] = g_row
        else:
            _, present = pack_ord_key_rows(index, anchor, L, doc_shards, stride,
                                           out=(a_g[qi],))
        for ki, (lemma, r) in enumerate(others[:Kn]):
            ns_r[qi, ki] = r
            if cache is not None:
                g_row, pres = cache.get(index, "ord", lemma, L, doc_shards, stride)
                if pres:
                    ns_g[qi, ki] = g_row
            else:
                pack_ord_key_rows(index, lemma, L, doc_shards, stride,
                                  out=(ns_g[qi, ki],))
        for ki, (sid, r) in enumerate(stops[:Ks]):
            st_r[qi, ki] = r
            if cache is not None:
                cnt_row, ext_row, pres = cache.get(index, "nsw", (anchor, sid),
                                                   L, doc_shards, stride)
                if pres:
                    st_cnt[qi, ki] = cnt_row
                    st_ext[qi, ki] = ext_row
            else:
                pack_nsw_key_rows(index, (anchor, sid), L, doc_shards, stride,
                                  out=(st_cnt[qi, ki], st_ext[qi, ki]))
        idf_sum[qi] = sum(lex.idf(l) for l in q)
    return QT5Batch(a_g, ns_g, ns_r, st_cnt, st_ext, st_r, idf_sum, span_adj, stride)


def pack_qt34_batch(
    index,
    queries: list[list[int]],
    L: int,
    Kn: int = 4,
    doc_shards: int = 1,
    cache=None,
    plans: list | None = None,
) -> QT34Batch:
    """Pack QT3/QT4 queries: anchor (most frequent lemma) + other
    ordinary rows, all kind "ord" — the same per-key rows the QT5
    packer's non-stop streams use, so a warm row cache is shared across
    both paths. The serving router guarantees the per-query constraint
    count fits Kn and multiplicities fit the step's r_max; anything else
    takes the CPU fallback. Same alignment invariant as pack_qt1_batch:
    doc_shards must equal the mesh's model-axis size."""
    B = len(queries)
    lex = index.lexicon
    stride = qt1_stride(index)
    assert L % doc_shards == 0
    a_g = np.full((B, L), SENTINEL, np.int32)
    ns_g = np.full((B, Kn, L), SENTINEL, np.int32)
    ns_r = np.zeros((B, Kn), np.int32)
    idf_sum = np.zeros(B, np.float32)
    span_adj = np.zeros(B, np.float32)
    for qi, q in enumerate(queries):
        if not q:
            continue  # padding slot
        plan = (plans[qi] if plans is not None and plans[qi] is not None
                else qt34_plan(index, q))
        anchor, others, _ = plan
        span_adj[qi] = len(q) - 1
        if cache is not None:
            g_row, present = cache.get(index, "ord", anchor, L, doc_shards, stride)
            if present:
                a_g[qi] = g_row
        else:
            _, present = pack_ord_key_rows(index, anchor, L, doc_shards, stride,
                                           out=(a_g[qi],))
        for ki, (lemma, r) in enumerate(others[:Kn]):
            ns_r[qi, ki] = r
            if lemma == anchor:
                # the anchor's own multiplicity constraint re-windows its row
                ns_g[qi, ki] = a_g[qi]
                continue
            if cache is not None:
                g_row, pres = cache.get(index, "ord", lemma, L, doc_shards, stride)
                if pres:
                    ns_g[qi, ki] = g_row
            else:
                pack_ord_key_rows(index, lemma, L, doc_shards, stride,
                                  out=(ns_g[qi, ki],))
        idf_sum[qi] = sum(lex.idf(l) for l in q)
    return QT34Batch(a_g, ns_g, ns_r, idf_sum, span_adj, stride)


def compress_qt2_batch(batch: QT2Batch, delta_g: bool = True):
    """QT2Batch -> compressed device args. Interval widths (hi - lo <=
    MaxDistance <= 254) ride as uint8 (255 marks padding); with delta_g
    the lo stream is block-delta16 coded. Raises on uint16 overflow (the
    engine then falls back to the offsets format)."""
    lo = batch.wv_lo.astype(np.int64)
    pad = lo == np.int64(SENTINEL)
    width = np.where(pad, 255,
                     np.clip(batch.wv_hi.astype(np.int64) - lo, 0, 254)).astype(np.uint8)
    tail = (jnp.asarray(width), jnp.asarray(batch.n_keys),
            jnp.asarray(batch.idf_sum), jnp.asarray(batch.span_adjust))
    if not delta_g:
        return (jnp.asarray(batch.wv_lo),) + tail
    assert lo.shape[-1] % BLK == 0
    base, delta, ok = _delta16_blocks(lo)
    if not ok:
        raise ValueError("in-block key span exceeds uint16; use offsets format")
    return (jnp.asarray(base), jnp.asarray(delta)) + tail


def compress_qt34_batch(batch: QT34Batch, delta_g: bool = True):
    """QT34Batch -> compressed device args: with delta_g the anchor and
    other ordinary streams are block-delta16 coded behind uint8 pad
    masks (4 B/posting class); without it the int32 rows ship as-is
    (the "offsets" format — QT3/QT4 has no uint8 side channels, so the
    fallback is simply uncompressed). Raises on uint16 overflow (the
    engine then falls back to the offsets format)."""
    tail = (jnp.asarray(batch.ns_r), jnp.asarray(batch.idf_sum),
            jnp.asarray(batch.span_adjust))
    if not delta_g:
        return (jnp.asarray(batch.a_g), jnp.asarray(batch.ns_g)) + tail
    a = batch.a_g.astype(np.int64)
    ns = batch.ns_g.astype(np.int64)
    assert a.shape[-1] % BLK == 0
    a_base, a_delta, ok_a = _delta16_blocks(a)
    ns_base, ns_delta, ok_n = _delta16_blocks(ns)
    if not (ok_a and ok_n):
        raise ValueError("in-block key span exceeds uint16; use offsets format")
    a_pad = (a == np.int64(SENTINEL)).astype(np.uint8)
    ns_pad = (ns == np.int64(SENTINEL)).astype(np.uint8)
    return (jnp.asarray(a_base), jnp.asarray(a_delta), jnp.asarray(a_pad),
            jnp.asarray(ns_base), jnp.asarray(ns_delta), jnp.asarray(ns_pad)) + tail


def compress_qt5_batch(batch: QT5Batch, delta_g: bool = True):
    """QT5Batch -> compressed device args: uint8 NSW counts (clipped at
    255 — multiplicities are far smaller) and split-sign uint8 nearest
    offsets (|ext| <= MaxDistance <= 254); with delta_g the anchor and
    non-stop streams are block-delta16 coded behind uint8 pad masks."""
    cnt8 = np.clip(batch.st_cnt, 0, 255).astype(np.uint8)
    eneg = np.clip(-np.minimum(batch.st_ext, 0), 0, 255).astype(np.uint8)
    epos = np.clip(np.maximum(batch.st_ext, 0), 0, 255).astype(np.uint8)
    tail = (jnp.asarray(batch.ns_r), jnp.asarray(cnt8), jnp.asarray(eneg),
            jnp.asarray(epos), jnp.asarray(batch.st_r),
            jnp.asarray(batch.idf_sum), jnp.asarray(batch.span_adjust))
    if not delta_g:
        return (jnp.asarray(batch.a_g), jnp.asarray(batch.ns_g)) + tail
    a = batch.a_g.astype(np.int64)
    ns = batch.ns_g.astype(np.int64)
    assert a.shape[-1] % BLK == 0
    a_base, a_delta, ok_a = _delta16_blocks(a)
    ns_base, ns_delta, ok_n = _delta16_blocks(ns)
    if not (ok_a and ok_n):
        raise ValueError("in-block key span exceeds uint16; use offsets format")
    a_pad = (a == np.int64(SENTINEL)).astype(np.uint8)
    ns_pad = (ns == np.int64(SENTINEL)).astype(np.uint8)
    return (jnp.asarray(a_base), jnp.asarray(a_delta), jnp.asarray(a_pad),
            jnp.asarray(ns_base), jnp.asarray(ns_delta), jnp.asarray(ns_pad)) + tail


# --------------------------------------------------------------------------
# per-key compressed rows (the compressed-row cache's unit, DESIGN.md §12)
# --------------------------------------------------------------------------
def compress_fst_rows(rows):
    """(g, lo, hi, present) -> (base, delta16, lo_off, hi_off, delta_ok,
    present). base/delta are None when the key's in-block span overflows
    uint16 — the batch assembler then falls back to the offsets format,
    which reuses lo_off/hi_off with the raw g row."""
    g, lo, hi, present = rows
    g64 = g.astype(np.int64)
    lo_off = np.where(lo == SENTINEL, 255, np.clip(g64 - lo, 0, 254)).astype(np.uint8)
    hi_off = np.where(hi == SENTINEL, 0, np.clip(hi - g64, 0, 254)).astype(np.uint8)
    if g64.shape[-1] % BLK:
        return (None, None, lo_off, hi_off, False, present)
    base, delta, ok = _delta16_blocks(g64)
    return (base, delta, lo_off, hi_off, ok, present)


def compress_wv_rows(rows):
    """(lo, hi, present) -> (base, delta16, width, delta_ok, present)."""
    lo, hi, present = rows
    lo64 = lo.astype(np.int64)
    pad = lo64 == np.int64(SENTINEL)
    width = np.where(pad, 255, np.clip(hi.astype(np.int64) - lo64, 0, 254)).astype(np.uint8)
    if lo64.shape[-1] % BLK:
        return (None, None, width, False, present)
    base, delta, ok = _delta16_blocks(lo64)
    return (base, delta, width, ok, present)


def compress_ord_rows(rows):
    """(g, present) -> (base, delta16, pad, delta_ok, present)."""
    g, present = rows
    g64 = g.astype(np.int64)
    pad = (g64 == np.int64(SENTINEL)).astype(np.uint8)
    if g64.shape[-1] % BLK:
        return (None, None, pad, False, present)
    base, delta, ok = _delta16_blocks(g64)
    return (base, delta, pad, ok, present)


def compress_nsw_rows(rows):
    """(cnt, ext, present) -> (cnt8, ext_neg, ext_pos, True, present)."""
    cnt, ext, present = rows
    cnt8 = np.clip(cnt, 0, 255).astype(np.uint8)
    eneg = np.clip(-np.minimum(ext, 0), 0, 255).astype(np.uint8)
    epos = np.clip(np.maximum(ext, 0), 0, 255).astype(np.uint8)
    return (cnt8, eneg, epos, True, present)


# --------------------------------------------------------------------------
# compressed batch assembly from per-key cached rows
# --------------------------------------------------------------------------
def assemble_qt1_compressed(index, queries, L, K=2, doc_shards=1,
                            ccache=None, cache=None, plans=None):
    """Build compressed QT1 device args from per-key *cached* compressed
    rows: warm drains become B*K row copies instead of an O(B·K·L) host
    re-encode. Returns (kind, args, batch_stub) with kind "delta" or
    "offsets" (chosen per batch: offsets when any key's in-block span
    overflows uint16 or the bucket is block/shard-misaligned)."""
    B = len(queries)
    stride = qt1_stride(index)
    lex = index.lexicon
    delta_fmt = L % (BLK * doc_shards) == 0
    lo_off = np.full((B, K, L), 255, np.uint8)
    hi_off = np.zeros((B, K, L), np.uint8)
    idf_sum = np.zeros(B, np.float32)
    span_adj = np.zeros(B, np.float32)
    ents: list = [None] * B
    for qi, q in enumerate(queries):
        if not q:
            continue
        keys = plans[qi] if plans is not None and plans[qi] is not None \
            else select_fst_keys(list(q))[1]
        keys = (keys + [keys[-1]] * K)[:K]
        span_adj[qi] = len(q) - 1
        row_ents = []
        any_present = False
        for ki, key in enumerate(keys):
            base, delta, lo_o, hi_o, ok, present = ccache.get(
                index, "fst_c", key, L, doc_shards, stride)
            delta_fmt &= ok
            if present:
                lo_off[qi, ki] = lo_o
                hi_off[qi, ki] = hi_o
                any_present = True
            row_ents.append((key, base, delta, present))
        if any_present:
            idf_sum[qi] = sum(lex.idf(l) for l in q)
        ents[qi] = row_ents
    stub = QT1Batch(None, None, None, idf_sum, span_adj, stride)
    tail = (jnp.asarray(lo_off), jnp.asarray(hi_off),
            jnp.asarray(idf_sum), jnp.asarray(span_adj))
    if delta_fmt:
        key_base = np.zeros((B, K, L // BLK), np.int32)
        key_delta = np.zeros((B, K, L), np.uint16)
        for qi, row_ents in enumerate(ents):
            if row_ents is None:
                continue
            for ki, (_, base, delta, present) in enumerate(row_ents):
                if present:
                    key_base[qi, ki] = base
                    key_delta[qi, ki] = delta
        return "delta", (jnp.asarray(key_base), jnp.asarray(key_delta)) + tail, stub
    key_g = np.full((B, K, L), SENTINEL, np.int32)
    for qi, row_ents in enumerate(ents):
        if row_ents is None:
            continue
        for ki, (key, _, _, present) in enumerate(row_ents):
            if not present:
                continue
            if cache is not None:
                g_row, _, _, pres = cache.get_rows(index, key, L, doc_shards, stride)
            else:
                g_row, _, _, pres = pack_fst_key_rows(index, key, L, doc_shards, stride)
            if pres:
                key_g[qi, ki] = g_row
    args = (jnp.zeros((B, K, 1), jnp.int32), jnp.asarray(key_g)) + tail
    return "offsets", args, stub


def assemble_qt2_compressed(index, queries, L, K=3, doc_shards=1,
                            ccache=None, cache=None, plans=None):
    """Compressed QT2 device args from per-key cached rows (kind "wv_c").
    Returns (kind, args, batch_stub), kind "qt2_delta" / "qt2_offsets"."""
    B = len(queries)
    stride = qt1_stride(index)
    lex = index.lexicon
    delta_fmt = L % (BLK * doc_shards) == 0
    width = np.full((B, K, L), 255, np.uint8)
    n_keys = np.zeros(B, np.int32)
    idf_sum = np.zeros(B, np.float32)
    span_adj = np.zeros(B, np.float32)
    ents: list = [None] * B
    for qi, q in enumerate(queries):
        if not q:
            continue
        keys = (plans[qi] if plans is not None and plans[qi] is not None
                else ordered_wv_keys(index, q)[0])[:K]
        n_keys[qi] = len(keys)
        span_adj[qi] = len(q) - 1
        row_ents = []
        any_present = False
        for ki, key in enumerate(keys):
            base, delta, w, ok, present = ccache.get(
                index, "wv_c", key, L, doc_shards, stride)
            delta_fmt &= ok
            if present:
                width[qi, ki] = w
                any_present = True
            row_ents.append((key, base, delta, present))
        if any_present:
            idf_sum[qi] = sum(lex.idf(l) for l in q)
        ents[qi] = row_ents
    stub = QT2Batch(None, None, n_keys, idf_sum, span_adj, stride)
    tail = (jnp.asarray(width), jnp.asarray(n_keys),
            jnp.asarray(idf_sum), jnp.asarray(span_adj))
    if delta_fmt:
        lo_base = np.zeros((B, K, L // BLK), np.int32)
        lo_delta = np.zeros((B, K, L), np.uint16)
        for qi, row_ents in enumerate(ents):
            if row_ents is None:
                continue
            for ki, (_, base, delta, present) in enumerate(row_ents):
                if present:
                    lo_base[qi, ki] = base
                    lo_delta[qi, ki] = delta
        return "qt2_delta", (jnp.asarray(lo_base), jnp.asarray(lo_delta)) + tail, stub
    wv_lo = np.full((B, K, L), SENTINEL, np.int32)
    for qi, row_ents in enumerate(ents):
        if row_ents is None:
            continue
        for ki, (key, _, _, present) in enumerate(row_ents):
            if not present:
                continue
            if cache is not None:
                lo_row, _, pres = cache.get(index, "wv", key, L, doc_shards, stride)
            else:
                lo_row, _, pres = pack_wv_key_rows(index, key, L, doc_shards, stride)
            if pres:
                wv_lo[qi, ki] = lo_row
    return "qt2_offsets", (jnp.asarray(wv_lo),) + tail, stub


def assemble_qt34_compressed(index, queries, L, Kn=4, doc_shards=1,
                             ccache=None, cache=None, plans=None):
    """Compressed QT3/QT4 device args from per-key cached rows (kind
    "ord_c" — shared with the QT5 anchor/non-stop streams, so a key hot
    on either path warms both). Returns (kind, args, batch_stub), kind
    "qt34_delta" / "qt34_offsets"."""
    B = len(queries)
    stride = qt1_stride(index)
    lex = index.lexicon
    delta_fmt = L % (BLK * doc_shards) == 0
    a_pad = np.ones((B, L), np.uint8)
    ns_pad = np.ones((B, Kn, L), np.uint8)
    ns_r = np.zeros((B, Kn), np.int32)
    idf_sum = np.zeros(B, np.float32)
    span_adj = np.zeros(B, np.float32)
    a_ents: list = [None] * B
    ns_ents: list = [None] * B
    for qi, q in enumerate(queries):
        if not q:
            continue
        plan = (plans[qi] if plans is not None and plans[qi] is not None
                else qt34_plan(index, q))
        anchor, others, _ = plan
        span_adj[qi] = len(q) - 1
        base, delta, pad, ok, present = ccache.get(
            index, "ord_c", anchor, L, doc_shards, stride)
        delta_fmt &= ok
        if present:
            a_pad[qi] = pad
        a_ents[qi] = (anchor, base, delta, present)
        row_ents = []
        for ki, (lemma, r) in enumerate(others[:Kn]):
            b2, d2, p2, ok2, pr2 = ccache.get(
                index, "ord_c", lemma, L, doc_shards, stride)
            delta_fmt &= ok2
            ns_r[qi, ki] = r
            if pr2:
                ns_pad[qi, ki] = p2
            row_ents.append((lemma, b2, d2, pr2))
        ns_ents[qi] = row_ents
        idf_sum[qi] = sum(lex.idf(l) for l in q)
    stub = QT34Batch(None, None, ns_r, idf_sum, span_adj, stride)
    tail = (jnp.asarray(ns_r), jnp.asarray(idf_sum), jnp.asarray(span_adj))
    if delta_fmt:
        nb = L // BLK
        a_base = np.zeros((B, nb), np.int32)
        a_delta = np.zeros((B, L), np.uint16)
        ns_base = np.zeros((B, Kn, nb), np.int32)
        ns_delta = np.zeros((B, Kn, L), np.uint16)
        for qi in range(B):
            if a_ents[qi] is not None and a_ents[qi][3]:
                a_base[qi] = a_ents[qi][1]
                a_delta[qi] = a_ents[qi][2]
            for ki, (_, b2, d2, pr2) in enumerate(ns_ents[qi] or ()):
                if pr2:
                    ns_base[qi, ki] = b2
                    ns_delta[qi, ki] = d2
        args = (jnp.asarray(a_base), jnp.asarray(a_delta), jnp.asarray(a_pad),
                jnp.asarray(ns_base), jnp.asarray(ns_delta),
                jnp.asarray(ns_pad)) + tail
        return "qt34_delta", args, stub

    def raw_row(lemma):
        if cache is not None:
            return cache.get(index, "ord", lemma, L, doc_shards, stride)
        return pack_ord_key_rows(index, lemma, L, doc_shards, stride)

    a_g = np.full((B, L), SENTINEL, np.int32)
    ns_g = np.full((B, Kn, L), SENTINEL, np.int32)
    for qi in range(B):
        if a_ents[qi] is not None and a_ents[qi][3]:
            g_row, pres = raw_row(a_ents[qi][0])
            if pres:
                a_g[qi] = g_row
        for ki, (lemma, _, _, pr2) in enumerate(ns_ents[qi] or ()):
            if pr2:
                g_row, pres = raw_row(lemma)
                if pres:
                    ns_g[qi, ki] = g_row
    return "qt34_offsets", (jnp.asarray(a_g), jnp.asarray(ns_g)) + tail, stub


def assemble_qt5_compressed(index, queries, L, Kn=3, Ks=3, doc_shards=1,
                            ccache=None, cache=None, plans=None):
    """Compressed QT5 device args from per-key cached rows (kinds "ord_c"
    for anchor/non-stop streams, "nsw_c" for the uint8 NSW aggregates).
    Returns (kind, args, batch_stub), kind "qt5_delta" / "qt5_offsets"."""
    B = len(queries)
    stride = qt1_stride(index)
    lex = index.lexicon
    delta_fmt = L % (BLK * doc_shards) == 0
    a_pad = np.ones((B, L), np.uint8)
    ns_pad = np.ones((B, Kn, L), np.uint8)
    ns_r = np.zeros((B, Kn), np.int32)
    st_r = np.zeros((B, Ks), np.int32)
    cnt8 = np.zeros((B, Ks, L), np.uint8)
    eneg = np.zeros((B, Ks, L), np.uint8)
    epos = np.zeros((B, Ks, L), np.uint8)
    idf_sum = np.zeros(B, np.float32)
    span_adj = np.zeros(B, np.float32)
    a_ents: list = [None] * B
    ns_ents: list = [None] * B
    for qi, q in enumerate(queries):
        if not q:
            continue
        plan = (plans[qi] if plans is not None and plans[qi] is not None
                else qt5_plan(index, q))
        if plan is None:
            continue  # degenerate; routed to the CPU by the engine
        anchor, others, stops, _ = plan
        span_adj[qi] = len(q) - 1
        base, delta, pad, ok, present = ccache.get(
            index, "ord_c", anchor, L, doc_shards, stride)
        delta_fmt &= ok
        if present:
            a_pad[qi] = pad
        a_ents[qi] = (anchor, base, delta, present)
        row_ents = []
        for ki, (lemma, r) in enumerate(others[:Kn]):
            b2, d2, p2, ok2, pr2 = ccache.get(
                index, "ord_c", lemma, L, doc_shards, stride)
            delta_fmt &= ok2
            ns_r[qi, ki] = r
            if pr2:
                ns_pad[qi, ki] = p2
            row_ents.append((lemma, b2, d2, pr2))
        ns_ents[qi] = row_ents
        for ki, (sid, r) in enumerate(stops[:Ks]):
            c8, en, ep, _, pr = ccache.get(
                index, "nsw_c", (anchor, sid), L, doc_shards, stride)
            st_r[qi, ki] = r
            if pr:
                cnt8[qi, ki] = c8
                eneg[qi, ki] = en
                epos[qi, ki] = ep
        idf_sum[qi] = sum(lex.idf(l) for l in q)
    stub = QT5Batch(None, None, ns_r, None, None, st_r, idf_sum, span_adj, stride)
    tail = (jnp.asarray(ns_r), jnp.asarray(cnt8), jnp.asarray(eneg),
            jnp.asarray(epos), jnp.asarray(st_r),
            jnp.asarray(idf_sum), jnp.asarray(span_adj))
    if delta_fmt:
        nb = L // BLK
        a_base = np.zeros((B, nb), np.int32)
        a_delta = np.zeros((B, L), np.uint16)
        ns_base = np.zeros((B, Kn, nb), np.int32)
        ns_delta = np.zeros((B, Kn, L), np.uint16)
        for qi in range(B):
            if a_ents[qi] is not None and a_ents[qi][3]:
                a_base[qi] = a_ents[qi][1]
                a_delta[qi] = a_ents[qi][2]
            for ki, (_, b2, d2, pr2) in enumerate(ns_ents[qi] or ()):
                if pr2:
                    ns_base[qi, ki] = b2
                    ns_delta[qi, ki] = d2
        args = (jnp.asarray(a_base), jnp.asarray(a_delta), jnp.asarray(a_pad),
                jnp.asarray(ns_base), jnp.asarray(ns_delta),
                jnp.asarray(ns_pad)) + tail
        return "qt5_delta", args, stub

    def raw_row(lemma):
        if cache is not None:
            return cache.get(index, "ord", lemma, L, doc_shards, stride)
        return pack_ord_key_rows(index, lemma, L, doc_shards, stride)

    a_g = np.full((B, L), SENTINEL, np.int32)
    ns_g = np.full((B, Kn, L), SENTINEL, np.int32)
    for qi in range(B):
        if a_ents[qi] is not None and a_ents[qi][3]:
            g_row, pres = raw_row(a_ents[qi][0])
            if pres:
                a_g[qi] = g_row
        for ki, (lemma, _, _, pr2) in enumerate(ns_ents[qi] or ()):
            if pr2:
                g_row, pres = raw_row(lemma)
                if pres:
                    ns_g[qi, ki] = g_row
    return "qt5_offsets", (jnp.asarray(a_g), jnp.asarray(ns_g)) + tail, stub


def decode_results(batch: QT1Batch, top_s, top_g, top_lo, top_hi):
    """Device top-k -> per-query (doc, start, end, score) numpy records.

    Vectorized: the four (B, k) result matrices are tiny (k = top_k), so
    they transfer wholesale in four copies and every filter/divmod runs
    in numpy — per-row device gathers would cost more in op dispatch
    than the masked rows' bytes (measured: ~0.7 ms per device
    ``__getitem__`` on CPU vs ~4 KB of extra transfer)."""
    s = np.asarray(top_s)
    valid = s > -1e29
    B = s.shape[0]
    z = np.zeros(0, np.int64)
    out = [
        {"doc": z, "start": z, "end": z, "score": np.zeros(0, s.dtype)}
        for _ in range(B)
    ]
    rows = np.flatnonzero(valid.any(axis=1))
    if rows.size == 0:
        return out
    g = np.asarray(top_g).astype(np.int64)[rows]
    lo = np.asarray(top_lo).astype(np.int64)[rows]
    hi = np.asarray(top_hi).astype(np.int64)[rows]
    vm = valid[rows]
    doc = g[vm] // batch.stride
    start = lo[vm] % batch.stride
    end = hi[vm] % batch.stride
    score = s[rows][vm]
    splits = np.cumsum(vm.sum(axis=1))[:-1]
    for qi, d, st, en, sc in zip(
        rows.tolist(),
        np.split(doc, splits),
        np.split(start, splits),
        np.split(end, splits),
        np.split(score, splits),
    ):
        out[qi] = {"doc": d, "start": st, "end": en, "score": sc}
    return out
