"""Dispatch wrapper for the fused nearest-r window join.

Two production paths behind one signature:

- ``use_pallas=False`` (default, and the serve default on CPU hosts):
  a sort-free *counting* formulation. One ``searchsorted`` per
  flattened (query, key) row, then the p-th nearest predecessor /
  q-th nearest successor distances are ranked by counting comparisons
  across the 2·r_max candidate lanes instead of materialising and
  sorting a (B, L, 2·r_max) distance tensor per key. This is the ~9×
  win over the argsort join on CPU and the baseline the kernel rows in
  ``benchmarks/kernel_bench.py`` quantify.
- ``use_pallas=True``: the Pallas TPU kernel in ``nearest_r.py`` —
  one blocked pass over all Kn rows with a signed-distance bitmask per
  anchor, sparsest-first key order exploited via early-masked blocks.
  It compiles for the TPU; on any other backend the caller must pass
  ``interpret=True`` (see DESIGN.md §16).

Both reproduce ``ref.window_join_ref`` (and therefore the CPU engine's
``search._nearest_r``) bit-for-bit on valid lanes, including stable
tie-breaking at equal distances: pred_p wins over succ_q iff p <= q,
the column order [idx-1, idx, idx-2, idx+1, ...] of the CPU oracle.

Preconditions shared with the rest of the serve path: rows are sorted
ascending, strictly increasing on real values, SENTINEL-padded; ns_r
multiplicities are <= r_max.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import SENTINEL, cdiv

BIG_DIST = jnp.int32(2**30)


def _nearest_r_counting(b_rows, centers, max_sep: int, r, r_max: int):
    """Sort-free device twin of ``search._nearest_r``.

    b_rows (N, L) sorted asc (SENTINEL pad), centers (N, L), r (N,).
    Returns (matched, mn, mx) with mn/mx = min/max over (r nearest
    values + center) — identical to the argsort formulation at the
    join level, where lo/hi already bracket the center.
    """
    Lb = b_rows.shape[-1]

    def one(b_row, c_row, r1):
        idx = jnp.searchsorted(b_row, c_row)
        dp, ds = [], []
        for j in range(1, r_max + 1):
            ip = idx - j
            vp = b_row[jnp.clip(ip, 0, Lb - 1)]
            okp = (ip >= 0) & (vp != SENTINEL) & (jnp.int32(j) <= r1)
            d = c_row - vp
            dp.append(jnp.where(okp & (d <= max_sep), d, BIG_DIST))
            iq = idx + (j - 1)
            vq = b_row[jnp.clip(iq, 0, Lb - 1)]
            okq = (iq < Lb) & (vq != SENTINEL) & (jnp.int32(j) <= r1)
            d = vq - c_row
            ds.append(jnp.where(okq & (d <= max_sep), d, BIG_DIST))
        cnt = sum((d != BIG_DIST).astype(jnp.int32) for d in dp + ds)
        matched = cnt >= r1
        # pred_p is kept iff p + #{succs strictly before it} <= r;
        # tie at equal distance: pred_p before succ_q iff p <= q.
        mn_d = jnp.zeros_like(c_row)
        mx_d = jnp.zeros_like(c_row)
        for p in range(1, r_max + 1):
            s_before = sum(
                ((ds[q - 1] < dp[p - 1])
                 | ((ds[q - 1] == dp[p - 1]) & (q < p))).astype(jnp.int32)
                for q in range(1, r_max + 1)
            )
            keep = (dp[p - 1] != BIG_DIST) & (p + s_before <= r1)
            mn_d = jnp.maximum(mn_d, jnp.where(keep, dp[p - 1], 0))
        for q in range(1, r_max + 1):
            p_before = sum(
                ((dp[p - 1] < ds[q - 1])
                 | ((dp[p - 1] == ds[q - 1]) & (p <= q))).astype(jnp.int32)
                for p in range(1, r_max + 1)
            )
            keep = (ds[q - 1] != BIG_DIST) & (q + p_before <= r1)
            mx_d = jnp.maximum(mx_d, jnp.where(keep, ds[q - 1], 0))
        return matched, c_row - mn_d, c_row + mx_d

    return jax.vmap(one)(b_rows, centers, r)


def _fold_stops(valid, lo, hi, a_g, st_cnt, st_ext, st_r):
    """Elementwise NSW stop-row constraints of ``qt5_join``."""
    for k in range(st_cnt.shape[1]):
        r = st_r[:, k][:, None]
        active = r > 0
        valid &= (st_cnt[:, k] >= r) | ~active
        ext = jnp.where(active, st_ext[:, k], 0)
        lo = jnp.minimum(lo, a_g + jnp.minimum(ext, 0))
        hi = jnp.maximum(hi, a_g + jnp.maximum(ext, 0))
    return valid, lo, hi


def window_join(a_g, ns_g, ns_r, st_cnt=None, st_ext=None, st_r=None, *,
                max_sep: int, r_max: int, use_pallas: bool = False,
                interpret: bool = False, block_l: int = 1024,
                block_k: int = 1024, k_tiles=None):
    """Fused ordinary-window + NSW join over all keys at once.

    a_g: (B, L) anchor rows; ns_g: (B, Kn, L) non-stop rows; ns_r:
    (B, Kn) multiplicities (0 = inactive key). Optional stop aggregates
    st_cnt/st_ext (B, Ks, L) + st_r (B, Ks). Returns (valid, lo, hi)
    aligned with the anchor, SENTINEL lanes invalid.
    """
    if use_pallas:
        from repro.kernels.nearest_r.nearest_r import window_join_pallas
        return window_join_pallas(
            a_g, ns_g, ns_r, st_cnt, st_ext, st_r,
            max_sep=max_sep, r_max=r_max, interpret=interpret,
            block_l=block_l, block_k=block_k, k_tiles=k_tiles)

    B, Kn, L = ns_g.shape
    valid = a_g != SENTINEL
    lo = a_g
    hi = a_g
    if Kn:
        b_flat = ns_g.reshape(B * Kn, L)
        c_flat = jnp.broadcast_to(a_g[:, None, :], (B, Kn, L)).reshape(B * Kn, L)
        r_flat = ns_r.reshape(B * Kn)
        m, mn, mx = _nearest_r_counting(b_flat, c_flat, max_sep, r_flat, r_max)
        m = m.reshape(B, Kn, L)
        mn = mn.reshape(B, Kn, L)
        mx = mx.reshape(B, Kn, L)
        active = (ns_r > 0)[:, :, None]
        valid &= jnp.all(m | ~active, axis=1)
        upd = active & m
        lo = jnp.minimum(lo, jnp.where(upd, mn, lo[:, None, :]).min(axis=1))
        hi = jnp.maximum(hi, jnp.where(upd, mx, hi[:, None, :]).max(axis=1))
    if st_cnt is not None:
        valid, lo, hi = _fold_stops(valid, lo, hi, a_g, st_cnt, st_ext, st_r)
    return valid, lo, hi


def plan_k_tiles(a_g, ns_g, max_sep: int, block_l: int, block_k: int) -> int:
    """Host-side exact bound on b-tiles any (anchor-block, key) pair
    needs so every candidate within ``max_sep`` of a block's anchors is
    visited. Concrete inputs only; the kernel defaults to the safe
    full-row bound when this is not supplied."""
    import numpy as np

    a = np.asarray(a_g)
    ns = np.asarray(ns_g)
    B, Kn, L = ns.shape
    n_l = cdiv(L, block_l)
    nk = cdiv(L, block_k)
    worst = 1
    for b in range(B):
        for i in range(n_l):
            blk = a[b, i * block_l:(i + 1) * block_l]
            blk = blk[blk != SENTINEL]
            if blk.size == 0:
                continue
            for key in range(Kn):
                row = ns[b, key]
                s = np.searchsorted(row, blk.min() - max_sep) // block_k
                e = np.searchsorted(row, blk.max() + max_sep, "right")
                e = min(nk - 1, e // block_k)
                worst = max(worst, int(e - s) + 1)
    return worst
