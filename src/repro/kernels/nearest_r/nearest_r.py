"""Pallas TPU kernel: fused batched r-nearest window-membership join.

One blocked pass over all Kn non-stop rows replaces the per-key
searchsorted + argsort loop of the serve join. Structure:

* TPU tiling: every row is viewed as (rows/128, 128) so each block is
  a lane-dense (block/128, 128) int32 tile — anchors, candidates, the
  stop aggregates and the three outputs alike. ``valid`` is emitted as
  int32 0/1 (no ``bool`` memrefs).
* grid (B, n_l, Kn, k_tiles): the (valid, lo, hi) output block for an
  anchor tile stays resident in VMEM across the whole inner (key,
  b-tile) sweep — keys fold into it one after another, so the qt5
  stop-row constraints can seed it once and the qt34/qt5 executable
  sharing is preserved.
* signed-distance bitmask scratch: instead of gathering and sorting the
  2·r_max nearest candidates, each b-tile adds ``1 << (a - b + max_sep)``
  for every candidate b within ``max_sep`` of anchor a into one int32
  word per anchor. Real posting values are strictly increasing per row,
  so every signed distance occurs at most once per (anchor, key) and
  the sum is the OR. The candidate tile is transposed once per step so
  that candidates run along sublanes and anchors along lanes: the
  per-anchor reduction is then a sublane sum that lands lane-dense in
  the anchor's own tile row. At the last b-tile the p-th nearest
  distance is recovered by counting bits.
* early-mask join ordering (arXiv 2009.02684): callers order keys
  sparsest-first; a b-tile whose anchor block is already fully
  invalidated (or whose key is inactive) is skipped with pl.when, so
  later, denser keys touch fewer live lanes.
* scalar-prefetched b-tile windows: each (anchor-tile, key) only
  computes on b-tiles from searchsorted(block min − max_sep) to
  searchsorted(block max + max_sep); grid steps past the window repeat
  the last block index (no DMA) and skip the compute.

Tie-breaking matches ``search._nearest_r`` bit-for-bit: at equal
distance, pred_p precedes succ_q iff p <= q (CPU candidate-column
order [idx-1, idx, idx-2, idx+1, ...] under a stable sort).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import SENTINEL, pad_to_multiple

LANES = 128
DEFAULT_BLOCK_L = 1024  # one (8, 128) int32 tile of anchors
DEFAULT_BLOCK_K = 1024  # one (8, 128) int32 tile of candidates
MAX_SEP = 15  # the 2 * max_sep + 1 signed distances fit one int32 word

BIG_DIST = 2**30  # plain int: Pallas kernels cannot capture device constants


def _kernel(starts_ref, ends_ref, nsr_ref, str_ref, a_ref, ns_ref, *rest,
            max_sep: int, r_max: int, n_keys: int, n_stops: int, n_l: int):
    if n_stops:
        st_cnt_ref, st_ext_ref, valid_ref, lo_ref, hi_ref, bits_ref = rest
    else:
        st_cnt_ref = st_ext_ref = None
        valid_ref, lo_ref, hi_ref, bits_ref = rest

    b = pl.program_id(0)
    i = pl.program_id(1)
    key = pl.program_id(2)
    k = pl.program_id(3)
    a = a_ref[...]  # (R, 128) anchors, row-major
    rows = a.shape[0]

    @pl.when((key == 0) & (k == 0))
    def _init():
        # Seed outputs from the anchor; fold the elementwise stop-row
        # constraints here so one kernel serves qt34 (n_stops=0) and qt5.
        v = a != SENTINEL
        lo = a
        hi = a
        for s in range(n_stops):
            rs = str_ref[b * n_stops + s]
            act = rs > 0
            v &= (st_cnt_ref[s] >= rs) | jnp.logical_not(act)
            ext = jnp.where(act, st_ext_ref[s], 0)
            lo = jnp.minimum(lo, a + jnp.minimum(ext, 0))
            hi = jnp.maximum(hi, a + jnp.maximum(ext, 0))
        valid_ref[...] = v.astype(jnp.int32)
        lo_ref[...] = lo
        hi_ref[...] = hi

    @pl.when(k == 0)
    def _reset():
        bits_ref[...] = jnp.zeros_like(bits_ref)

    win = (b * n_l + i) * n_keys + key
    r1 = nsr_ref[b * n_keys + key]
    live = ((r1 > 0) & (starts_ref[win] + k <= ends_ref[win])
            & (jnp.max(valid_ref[...]) > 0))

    @pl.when(live)
    def _accumulate():
        # candidates down the sublanes: column c of wt holds b values
        # c*128 .. c*128+127 of this tile
        wt = ns_ref[...].T  # (128, C)
        for r in range(rows):
            a_row = a[r:r + 1, :]  # (1, 128) anchors along lanes
            acc = jnp.zeros((LANES, LANES), jnp.int32)
            for c in range(wt.shape[1]):
                w_col = wt[:, c:c + 1]  # (128, 1)
                d = a_row - w_col  # (128, 128): signed distance a - b
                near = (d >= -max_sep) & (d <= max_sep) & (w_col != SENTINEL)
                shift = jnp.where(near, d + max_sep, 0)
                acc = acc + jnp.where(near, jnp.left_shift(1, shift), 0)
            bits_ref[r:r + 1, :] = bits_ref[r:r + 1, :] | jnp.sum(
                acc, axis=0, keepdims=True)

    @pl.when(k == pl.num_programs(3) - 1)
    def _finalize():
        act = r1 > 0
        bits = bits_ref[...]

        def present(dist):  # signed distance a - b -> 0/1 per anchor
            return jnp.right_shift(bits, dist + max_sep) & 1

        # p-th / q-th smallest present distance per side by counting.
        dp, ds = [], []
        for p in range(1, r_max + 1):
            run = jnp.zeros_like(bits)
            lt = jnp.zeros_like(bits)
            for dlt in range(1, max_sep + 1):
                run = run + present(dlt)
                lt = lt + (run < p).astype(jnp.int32)
            d = 1 + lt
            dp.append(jnp.where((d <= max_sep) & (p <= r1), d, BIG_DIST))
        for q in range(1, r_max + 1):
            run = jnp.zeros_like(bits)
            lt = jnp.zeros_like(bits)
            for dlt in range(0, max_sep + 1):
                run = run + present(-dlt)
                lt = lt + (run < q).astype(jnp.int32)
            d = lt
            ds.append(jnp.where((d <= max_sep) & (q <= r1), d, BIG_DIST))
        cnt = sum((d != BIG_DIST).astype(jnp.int32) for d in dp + ds)
        m = cnt >= r1
        # pred_p kept iff p + #{succs strictly before it} <= r; ties at
        # equal distance resolve pred_p before succ_q iff p <= q.
        mn_d = jnp.zeros_like(bits)
        mx_d = jnp.zeros_like(bits)
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
        upd = act & m
        valid_ref[...] = jnp.where(act & jnp.logical_not(m), 0, valid_ref[...])
        lo = lo_ref[...]
        hi = hi_ref[...]
        lo_ref[...] = jnp.where(upd, jnp.minimum(lo, a - mn_d), lo)
        hi_ref[...] = jnp.where(upd, jnp.maximum(hi, a + mx_d), hi)


def _tiles(x):
    """(..., L) -> (..., L/128, 128): the lane-dense view every block
    of the kernel is cut from."""
    return x.reshape(*x.shape[:-1], x.shape[-1] // LANES, LANES)


@functools.partial(
    jax.jit,
    static_argnames=("max_sep", "r_max", "interpret", "block_l", "block_k",
                     "k_tiles"),
)
def window_join_pallas(a_g, ns_g, ns_r, st_cnt=None, st_ext=None, st_r=None, *,
                       max_sep: int, r_max: int, interpret: bool = False,
                       block_l: int = DEFAULT_BLOCK_L,
                       block_k: int = DEFAULT_BLOCK_K, k_tiles=None):
    B, Kn, L = ns_g.shape
    if Kn == 0:
        raise ValueError("window_join_pallas needs at least one non-stop row")
    if max_sep > MAX_SEP:
        raise ValueError(
            f"window_join_pallas supports max_sep <= {MAX_SEP} "
            f"(got {max_sep}); use the counting join")
    if block_l % LANES or block_k % LANES:
        raise ValueError("block_l and block_k must be multiples of 128")
    a_p = pad_to_multiple(a_g, block_l, SENTINEL)
    ns_p = pad_to_multiple(ns_g, block_k, SENTINEL)
    La = a_p.shape[-1]
    n_l = La // block_l
    nk = ns_p.shape[-1] // block_k
    if k_tiles is None:
        k_tiles = nk
    k_tiles = max(1, min(k_tiles, nk))
    n_stops = 0 if st_cnt is None else st_cnt.shape[1]

    # Scalar-prefetched b-tile windows: rows are sorted, so the tiles
    # that can matter for an anchor tile run from the insertion point
    # of (tile minimum - max_sep) to that of (tile maximum + max_sep).
    a_tiles = a_p.reshape(B, n_l, block_l)
    t_lo = a_tiles[:, :, 0] - max_sep  # (B, n_l)
    t_hi = jnp.max(jnp.where(a_tiles != SENTINEL, a_tiles, -1), axis=2) + max_sep

    def window(rows, t, side):  # (Kn, Lk), (n_l,) -> (n_l, Kn)
        return jax.vmap(lambda row: jnp.searchsorted(row, t, side=side))(rows).T

    starts = jax.vmap(functools.partial(window, side="left"))(ns_p, t_lo)
    ends = jax.vmap(functools.partial(window, side="right"))(ns_p, t_hi)
    starts = jnp.minimum(starts // block_k, nk - 1).astype(jnp.int32)
    ends = jnp.maximum(jnp.minimum((ends - 1) // block_k, nk - 1), starts)
    ends = ends.astype(jnp.int32)

    R = block_l // LANES
    C = block_k // LANES

    def cand_map(b, i, key, k, starts, ends, *_):
        w = (b * n_l + i) * Kn + key
        return b, key, jnp.minimum(starts[w] + k, ends[w]), 0

    anchor_spec = pl.BlockSpec((None, R, LANES),
                               lambda b, i, key, k, *_: (b, i, 0))
    in_specs = [anchor_spec, pl.BlockSpec((None, None, C, LANES), cand_map)]
    operands = [_tiles(a_p), _tiles(ns_p)]
    if n_stops:
        st_spec = pl.BlockSpec((None, n_stops, R, LANES),
                               lambda b, i, key, k, *_: (b, 0, i, 0))
        in_specs += [st_spec, st_spec]
        operands += [_tiles(pad_to_multiple(st_cnt, block_l, 0)),
                     _tiles(pad_to_multiple(st_ext, block_l, 0))]
    st_r_flat = (jnp.zeros((B,), jnp.int32) if st_r is None
                 else st_r.astype(jnp.int32).reshape(-1))

    kernel = functools.partial(_kernel, max_sep=max_sep, r_max=r_max,
                               n_keys=Kn, n_stops=n_stops, n_l=n_l)
    out = jax.ShapeDtypeStruct((B, La // LANES, LANES), jnp.int32)
    valid, lo, hi = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, n_l, Kn, k_tiles),
            in_specs=in_specs,
            out_specs=[anchor_spec] * 3,
            scratch_shapes=[pltpu.VMEM((R, LANES), jnp.int32)],
        ),
        out_shape=[out, out, out],
        interpret=interpret,
    )(starts.reshape(-1), ends.reshape(-1), ns_r.astype(jnp.int32).reshape(-1),
      st_r_flat, *operands)
    unview = lambda x: x.reshape(B, La)[:, :L]  # noqa: E731
    return unview(valid) != 0, unview(lo), unview(hi)
