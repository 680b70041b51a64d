"""Property tests for the fused nearest-r window join: the lax counting
path and the Pallas kernel (interpret mode) vs the argsort oracle
``window_join_ref`` and the CPU engine's ``search._nearest_r`` replayed
at the join level. Comparison is on (valid, lo[valid], hi[valid]) — the
contract every consumer reads — because the impls differ only on lanes
the join masks out (center inclusion in mn/mx, matched at r=0).

Randomized cases run under hypothesis when it is installed (shrinking,
fresh examples); otherwise the same generators sweep a fixed seed grid
via parametrize so the coverage does not silently vanish."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import search
from repro.kernels.common import SENTINEL
from repro.kernels.nearest_r import plan_k_tiles, window_join
from repro.kernels.nearest_r.ref import window_join_ref

R_MAX = 4

try:
    from hypothesis import given, settings, strategies as st

    def property_cases(max_examples, **bounds):
        def deco(fn):
            strat = {k: st.integers(lo, hi) for k, (lo, hi) in bounds.items()}
            return settings(max_examples=max_examples, deadline=None)(
                given(**strat)(fn))
        return deco
except ModuleNotFoundError:
    def property_cases(max_examples, **bounds):
        def deco(fn):
            rng = np.random.default_rng(0)
            rows = [tuple(int(rng.integers(lo, hi + 1))
                          for lo, hi in bounds.values())
                    for _ in range(max_examples)]
            return pytest.mark.parametrize(",".join(bounds), rows)(fn)
        return deco


def _rows(rng, b, kn, l, stride, p_empty=0.15):
    """Strictly increasing SENTINEL-padded rows. Small ``stride`` makes
    equal pred/succ distances common — the tie-breaking cases."""
    out = np.full((b, kn, l), SENTINEL, np.int32)
    for i in range(b):
        for k in range(kn):
            if rng.random() < p_empty:
                continue
            n = int(rng.integers(1, l + 1))
            out[i, k, :n] = np.cumsum(rng.integers(1, stride + 1, n))
    return out


def _np3(out):
    return tuple(np.asarray(x) for x in out)


def _assert_same(got, want):
    gv, gl, gh = _np3(got)
    wv, wl, wh = _np3(want)
    np.testing.assert_array_equal(gv, wv)
    np.testing.assert_array_equal(gl[wv], wl[wv])
    np.testing.assert_array_equal(gh[wv], wh[wv])


def _cpu_join(a, ns, ns_r, st_cnt=None, st_ext=None, st_r=None, *, max_sep):
    """The CPU engine verbatim: ``search._nearest_r`` per key folded with
    ``_window_match``'s accumulation, then the elementwise stop fold —
    run on the unpadded rows, scattered back to the padded layout."""
    b, kn, l = ns.shape
    valid = np.zeros((b, l), bool)
    lo = a.astype(np.int64).copy()
    hi = a.astype(np.int64).copy()
    for i in range(b):
        real = a[i] != SENTINEL
        centers = a[i][real].astype(np.int64)
        ok = np.ones(centers.size, bool)
        lo_i = centers.copy()
        hi_i = centers.copy()
        for k in range(kn):
            r = int(ns_r[i, k])
            if r == 0:
                continue
            row = ns[i, k]
            g = row[row != SENTINEL].astype(np.int64)
            m, mn, mx = search._nearest_r(g, centers, max_sep, r)
            ok &= m
            lo_i = np.minimum(lo_i, np.where(m, mn, lo_i))
            hi_i = np.maximum(hi_i, np.where(m, mx, hi_i))
        valid[i, real] = ok
        lo[i, real] = lo_i
        hi[i, real] = hi_i
    if st_cnt is not None:
        a64 = a.astype(np.int64)
        for k in range(st_cnt.shape[1]):
            r = st_r[:, k][:, None]
            active = r > 0
            valid &= (st_cnt[:, k] >= r) | ~active
            ext = np.where(active, st_ext[:, k], 0)
            lo = np.minimum(lo, a64 + np.minimum(ext, 0))
            hi = np.maximum(hi, a64 + np.maximum(ext, 0))
    return valid, lo, hi


def _stops(rng, b, ks, l, max_sep):
    st_cnt = rng.integers(0, 4, (b, ks, l)).astype(np.int32)
    st_ext = rng.integers(-max_sep, max_sep + 1, (b, ks, l)).astype(np.int32)
    st_r = rng.integers(0, 3, (b, ks)).astype(np.int32)
    return st_cnt, st_ext, st_r


# ---------------- lax counting path vs oracle vs CPU ------------------------
@property_cases(40, seed=(0, 2**31 - 1), b=(1, 3), kn=(1, 3), l=(4, 48),
                stride=(1, 5), max_sep=(1, 8))
def test_counting_vs_ref_vs_cpu(seed, b, kn, l, stride, max_sep):
    rng = np.random.default_rng(seed)
    a = _rows(rng, b, 1, l, stride)[:, 0]
    ns = _rows(rng, b, kn, l, stride)
    ns_r = rng.integers(0, R_MAX + 1, (b, kn)).astype(np.int32)
    args = (jnp.asarray(a), jnp.asarray(ns), jnp.asarray(ns_r))
    got = window_join(*args, max_sep=max_sep, r_max=R_MAX)
    ref = window_join_ref(*args, max_sep=max_sep, r_max=R_MAX)
    cpu = _cpu_join(a, ns, ns_r, max_sep=max_sep)
    _assert_same(got, ref)
    _assert_same(got, cpu)


@property_cases(25, seed=(0, 2**31 - 1), stride=(1, 4))
def test_counting_qt5_stop_fold(seed, stride):
    rng = np.random.default_rng(seed)
    b, kn, ks, l, max_sep = 2, 2, 2, 32, 5
    a = _rows(rng, b, 1, l, stride)[:, 0]
    ns = _rows(rng, b, kn, l, stride)
    ns_r = rng.integers(0, R_MAX + 1, (b, kn)).astype(np.int32)
    st_cnt, st_ext, st_r = _stops(rng, b, ks, l, max_sep)
    args = (jnp.asarray(a), jnp.asarray(ns), jnp.asarray(ns_r),
            jnp.asarray(st_cnt), jnp.asarray(st_ext), jnp.asarray(st_r))
    got = window_join(*args, max_sep=max_sep, r_max=R_MAX)
    ref = window_join_ref(*args, max_sep=max_sep, r_max=R_MAX)
    cpu = _cpu_join(a, ns, ns_r, st_cnt, st_ext, st_r, max_sep=max_sep)
    _assert_same(got, ref)
    _assert_same(got, cpu)


# ---------------- Pallas kernel (interpret) vs oracle -----------------------
@property_cases(10, seed=(0, 2**31 - 1), stride=(1, 4))
def test_pallas_vs_ref(seed, stride):
    # Fixed shape/statics: one trace across examples (interpret is slow).
    rng = np.random.default_rng(seed)
    b, kn, l, max_sep = 2, 2, 200, 4  # two 128-lane tiles per row
    a = _rows(rng, b, 1, l, stride, p_empty=0.0)[:, 0]
    ns = _rows(rng, b, kn, l, stride)
    ns_r = rng.integers(0, R_MAX + 1, (b, kn)).astype(np.int32)
    args = (jnp.asarray(a), jnp.asarray(ns), jnp.asarray(ns_r))
    got = window_join(*args, max_sep=max_sep, r_max=R_MAX,
                      use_pallas=True, interpret=True, block_l=128, block_k=128)
    ref = window_join_ref(*args, max_sep=max_sep, r_max=R_MAX)
    _assert_same(got, ref)


@property_cases(6, seed=(0, 2**31 - 1))
def test_pallas_qt5_stop_fold(seed):
    rng = np.random.default_rng(seed)
    b, kn, ks, l, max_sep = 2, 2, 2, 32, 4
    a = _rows(rng, b, 1, l, 3, p_empty=0.0)[:, 0]
    ns = _rows(rng, b, kn, l, 3)
    ns_r = rng.integers(0, R_MAX + 1, (b, kn)).astype(np.int32)
    st_cnt, st_ext, st_r = _stops(rng, b, ks, l, max_sep)
    args = (jnp.asarray(a), jnp.asarray(ns), jnp.asarray(ns_r),
            jnp.asarray(st_cnt), jnp.asarray(st_ext), jnp.asarray(st_r))
    got = window_join(*args, max_sep=max_sep, r_max=R_MAX,
                      use_pallas=True, interpret=True, block_l=128, block_k=128)
    ref = window_join_ref(*args, max_sep=max_sep, r_max=R_MAX)
    cpu = _cpu_join(a, ns, ns_r, st_cnt, st_ext, st_r, max_sep=max_sep)
    _assert_same(got, ref)
    _assert_same(got, cpu)


def test_pallas_block_boundary_straddle():
    """Candidates of one anchor block live in two different key b-tiles:
    anchors sit right at block_k boundaries of a dense key row, so the
    r nearest predecessors land in tile t and the successors in t+1.
    Exercised both with the safe full-row k_tiles bound and with the
    exact ``plan_k_tiles`` bound."""
    l, block, max_sep = 512, 128, 6
    ns = np.arange(2, 2 + 2 * l, 2, dtype=np.int32)[None, None, :]  # 2,..,1024
    # anchors at the values just past each 128-value tile edge (256, 512, 768)
    a = np.full((1, l), SENTINEL, np.int32)
    a[0, :6] = [255, 257, 511, 513, 767, 769]
    ns_r = np.full((1, 1), 3, np.int32)
    args = (jnp.asarray(a), jnp.asarray(ns), jnp.asarray(ns_r))
    ref = window_join_ref(*args, max_sep=max_sep, r_max=R_MAX)
    for kt in (None, plan_k_tiles(a, ns, max_sep, block, block)):
        got = window_join(*args, max_sep=max_sep, r_max=R_MAX,
                          use_pallas=True, interpret=True,
                          block_l=block, block_k=block, k_tiles=kt)
        _assert_same(got, ref)
    # every anchor has >=3 even neighbours within 6 on both sides
    valid = np.asarray(ref[0])
    assert valid[0, :6].all() and not valid[0, 6:].any()


# ---------------- deterministic tie-breaking + degenerate cases -------------
def test_tie_pred_before_succ():
    """At equal distance the CPU column order [idx-1, idx, idx-2, ...]
    keeps pred_p before succ_q iff p <= q; pin one hand-computed case on
    all three implementations."""
    a = np.array([[100, SENTINEL]], np.int32)
    ns = np.array([[[98, 102]]], np.int32)  # pred and succ both at dist 2
    for r, want_lo, want_hi in ((1, 98, 100), (2, 98, 102)):
        ns_r = np.array([[r]], np.int32)
        args = (jnp.asarray(a), jnp.asarray(ns), jnp.asarray(ns_r))
        for impl in (
            lambda: window_join(*args, max_sep=5, r_max=R_MAX),
            lambda: window_join_ref(*args, max_sep=5, r_max=R_MAX),
            lambda: window_join(*args, max_sep=5, r_max=R_MAX,
                                use_pallas=True, interpret=True,
                                block_l=128, block_k=128),
        ):
            valid, lo, hi = _np3(impl())
            assert valid[0, 0] and not valid[0, 1]
            assert lo[0, 0] == want_lo and hi[0, 0] == want_hi
    # and the CPU oracle agrees on the r=1 tie
    m, mn, mx = search._nearest_r(np.array([98, 102], np.int64),
                                  np.array([100], np.int64), 5, 1)
    assert m[0] and mn[0] == 98 and mx[0] == 98


def test_inactive_and_empty_keys():
    a = np.array([[10, 20, SENTINEL, SENTINEL]], np.int32)
    empty = np.full((1, 1, 4), SENTINEL, np.int32)
    # r=0: key is padding -> anchors valid with degenerate [a, a] windows
    v, lo, hi = _np3(window_join(jnp.asarray(a), jnp.asarray(empty),
                                 jnp.asarray(np.zeros((1, 1), np.int32)),
                                 max_sep=3, r_max=R_MAX))
    assert list(v[0]) == [True, True, False, False]
    np.testing.assert_array_equal(lo[0, :2], [10, 20])
    np.testing.assert_array_equal(hi[0, :2], [10, 20])
    # r>0 against an empty row -> nothing matches, same as the CPU engine
    v, _, _ = _np3(window_join(jnp.asarray(a), jnp.asarray(empty),
                               jnp.asarray(np.ones((1, 1), np.int32)),
                               max_sep=3, r_max=R_MAX))
    assert not v.any()
    cpu_v, _, _ = _cpu_join(a, empty, np.ones((1, 1), np.int32), max_sep=3)
    assert not cpu_v.any()
