"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes, and hypothesis property tests."""

import numpy as np
import pytest
import jax.numpy as jnp

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.common import SENTINEL
from repro.kernels.intersect.ops import intersect_sorted, plan_k_tiles as plan_k_int
from repro.kernels.intersect.ref import intersect_mask_ref
from repro.kernels.proximity.ops import proximity_join, plan_k_tiles as plan_k_prox
from repro.kernels.proximity.ref import proximity_join_ref
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref


def _sorted_unique(rng, n, hi):
    return np.unique(rng.integers(0, hi, n).astype(np.int32))


# ---------------- intersect -------------------------------------------------
@pytest.mark.parametrize("na,nb,hi", [
    (100, 100, 500),       # dense overlap
    (1000, 5000, 20000),   # skewed sizes
    (5000, 700, 100000),   # sparse overlap
    (513, 1025, 4000),     # non-multiple-of-block sizes
    (3, 2, 10),            # tiny
])
def test_intersect_vs_ref_shapes(na, nb, hi):
    rng = np.random.default_rng(na * 7 + nb)
    a = _sorted_unique(rng, na, hi)
    b = _sorted_unique(rng, nb, hi)
    k = plan_k_int(a, b)
    mask, idx = intersect_sorted(jnp.asarray(a), jnp.asarray(b), k_tiles=k,
                                 interpret=True)
    want = np.isin(a, b)
    np.testing.assert_array_equal(np.asarray(mask), want)
    # idx must point at the matching value in padded b
    b_pad = np.concatenate([b, np.full((-len(b)) % 1024, SENTINEL, np.int32)])
    got_idx = np.asarray(idx)
    assert np.all(b_pad[got_idx[want]] == a[want])


def test_intersect_ref_matches_numpy():
    rng = np.random.default_rng(0)
    a = _sorted_unique(rng, 400, 2000)
    b = _sorted_unique(rng, 300, 2000)
    mask = intersect_mask_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(mask), np.isin(a, b))


@given(
    st.lists(st.integers(0, 300), max_size=60),
    st.lists(st.integers(0, 300), max_size=60),
)
@settings(max_examples=40, deadline=None)
def test_intersect_property(xs, ys):
    a = np.unique(np.array(xs + [0], np.int32))
    b = np.unique(np.array(ys + [0], np.int32))
    mask, _ = intersect_sorted(jnp.asarray(a), jnp.asarray(b), block_a=8, block_b=16,
                               k_tiles=plan_k_int(a, b, 8, 16), interpret=True)
    np.testing.assert_array_equal(np.asarray(mask), np.isin(a, b))


def test_intersect_full_scan_default_k():
    rng = np.random.default_rng(3)
    a = _sorted_unique(rng, 600, 3000)
    b = _sorted_unique(rng, 900, 3000)
    mask, _ = intersect_sorted(jnp.asarray(a), jnp.asarray(b),  # k_tiles=None
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(mask), np.isin(a, b))


# ---------------- proximity -------------------------------------------------
@pytest.mark.parametrize("d", [1, 5, 7, 9])
@pytest.mark.parametrize("na,nb", [(200, 300), (1100, 600)])
def test_proximity_vs_ref(d, na, nb):
    rng = np.random.default_rng(d * 101 + na)
    a = _sorted_unique(rng, na, 8000)
    b = _sorted_unique(rng, nb, 8000)
    k = plan_k_prox(a, b, d)
    mask, lo, hi = proximity_join(jnp.asarray(a), jnp.asarray(b), d, k_tiles=k,
                                  interpret=True)
    rmask, rlo, rhi = proximity_join_ref(jnp.asarray(a), jnp.asarray(b), d)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(rmask))
    m = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(lo)[m], np.asarray(rlo)[m])
    np.testing.assert_array_equal(np.asarray(hi)[m], np.asarray(rhi)[m])


def test_proximity_ref_matches_bruteforce():
    rng = np.random.default_rng(1)
    a = _sorted_unique(rng, 80, 400)
    b = _sorted_unique(rng, 60, 400)
    d = 5
    mask, lo, hi = proximity_join_ref(jnp.asarray(a), jnp.asarray(b), d)
    for i, av in enumerate(a.tolist()):
        near = b[(b >= av - d) & (b <= av + d)]
        assert bool(mask[i]) == (near.size > 0)
        if near.size:
            assert int(lo[i]) == near.min() and int(hi[i]) == near.max()


# ---------------- embedding bag ---------------------------------------------
@pytest.mark.parametrize("B,S,V,D", [
    (32, 8, 100, 16),
    (130, 5, 513, 32),   # non-multiples
    (8, 1, 2000, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_vs_ref(B, S, V, D, dtype):
    rng = np.random.default_rng(B + V)
    ids = rng.integers(-1, V, (B, S)).astype(np.int32)
    table = rng.normal(size=(V, D)).astype(np.float32)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    out_k = embedding_bag(jnp.asarray(ids), jnp.asarray(table, dtype), use_pallas=True,
                          block_b=32, block_v=128, interpret=True)
    out_r = embedding_bag_ref(jnp.asarray(ids), jnp.asarray(table, dtype))
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32), rtol=tol, atol=tol * 10
    )


def test_embedding_bag_weights_and_mean():
    rng = np.random.default_rng(7)
    B, S, V, D = 16, 6, 50, 8
    ids = rng.integers(-1, V, (B, S)).astype(np.int32)
    w = rng.normal(size=(B, S)).astype(np.float32)
    table = rng.normal(size=(V, D)).astype(np.float32)
    for combine in ("sum", "mean"):
        out_k = embedding_bag(jnp.asarray(ids), jnp.asarray(table), jnp.asarray(w),
                              combine, use_pallas=True, block_b=8, block_v=16,
                              interpret=True)
        out_r = embedding_bag_ref(jnp.asarray(ids), jnp.asarray(table), jnp.asarray(w), combine)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5)


def test_embedding_bag_ref_manual():
    table = jnp.asarray(np.eye(4, dtype=np.float32))
    ids = jnp.asarray(np.array([[0, 1, -1], [2, 2, 3]], np.int32))
    out = embedding_bag_ref(ids, table)
    np.testing.assert_array_equal(
        np.asarray(out), np.array([[1, 1, 0, 0], [0, 0, 2, 1]], np.float32)
    )


# ---------------- compressed-stream intersect (in-kernel decode) -----------
@pytest.mark.parametrize("na,nb,hi", [
    (300, 500, 4000),
    (1000, 2000, 30000),
    (70, 1500, 9000),
])
def test_intersect_compressed_vs_numpy(na, nb, hi):
    from repro.kernels.intersect.ops import intersect_sorted_compressed

    rng = np.random.default_rng(na + nb)
    a = _sorted_unique(rng, na, hi)
    b = _sorted_unique(rng, nb, hi)
    mask = intersect_sorted_compressed(a, b, block_a=128, block_b=256,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(mask), np.isin(a, b))


def test_pack_delta_stream_roundtrip():
    from repro.kernels.intersect.intersect import DELTA_BLK, PAD_DELTA
    from repro.kernels.intersect.ops import pack_delta_stream

    rng = np.random.default_rng(0)
    x = np.unique(rng.integers(0, 10_000, 500)).astype(np.int32)
    base, delta = pack_delta_stream(x, 1024)
    rec = np.repeat(base, DELTA_BLK).astype(np.int64) + delta
    valid = delta != PAD_DELTA
    np.testing.assert_array_equal(rec[valid][: x.size], x)
    assert valid.sum() == x.size
