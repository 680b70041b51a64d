"""Device (batched/sharded) QT1 engine vs the reference CPU engine."""

import numpy as np
import jax
import pytest

from repro.core.index_builder import build_index
from repro.core.jax_search import (
    decode_results,
    make_qt1_serve_step,
    pack_qt1_batch,
    qt1_join,
    qt1_score,
)
from repro.core.search import ProximitySearchEngine
from repro.data.corpus import generate_corpus, sample_stop_queries

D = 5


@pytest.fixture(scope="module")
def world():
    table, lex = generate_corpus(n_docs=80, mean_doc_len=70, vocab_size=500, seed=11)
    lex.sw_count = 14
    lex.fu_count = 30
    idx = build_index(table, lex, max_distance=D)
    queries = sample_stop_queries(table, lex, 16, window=D, seed=4)
    return table, lex, idx, queries


def _engine_results(idx, q):
    eng = ProximitySearchEngine(idx, top_k=100_000, equalize_mode="bulk")
    res, _ = eng.search_ids(q)
    return set(zip(res.doc.tolist(), res.start.tolist(), res.end.tolist()))


def test_device_qt1_matches_reference(world):
    table, lex, idx, queries = world
    batch = pack_qt1_batch(idx, queries, L=2048, K=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    step = make_qt1_serve_step(mesh, top_k=512)
    outs = step(*batch.device_args())
    decoded = decode_results(batch, *outs)
    for qi, q in enumerate(queries):
        got = set(
            zip(
                decoded[qi]["doc"].tolist(),
                decoded[qi]["start"].tolist(),
                decoded[qi]["end"].tolist(),
            )
        )
        want = _engine_results(idx, q)
        assert got == want, (qi, q, got ^ want)


def test_device_qt1_scores_match_reference(world):
    table, lex, idx, queries = world
    batch = pack_qt1_batch(idx, queries, L=2048, K=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    step = make_qt1_serve_step(mesh, top_k=64)
    outs = step(*batch.device_args())
    decoded = decode_results(batch, *outs)
    eng = ProximitySearchEngine(idx, top_k=64, equalize_mode="bulk")
    for qi, q in enumerate(queries):
        res, _ = eng.search_ids(q)
        if res.size == 0:
            assert decoded[qi]["doc"].size == 0
            continue
        assert decoded[qi]["score"].size > 0
        np.testing.assert_allclose(
            np.max(decoded[qi]["score"]), float(res.score[0]), rtol=1e-6
        )


def test_doc_sharded_serving_multidevice():
    """The real distributed invariant: on a (2, 4) mesh with doc_shards ==
    model size == 4, the sharded join must match the single-device result.
    Runs in a subprocess with 8 forced host devices."""
    import subprocess
    import sys
    from pathlib import Path

    script = Path(__file__).parent / "multidevice" / "check_sharded_search.py"
    env = dict(
        PYTHONPATH=str(Path(__file__).parent.parent / "src"),
        PATH="/usr/bin:/bin",
        HOME="/root",
    )
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True, env=env, timeout=300
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED_SEARCH_OK" in proc.stdout


def test_qt1_join_handles_all_sentinel_query():
    from repro.kernels.common import SENTINEL

    B, K, L = 2, 2, 64
    g = np.full((B, K, L), SENTINEL, np.int32)
    lo = g.copy()
    hi = g.copy()
    _, valid, _, _ = qt1_join(*(map(np.asarray, (g, lo, hi))))
    assert not bool(np.asarray(valid).any())


# --------------------------------------------------------------------------
# the merge join against NumPy and against the formulation it replaced
# --------------------------------------------------------------------------
def _intersect_ref(g, lo, hi):
    """One query's join as the CPU engine's ``_qt1_bulk`` computes it:
    ``np.intersect1d`` of the anchor row with each other key row."""
    from repro.kernels.common import SENTINEL

    keep = g[0] != SENTINEL
    g0, lo0, hi0 = g[0][keep], lo[0][keep], hi[0][keep]
    for k in range(1, g.shape[0]):
        m = g[k] != SENTINEL
        g0, ia, ib = np.intersect1d(g0, g[k][m], return_indices=True)
        lo0 = np.minimum(lo0[ia], lo[k][m][ib])
        hi0 = np.maximum(hi0[ia], hi[k][m][ib])
    return g0, lo0, hi0


def _join_rows(case, L):
    """(K, L) strictly increasing, SENTINEL-padded key rows of one query
    and their fragment bounds (lo <= g <= hi)."""
    from repro.kernels.common import SENTINEL

    rng = np.random.default_rng(sum(map(ord, case)))
    top = int(SENTINEL)
    if case == "disjoint":
        vals = [np.arange(0, 2 * L, 2), np.arange(1, 2 * L, 2)]
    elif case == "identical":  # the K-padding repeat of one key
        a = np.sort(rng.choice(10 * L, L - 5, replace=False))
        vals = [a, a]
    elif case == "partial":
        vals = [np.sort(rng.choice(3 * L, L, replace=False)) for _ in range(2)]
    elif case == "all_sentinel":
        vals = [np.zeros(0, np.int64)] * 2
    elif case == "empty_key":
        vals = [np.arange(5, 5 + L), np.zeros(0, np.int64)]
    elif case == "unequal_fill":
        a = np.sort(rng.choice(4 * L, L, replace=False))
        vals = [a[::9], a[: L // 3]]
    elif case == "near_sentinel":  # the largest keys an int32 row holds
        vals = [np.arange(top - 12, top), np.arange(top - 20, top, 2)]
    elif case == "full_rows":  # no padding lane at all
        vals = [np.arange(L) * 3, np.arange(L) * 2]
    elif case == "three_keys":
        a = np.sort(rng.choice(2 * L, L, replace=False))
        b = np.sort(rng.choice(2 * L, L, replace=False))
        vals = [a, b, a]
    else:
        raise ValueError(case)
    K = len(vals)
    g = np.full((K, L), SENTINEL, np.int32)
    for k, v in enumerate(vals):
        g[k, : len(v)] = v
    pad = g == SENTINEL
    lo = np.where(pad, SENTINEL, g - rng.integers(0, 6, g.shape)).astype(np.int32)
    hi = np.where(pad, SENTINEL, np.minimum(g.astype(np.int64) + rng.integers(0, 6, g.shape),
                                            top - 1)).astype(np.int32)
    return g, lo, hi


JOIN_CASES = ["disjoint", "identical", "partial", "all_sentinel", "empty_key",
              "unequal_fill", "near_sentinel", "full_rows", "three_keys"]


@pytest.mark.parametrize("L", [64, 48])
@pytest.mark.parametrize("case", JOIN_CASES)
def test_qt1_join_matches_numpy_intersection(case, L):
    rows = [_join_rows(c, L) for c in (case, "partial" if case != "three_keys" else case)]
    g, lo, hi = (np.stack([r[i] for r in rows]) for i in range(3))
    mg, valid, mlo, mhi = map(np.asarray, qt1_join(g, lo, hi))
    for b in range(g.shape[0]):
        want = _intersect_ref(g[b], lo[b], hi[b])
        got = (mg[b][valid[b]], mlo[b][valid[b]], mhi[b][valid[b]])
        for w, x in zip(want, got):  # the anchor's order: increasing g
            np.testing.assert_array_equal(x, w)


def _searchsorted_step(top_k):
    """The QT1 step as it was before the merge join: a binary search of
    every anchor lane in each other key row, then three gathers. Kept
    here only as the reference the merge join must reproduce."""
    import jax.numpy as jnp

    from repro.core.jax_search import qt1_topk
    from repro.kernels.common import SENTINEL

    def step(key_g, key_lo, key_hi, idf_sum, span_adjust):
        g0 = key_g[:, 0]
        valid = g0 != SENTINEL
        lo, hi = key_lo[:, 0], key_hi[:, 0]
        for k in range(1, key_g.shape[1]):
            idx = jax.vmap(jnp.searchsorted)(key_g[:, k], g0)
            idx = jnp.clip(idx, 0, key_g.shape[2] - 1)
            found = (jnp.take_along_axis(key_g[:, k], idx, axis=1) == g0) & valid
            valid &= found
            lo_k = jnp.take_along_axis(key_lo[:, k], idx, axis=1)
            hi_k = jnp.take_along_axis(key_hi[:, k], idx, axis=1)
            lo = jnp.where(found, jnp.minimum(lo, lo_k), lo)
            hi = jnp.where(found, jnp.maximum(hi, hi_k), hi)
        score = qt1_score(valid, lo, hi, idf_sum, span_adjust)
        return qt1_topk(score, g0, lo, hi, top_k)

    return jax.jit(step)


@pytest.mark.parametrize("payload,top_k", [("raw", 16), ("raw", 512), ("delta16", 16)])
def test_device_qt1_step_matches_searchsorted_formulation(world, payload, top_k):
    from repro.core.jax_search import compress_qt1_batch, make_qt1_serve_step_compressed

    table, lex, idx, queries = world
    batch = pack_qt1_batch(idx, queries, L=2048, K=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    if payload == "raw":
        outs = make_qt1_serve_step(mesh, top_k=top_k)(*batch.device_args())
    else:
        step = make_qt1_serve_step_compressed(mesh, top_k=top_k, delta_g=True)
        outs = step(*compress_qt1_batch(batch, delta_g=True))
    got = decode_results(batch, *outs)
    want = decode_results(batch, *_searchsorted_step(top_k)(*batch.device_args()))
    assert sum(w["doc"].size for w in want) > 0
    for g, w in zip(got, want):
        for field in ("doc", "start", "end", "score"):
            np.testing.assert_array_equal(g[field], w[field])
