"""Kernel micro-benchmarks: jitted oracle throughput on CPU + Pallas
(interpret) correctness spot-check per shape. Wall-times on this host are
CPU numbers; the TPU story is in the roofline analysis."""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.intersect.ref import intersect_mask_ref
from repro.kernels.nearest_r import window_join
from repro.kernels.nearest_r.ref import window_join_ref
from repro.kernels.proximity.ref import proximity_join_ref


def _timeit(fn, *args, reps=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _sorted_rows(rng, shape, max_step=3):
    """Strictly increasing int32 rows — the posting-row precondition of
    the nearest-r join."""
    return np.cumsum(rng.integers(1, max_step + 1, shape), axis=-1).astype(np.int32)


def _nearest_r_rows(rng, smoke):
    """Fused window-join rows: argsort baseline vs sort-free counting
    path at a serve-representative shape, plus the Pallas kernel in
    interpret mode at a tiny shape (a correctness spot-check on CPU; the
    compiled-TPU story is DESIGN.md §16)."""
    rows = []
    max_sep, r_max = 5, 4
    shapes = ((8, 256, 2),) if smoke else ((64, 4096, 3),)
    jit_ref = jax.jit(lambda a, n, r: window_join_ref(a, n, r, max_sep=max_sep, r_max=r_max))
    jit_cnt = jax.jit(lambda a, n, r: window_join(a, n, r, max_sep=max_sep, r_max=r_max))
    for B, L, K in shapes:
        a = jnp.asarray(_sorted_rows(rng, (B, L)))
        ns = jnp.asarray(_sorted_rows(rng, (B, K, L)))
        ns_r = jnp.asarray(rng.integers(1, r_max + 1, (B, K)).astype(np.int32))
        reps = 20 if smoke else 5
        dt_ref = _timeit(jit_ref, a, ns, ns_r, reps=reps)
        rows.append((f"kernel/nearest_r_ref_B{B}xL{L}K{K}", dt_ref * 1e6,
                     f"anchors_per_s={B * L / dt_ref:.3e}"))
        dt = _timeit(jit_cnt, a, ns, ns_r, reps=reps)
        rows.append((f"kernel/nearest_r_count_B{B}xL{L}K{K}", dt * 1e6,
                     f"speedup_vs_ref={dt_ref / dt:.2f}x"))
    # Pallas interpret: tiny shape, verified bit-identical on valid lanes
    B, L, K = 2, 64, 2
    a = jnp.asarray(_sorted_rows(rng, (B, L)))
    ns = jnp.asarray(_sorted_rows(rng, (B, K, L)))
    ns_r = jnp.asarray(rng.integers(1, r_max + 1, (B, K)).astype(np.int32))
    pallas = lambda a, n, r: window_join(  # noqa: E731
        a, n, r, max_sep=max_sep, r_max=r_max,
        use_pallas=True, interpret=True, block_l=128, block_k=128)
    v, lo, hi = (np.asarray(x) for x in pallas(a, ns, ns_r))
    wv, wlo, whi = (np.asarray(x) for x in jit_ref(a, ns, ns_r))
    ok = int(np.array_equal(v, wv) and np.array_equal(lo[wv], wlo[wv])
             and np.array_equal(hi[wv], whi[wv]))
    dt = _timeit(pallas, a, ns, ns_r, reps=3)
    rows.append((f"kernel/nearest_r_pallas_interp_B{B}xL{L}K{K}", dt * 1e6,
                 f"bit_identical_to_ref={ok}"))
    return rows


def run(smoke: bool = False):
    rows = []
    rng = np.random.default_rng(0)
    jit_int = jax.jit(intersect_mask_ref)
    jit_prox = jax.jit(lambda a, b: proximity_join_ref(a, b, 5))
    jit_bag = jax.jit(embedding_bag_ref)
    shapes = ((4_096, 16_384),) if smoke else ((16_384, 65_536), (131_072, 1_048_576))
    for n, m in shapes:
        a = jnp.asarray(np.unique(rng.integers(0, 4 * m, n)).astype(np.int32))
        b = jnp.asarray(np.unique(rng.integers(0, 4 * m, m)).astype(np.int32))
        dt = _timeit(jit_int, a, b)
        rows.append((f"kernel/intersect_ref_{n}x{m}", dt * 1e6,
                     f"postings_per_s={(n + m) / dt:.3e}"))
        dt = _timeit(jit_prox, a, b)
        rows.append((f"kernel/proximity_ref_{n}x{m}", dt * 1e6,
                     f"postings_per_s={(n + m) / dt:.3e}"))
    for B, S, V, D in ((256, 20, 10_000, 32),) if smoke else ((4096, 50, 100_000, 64),):
        ids = jnp.asarray(rng.integers(-1, V, (B, S)).astype(np.int32))
        tbl = jnp.asarray(rng.normal(size=(V, D)).astype(np.float32))
        dt = _timeit(jit_bag, ids, tbl)
        rows.append((f"kernel/embedding_bag_ref_B{B}", dt * 1e6,
                     f"lookups_per_s={B * S / dt:.3e}"))
    rows += _nearest_r_rows(rng, smoke)
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
