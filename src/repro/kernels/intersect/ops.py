"""Jitted public wrapper around the intersection kernel.

Handles padding, host-side window planning (searchsorted on A-block
boundaries), and the k_tiles static bound. `plan_k_tiles` computes the
exact bound for concrete inputs; serving systems pick a bucket-level bound
offline (the response-time guarantee).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import SENTINEL, cdiv, pad_to_multiple
from repro.kernels.intersect.intersect import (
    DEFAULT_BLOCK_A,
    DEFAULT_BLOCK_B,
    intersect_pallas,
)
from repro.kernels.intersect.ref import intersect_idx_ref


def plan_starts(a_padded: jnp.ndarray, b_padded: jnp.ndarray, block_a: int, block_b: int):
    """Aligned B-block start per A-block (traceable; runs outside the kernel)."""
    a_mins = a_padded[::block_a]
    start_elem = jnp.searchsorted(b_padded, a_mins)
    return (start_elem // block_b).astype(jnp.int32)


def plan_k_tiles(a: np.ndarray, b: np.ndarray, block_a: int = DEFAULT_BLOCK_A, block_b: int = DEFAULT_BLOCK_B) -> int:
    """Exact static bound on B-blocks any A-block can span (host-side,
    concrete arrays): max over blocks of ceil span. Never < 1."""
    a = np.asarray(a)
    b = np.asarray(b)
    if a.size == 0 or b.size == 0:
        return 1
    na = cdiv(a.size, block_a)
    k = 1
    for i in range(na):
        blk = a[i * block_a : (i + 1) * block_a]
        lo = int(np.searchsorted(b, blk[0])) // block_b
        hi = int(np.searchsorted(b, blk[-1], side="right"))
        hi_blk = max(lo, cdiv(max(hi, 1), block_b) - 1)
        k = max(k, hi_blk - lo + 1)
    return int(k)


def pack_delta_stream(x: np.ndarray, total_len: int):
    """Host/offline packing: sorted int32 postings -> (base int32 per 64,
    delta uint16, padded to total_len). Raises if an in-block span exceeds
    uint16 (the index builder then splits the block)."""
    from repro.kernels.intersect.intersect import DELTA_BLK, PAD_DELTA

    x = np.asarray(x, np.int64)
    assert total_len % DELTA_BLK == 0
    nb = total_len // DELTA_BLK
    padded = np.full(total_len, 0, np.int64)
    padded[: x.size] = x
    blocks = padded.reshape(nb, DELTA_BLK)
    base = blocks[:, 0].copy()
    # blocks fully in padding get base of the last real value
    if x.size:
        last_real_block = (x.size - 1) // DELTA_BLK
        base[last_real_block + 1 :] = 0
    delta = blocks - base[:, None]
    if x.size and delta[: last_real_block + 1].max() >= PAD_DELTA:
        raise ValueError("in-block span exceeds uint16")
    delta = np.clip(delta, 0, PAD_DELTA).astype(np.uint16)
    flat = delta.reshape(-1)
    flat[x.size :] = PAD_DELTA  # pad marker
    return base.astype(np.int32), flat


def intersect_sorted_compressed(
    a: np.ndarray,
    b: np.ndarray,
    *,
    block_a: int = DEFAULT_BLOCK_A,
    block_b: int = DEFAULT_BLOCK_B,
    k_tiles: int | None = None,
    interpret: bool = False,
):
    """Same contract as intersect_sorted (mask only) but the posting
    streams cross HBM as base+delta (2.06 B/posting)."""
    from repro.kernels.intersect.intersect import intersect_pallas_compressed

    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    la = cdiv(max(a.size, 1), block_a) * block_a
    lb = cdiv(max(b.size, 1), block_b) * block_b
    a_base, a_delta = pack_delta_stream(a, la)
    b_base, b_delta = pack_delta_stream(b, lb)
    a_mins = a_base[:: block_a // 64]
    start_elem = np.searchsorted(b, a_mins)
    starts = (start_elem // block_b).astype(np.int32)
    if k_tiles is None:
        k_tiles = lb // block_b
    mask = intersect_pallas_compressed(
        jnp.asarray(a_base), jnp.asarray(a_delta), jnp.asarray(b_base),
        jnp.asarray(b_delta), jnp.asarray(starts),
        block_a=block_a, block_b=block_b, k_tiles=int(k_tiles), interpret=interpret,
    )
    return mask[: a.size]


def intersect_sorted(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_a: int = DEFAULT_BLOCK_A,
    block_b: int = DEFAULT_BLOCK_B,
    k_tiles: int | None = None,
    use_pallas: bool = True,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Membership of each element of sorted `a` in sorted `b`.

    Returns (mask, idx) of length len(a): idx is the matching position in
    the *padded* b (valid wherever mask). With use_pallas=False, the
    searchsorted oracle runs instead (same contract)."""
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    n = a.shape[0]
    if not use_pallas:
        mask, idx = intersect_idx_ref(a, b)
        return mask, idx
    a_p = pad_to_multiple(a, block_a, SENTINEL)
    b_p = pad_to_multiple(b, block_b, SENTINEL)
    if k_tiles is None:
        k_tiles = b_p.shape[0] // block_b  # safe full scan
    starts = plan_starts(a_p, b_p, block_a, block_b)
    mask, idx = intersect_pallas(
        a_p, b_p, starts, block_a=block_a, block_b=block_b, k_tiles=int(k_tiles), interpret=interpret
    )
    return mask[:n], idx[:n]
