"""Jitted wrapper for the proximity window join kernel."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.common import SENTINEL, cdiv, pad_to_multiple
from repro.kernels.proximity.proximity import (
    DEFAULT_BLOCK_A,
    DEFAULT_BLOCK_B,
    proximity_pallas,
)
from repro.kernels.proximity.ref import proximity_join_ref


def plan_starts(a_padded, b_padded, d: int, block_a: int, block_b: int):
    a_mins = a_padded[::block_a]
    start_elem = jnp.searchsorted(b_padded, a_mins - d)
    return (start_elem // block_b).astype(jnp.int32)


def plan_k_tiles(
    a: np.ndarray, b: np.ndarray, d: int, block_a: int = DEFAULT_BLOCK_A, block_b: int = DEFAULT_BLOCK_B
) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.size == 0 or b.size == 0:
        return 1
    k = 1
    for i in range(cdiv(a.size, block_a)):
        blk = a[i * block_a : (i + 1) * block_a]
        lo = int(np.searchsorted(b, blk[0] - d)) // block_b
        hi = int(np.searchsorted(b, blk[-1] + d, side="right"))
        hi_blk = max(lo, cdiv(max(hi, 1), block_b) - 1)
        k = max(k, hi_blk - lo + 1)
    return int(k)


def proximity_join(
    a: jnp.ndarray,
    b: jnp.ndarray,
    d: int,
    *,
    block_a: int = DEFAULT_BLOCK_A,
    block_b: int = DEFAULT_BLOCK_B,
    k_tiles: int | None = None,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """For each a_i: (is there a b within d, min matched b, max matched b)."""
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    n = a.shape[0]
    if not use_pallas:
        return proximity_join_ref(a, b, d)
    a_p = pad_to_multiple(a, block_a, SENTINEL)
    b_p = pad_to_multiple(b, block_b, SENTINEL)
    if k_tiles is None:
        k_tiles = b_p.shape[0] // block_b
    starts = plan_starts(a_p, b_p, d, block_a, block_b)
    mask, lo, hi = proximity_pallas(
        a_p, b_p, starts, d=d, block_a=block_a, block_b=block_b,
        k_tiles=int(k_tiles), interpret=interpret,
    )
    mask, lo, hi = mask[:n], lo[:n], hi[:n]
    lo = jnp.where(mask, lo, a)
    hi = jnp.where(mask, hi, a)
    return mask, lo, hi
