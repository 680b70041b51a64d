"""Jitted wrapper for EmbeddingBag: padding + mean-combine + fallback."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.common import cdiv
from repro.kernels.embedding_bag.embedding_bag import (
    DEFAULT_BLOCK_B,
    DEFAULT_BLOCK_V,
    embedding_bag_pallas,
)
from repro.kernels.embedding_bag.ref import embedding_bag_ref


def embedding_bag(
    ids: jnp.ndarray,
    table: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    combine: str = "sum",
    *,
    use_pallas: bool = False,
    block_b: int = DEFAULT_BLOCK_B,
    block_v: int = DEFAULT_BLOCK_V,
    interpret: bool = False,
) -> jnp.ndarray:
    """Bag-reduce embedding lookup. use_pallas routes through the MXU
    one-hot kernel (TPU hot path); default is the XLA gather reference,
    which is what large sharded tables use under GSPMD."""
    if not use_pallas:
        return embedding_bag_ref(ids, table, weights, combine)
    B, S = ids.shape
    V, D = table.shape
    w = jnp.ones_like(ids, jnp.float32) if weights is None else weights.astype(jnp.float32)
    pb = (-B) % block_b
    if pb:
        ids = jnp.pad(ids, ((0, pb), (0, 0)), constant_values=-1)
        w = jnp.pad(w, ((0, pb), (0, 0)))
    pv = (-V) % block_v
    if pv:
        table = jnp.pad(table, ((0, pv), (0, 0)))
    out = embedding_bag_pallas(
        ids.astype(jnp.int32), w, table, block_b=block_b, block_v=block_v, interpret=interpret
    )[:B]
    if combine == "mean":
        denom = jnp.maximum((ids[:B] >= 0).sum(axis=1, keepdims=True), 1)
        out = out / denom.astype(out.dtype)
    return out.astype(table.dtype)
