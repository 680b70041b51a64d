"""Sharded embedding tables (recsys substrate).

JAX has no EmbeddingBag and GSPMD's handling of gathers from row-sharded
operands is opaque, so the model-parallel lookup is explicit shard_map:
tables are row-sharded (contiguous ranges) over the `model` axis; each
rank gathers the ids it owns and the partials are psum'd — the collective
is only (batch, dim), never the table. This is the standard production
embedding-parallel pattern (DLRM-style) adapted to the jax mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def embedding_lookup(table, ids):
    """Unsharded reference: ids (...,) int32, -1 = padding -> zeros."""
    safe = jnp.maximum(ids, 0)
    out = jnp.take(table, safe, axis=0)
    return out * (ids >= 0)[..., None].astype(table.dtype)


def embedding_bag_sum(table, ids, weights=None):
    """Bag-reduce over the last id axis: ids (..., S) -> (..., D)."""
    rows = embedding_lookup(table, ids)
    if weights is not None:
        rows = rows * weights[..., None].astype(rows.dtype)
    return rows.sum(axis=-2)


def _local_lookup(table_l, ids, rank, rows_per_shard):
    local = ids - rank * rows_per_shard
    valid = (local >= 0) & (local < rows_per_shard) & (ids >= 0)
    safe = jnp.clip(local, 0, rows_per_shard - 1)
    out = jnp.take(table_l, safe, axis=0)
    return out * valid[..., None].astype(table_l.dtype)


def sharded_embedding_lookup(table, ids, mesh, tp_axis="model", dp_axes=("data",), ids_pspec=None):
    """table row-sharded over tp_axis; ids sharded over dp_axes (leading
    axis) unless an explicit ids_pspec is given (e.g. retrieval shards the
    *candidate* axis). Returns embeddings sharded like ids."""
    tp = mesh.shape[tp_axis]
    V = table.shape[0]
    assert V % tp == 0, (V, tp)
    rows_per_shard = V // tp

    def body(table_l, ids_l):
        rank = jax.lax.axis_index(tp_axis)
        out = _local_lookup(table_l, ids_l, rank, rows_per_shard)
        return jax.lax.psum(out, tp_axis)

    ndim_ids = ids.ndim
    if ids_pspec is None:
        ids_pspec = P(dp_axes, *([None] * (ndim_ids - 1)))
    out_spec = P(*(tuple(ids_pspec) + (None,) * (ndim_ids + 1 - len(tuple(ids_pspec)))))
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(tp_axis, None), ids_pspec),
        out_specs=out_spec,
        check_vma=False,
    )(table, ids)


def sharded_embedding_bag(table, ids, mesh, weights=None, tp_axis="model", dp_axes=("data",), ids_pspec=None):
    """Bag-reduce lookup with the psum applied *after* the local bag sum —
    the collective stays (batch, D) regardless of bag size S.

    NOTE: ids must never be sharded over tp_axis (the psum over table
    shards would then mix different rows' partials)."""
    tp = mesh.shape[tp_axis]
    V = table.shape[0]
    assert V % tp == 0
    rows_per_shard = V // tp

    def body(table_l, ids_l, w_l):
        rank = jax.lax.axis_index(tp_axis)
        rows = _local_lookup(table_l, ids_l, rank, rows_per_shard)
        if w_l is not None:
            rows = rows * w_l[..., None].astype(rows.dtype)
        return jax.lax.psum(rows.sum(axis=-2), tp_axis)

    nd = ids.ndim
    ids_spec = ids_pspec if ids_pspec is not None else P(dp_axes, *([None] * (nd - 1)))
    sp = tuple(ids_spec)
    sp = sp + (None,) * (nd - len(sp))
    out_spec = P(*(sp[: nd - 1] + (None,)))  # bag axis reduced away, D replicated
    if weights is None:
        return jax.shard_map(
            lambda t, i: body(t, i, None),
            mesh=mesh,
            in_specs=(P(tp_axis, None), ids_spec),
            out_specs=out_spec,
            check_vma=False,
        )(table, ids)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(tp_axis, None), ids_spec, ids_spec),
        out_specs=out_spec,
        check_vma=False,
    )(table, ids, weights)
