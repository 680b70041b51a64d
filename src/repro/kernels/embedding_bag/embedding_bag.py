"""Pallas TPU kernel: EmbeddingBag as blocked one-hot matmul.

TPU has no efficient in-kernel random gather; the TPU-native realization
of a bag lookup routes through the MXU: for each (batch-block, vocab-
block) grid cell, build the masked one-hot matrix of the ids that fall in
the vocab block and contract it with the resident table tile:

    out[Bb, D] += onehot(ids[Bb, S] - v0)  @  table[Vb, D]
                  (Bb*S, Vb)                  (Vb, D)

The vocab axis is the innermost grid dimension so the f32 accumulator
tile stays in VMEM across the sweep. For sharded tables (model-parallel
rows), the wrapper runs this kernel per shard and psums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_V = 512


def _kernel(ids_ref, w_ref, table_ref, out_ref, *, block_v: int):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]  # (Bb, S) int32
    w = w_ref[...]  # (Bb, S) f32
    table = table_ref[...]  # (Vb, D)
    v0 = v * block_v
    local = ids - v0  # (Bb, S)
    in_block = (local >= 0) & (local < block_v) & (ids >= 0)
    bb, s = ids.shape
    # one-hot on the MXU: (Bb*S, Vb) @ (Vb, D)
    local_flat = jnp.where(in_block, local, 0).reshape(bb * s)
    onehot = (
        local_flat[:, None] == jax.lax.iota(jnp.int32, block_v)[None, :]
    ).astype(table.dtype)
    onehot = onehot * (in_block.reshape(bb * s, 1)).astype(table.dtype)
    onehot = onehot * w.reshape(bb * s, 1).astype(table.dtype)
    contrib = jnp.dot(onehot, table, preferred_element_type=jnp.float32)
    out_ref[...] += contrib.reshape(bb, s, -1).sum(axis=1).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_v", "interpret")
)
def embedding_bag_pallas(
    ids: jnp.ndarray,  # (B, S) int32 (padded rows: -1)
    weights: jnp.ndarray,  # (B, S) f32
    table: jnp.ndarray,  # (V, D); V % block_v == 0
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_v: int = DEFAULT_BLOCK_V,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S = ids.shape
    V, D = table.shape
    grid = (B // block_b, V // block_v)
    kernel = functools.partial(_kernel, block_v=block_v)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, S), lambda b, v: (b, 0)),
            pl.BlockSpec((block_b, S), lambda b, v: (b, 0)),
            pl.BlockSpec((block_v, D), lambda b, v: (v, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, D), lambda b, v: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(ids, weights, table)
