"""Pallas TPU kernel: blocked sorted-list intersection with scalar-
prefetched dynamic B-window placement.

TPU adaptation of the paper's Equalize (§2.3): instead of a binary heap
advancing one iterator at a time, list A is tiled into VMEM blocks; for
each A-block the host precomputes (via searchsorted on block boundaries)
which aligned block of B its value range can possibly overlap. The grid is
(num_a_blocks, k_tiles): step (i, k) compares A-tile i against B-tile
(start[i] + k) with a broadcast equality over the VPU — a (BA, BB) int32
compare, well within VMEM at the default 512x1024 tile.

k_tiles bounds the per-block B-span and therefore the *compiled latency*
of the search step — the kernel-level realization of the paper's
"response time guarantee" (see DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import SENTINEL, cdiv, pad_to_multiple

DEFAULT_BLOCK_A = 512
DEFAULT_BLOCK_B = 1024


def _kernel(starts_ref, a_ref, b_ref, mask_ref, idx_ref, *, block_b: int, nb_blocks: int):
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        mask_ref[...] = jnp.zeros_like(mask_ref)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    a = a_ref[...]  # (BA,)
    b = b_ref[...]  # (BB,)
    eq = a[:, None] == b[None, :]  # (BA, BB) — VPU broadcast compare
    hit = jnp.any(eq, axis=1) & (a != SENTINEL)
    # global b-index of the first match within this tile
    col = jnp.argmax(eq, axis=1).astype(jnp.int32)
    b_block = jnp.minimum(starts_ref[i] + k, nb_blocks - 1)
    gidx = b_block * block_b + col
    newly = hit & (idx_ref[...] < 0)
    mask_ref[...] = mask_ref[...] | hit
    idx_ref[...] = jnp.where(newly, gidx, idx_ref[...])


DELTA_BLK = 64  # postings per delta-coding block
PAD_DELTA = 2**16 - 1  # uint16 marker for padding slots


def _kernel_compressed(
    starts_ref, a_base_ref, a_delta_ref, b_base_ref, b_delta_ref, mask_ref,
    *, nb_blocks: int
):
    """In-kernel decompression (§Perf hillclimb C, TPU completion): posting
    streams arrive as int32 per-64 block bases + uint16 in-block deltas and
    are decoded in VMEM between the DMA and the compare — the decoded int32
    form never round-trips through HBM (the XLA-level decompression did,
    which kept bytes_accessed flat; see EXPERIMENTS.md §Perf C)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        mask_ref[...] = jnp.zeros_like(mask_ref)

    a_delta = a_delta_ref[...]  # (BA,) uint16
    a = jnp.repeat(a_base_ref[...], DELTA_BLK) + a_delta.astype(jnp.int32)
    a_pad = a_delta == PAD_DELTA
    b_delta = b_delta_ref[...]
    b = jnp.repeat(b_base_ref[...], DELTA_BLK) + b_delta.astype(jnp.int32)
    b_ok = (b_delta != PAD_DELTA)[None, :]
    eq = (a[:, None] == b[None, :]) & b_ok
    hit = jnp.any(eq, axis=1) & ~a_pad
    mask_ref[...] = mask_ref[...] | hit


@functools.partial(
    jax.jit, static_argnames=("block_a", "block_b", "k_tiles", "interpret")
)
def intersect_pallas_compressed(
    a_base: jnp.ndarray,
    a_delta: jnp.ndarray,
    b_base: jnp.ndarray,
    b_delta: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    block_a: int = DEFAULT_BLOCK_A,
    block_b: int = DEFAULT_BLOCK_B,
    k_tiles: int = 1,
    interpret: bool = False,
) -> jnp.ndarray:
    """Compressed-stream variant: 2B deltas + 4B/64 bases ≈ 2.06 B/posting
    streamed from HBM vs 4 B/posting for raw int32."""
    na_blocks = a_delta.shape[0] // block_a
    nb_blocks = b_delta.shape[0] // block_b
    kernel = functools.partial(_kernel_compressed, nb_blocks=nb_blocks)
    (mask,) = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(na_blocks, k_tiles),
            in_specs=[
                pl.BlockSpec((block_a // DELTA_BLK,), lambda i, k, starts: (i,)),
                pl.BlockSpec((block_a,), lambda i, k, starts: (i,)),
                pl.BlockSpec(
                    (block_b // DELTA_BLK,),
                    lambda i, k, starts: (jnp.minimum(starts[i] + k, nb_blocks - 1),),
                ),
                pl.BlockSpec(
                    (block_b,),
                    lambda i, k, starts: (jnp.minimum(starts[i] + k, nb_blocks - 1),),
                ),
            ],
            out_specs=[pl.BlockSpec((block_a,), lambda i, k, starts: (i,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((a_delta.shape[0],), jnp.bool_)],
        interpret=interpret,
    )(starts, a_base, a_delta, b_base, b_delta)
    return mask


@functools.partial(
    jax.jit, static_argnames=("block_a", "block_b", "k_tiles", "interpret")
)
def intersect_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    block_a: int = DEFAULT_BLOCK_A,
    block_b: int = DEFAULT_BLOCK_B,
    k_tiles: int = 1,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """a, b: sorted int32, already padded to multiples of the block sizes
    with SENTINEL; starts: (num_a_blocks,) int32 — first B-block index each
    A-block may overlap. Returns (mask, idx) per element of a."""
    na_blocks = a.shape[0] // block_a
    nb_blocks = b.shape[0] // block_b
    grid = (na_blocks, k_tiles)
    kernel = functools.partial(_kernel, block_b=block_b, nb_blocks=nb_blocks)
    mask, idx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_a,), lambda i, k, starts: (i,)),
                pl.BlockSpec(
                    (block_b,),
                    lambda i, k, starts: (jnp.minimum(starts[i] + k, nb_blocks - 1),),
                ),
            ],
            out_specs=[
                pl.BlockSpec((block_a,), lambda i, k, starts: (i,)),
                pl.BlockSpec((block_a,), lambda i, k, starts: (i,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((a.shape[0],), jnp.bool_),
            jax.ShapeDtypeStruct((a.shape[0],), jnp.int32),
        ],
        interpret=interpret,
    )(starts, a, b)
    return mask, idx
