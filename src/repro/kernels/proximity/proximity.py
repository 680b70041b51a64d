"""Pallas TPU kernel: proximity window join (|a - b| <= MaxDistance).

Same blocked structure as the intersect kernel (scalar-prefetched B-window
per A-block), different predicate, three outputs: match mask, min and max
matched B-position per A element (fragment bounds [P, E] of the paper's
result records). The MaxDistance parameter of the paper is the kernel's
`d` — static, so each Idx_d index family compiles its own specialized
join, mirroring the paper's per-MaxDistance index files.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import SENTINEL

DEFAULT_BLOCK_A = 512
DEFAULT_BLOCK_B = 1024

_I32_MAX = 2**31 - 1
_I32_MIN = -(2**31)


def _kernel(starts_ref, a_ref, b_ref, mask_ref, lo_ref, hi_ref, *, d: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        mask_ref[...] = jnp.zeros_like(mask_ref)
        lo_ref[...] = jnp.full_like(lo_ref, _I32_MAX)
        hi_ref[...] = jnp.full_like(hi_ref, _I32_MIN)

    a = a_ref[...]
    b = b_ref[...]
    near = (jnp.abs(a[:, None] - b[None, :]) <= d) & (b[None, :] != SENTINEL)
    near = near & (a[:, None] != SENTINEL)
    hit = jnp.any(near, axis=1)
    b_lo = jnp.min(jnp.where(near, b[None, :], _I32_MAX), axis=1)
    b_hi = jnp.max(jnp.where(near, b[None, :], _I32_MIN), axis=1)
    mask_ref[...] = mask_ref[...] | hit
    lo_ref[...] = jnp.minimum(lo_ref[...], b_lo)
    hi_ref[...] = jnp.maximum(hi_ref[...], b_hi)


@functools.partial(
    jax.jit, static_argnames=("d", "block_a", "block_b", "k_tiles", "interpret")
)
def proximity_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    d: int,
    block_a: int = DEFAULT_BLOCK_A,
    block_b: int = DEFAULT_BLOCK_B,
    k_tiles: int = 1,
    interpret: bool = False,
):
    na_blocks = a.shape[0] // block_a
    nb_blocks = b.shape[0] // block_b
    kernel = functools.partial(_kernel, d=d)
    mask, lo, hi = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(na_blocks, k_tiles),
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
                pl.BlockSpec((block_a,), lambda i, k, starts: (i,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((a.shape[0],), jnp.bool_),
            jax.ShapeDtypeStruct((a.shape[0],), jnp.int32),
            jax.ShapeDtypeStruct((a.shape[0],), jnp.int32),
        ],
        interpret=interpret,
    )(starts, a, b)
    return mask, lo, hi
