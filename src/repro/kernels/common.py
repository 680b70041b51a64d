"""Shared helpers for the Pallas TPU kernels.

All kernels follow the same contract:
* written for TPU (pl.pallas_call + BlockSpec VMEM tiling, MXU/VPU-aligned
  tile shapes, scalar-prefetched dynamic block index maps);
* validated on CPU with interpret=True against the pure-jnp oracles in
  each kernel's ref.py. Interpret mode is never chosen for the caller:
  every kernel compiles for the TPU unless it is passed interpret=True.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel for padded posting slots: larger than any real doc id / packed
# (doc, pos) key, still valid int32.
SENTINEL = np.int32(2**31 - 1)


def pad_to_multiple(x: jnp.ndarray, multiple: int, fill) -> jnp.ndarray:
    n = x.shape[-1]
    rem = (-n) % multiple
    if rem == 0:
        return x
    pad_width = [(0, 0)] * (x.ndim - 1) + [(0, rem)]
    return jnp.pad(x, pad_width, constant_values=fill)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)

