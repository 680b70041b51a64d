"""Mixture-of-Experts FFN with top-k routing and expert parallelism.

Production design (DESIGN.md §5): activations are replicated over the
`model` mesh axis (Megatron TP keeps them replicated between blocks), so
expert parallelism needs *no all-to-all*: each model rank owns E/TP
experts, gathers the tokens routed to them from its (data-shard-local,
model-replicated) activation block, runs the expert FFNs, scatters back a
partial output, and the per-rank partials are combined by the same psum
that dense TP needs anyway.

The capacity discipline is GShard-style dropping: per data shard,
C = ceil(T_local * top_k * capacity_factor / E); overflow tokens fall back
to the residual stream (standard). Gather/scatter indices are (E_local, C)
int32 — tiny — so no (T, E, C) dense dispatch tensor is ever materialized.

Expressed with shard_map so the collective schedule is explicit and
dry-run-auditable. On a (1,1) mesh this degrades to plain single-device
top-k MoE (used by the smoke tests and the numerics test vs a dense
reference).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25


def init_moe(key, d_model: int, cfg: MoEConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    E, F = cfg.n_experts, cfg.d_ff_expert
    s_in = 1.0 / np.sqrt(d_model)
    s_out = 1.0 / np.sqrt(F)
    return {
        "router": jax.random.normal(k1, (d_model, E), jnp.float32) * s_in,
        "w_gate": jax.random.normal(k2, (E, d_model, F), jnp.float32) * s_in,
        "w_up": jax.random.normal(k3, (E, d_model, F), jnp.float32) * s_in,
        "w_down": jax.random.normal(k4, (E, F, d_model), jnp.float32) * s_out,
    }


def _local_moe(p, x, *, cfg: MoEConfig, n_local_experts: int, expert_offset, capacity: int):
    """Token dispatch for the experts owned by this rank.

    x: (T, D) local tokens (replicated over model axis);
    p arrays already sliced to this rank's experts (E_l, ...).
    Returns (partial_y (T, D), aux load-balance loss term)."""
    T, D = x.shape
    E = cfg.n_experts
    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_e = jax.lax.top_k(probs, cfg.top_k)  # (T, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # position_in_expert via cumulative one-hot counts (GShard)
    flat_e = gate_e.reshape(-1)  # (T*k,) expert ids, row-major by token
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = jnp.cumsum(oh, axis=0) * oh - 1  # (T*k, E), -1 where not routed
    pos = jnp.max(pos_in_e, axis=-1)  # (T*k,)
    keep = (pos >= 0) & (pos < capacity)

    # local expert slot for this rank: slot = (e - offset) * C + pos
    local_e = flat_e - expert_offset
    mine = keep & (local_e >= 0) & (local_e < n_local_experts)
    slot = jnp.where(mine, local_e * capacity + pos, n_local_experts * capacity)

    # scatter token rows into expert slots (one extra trash slot at the end)
    buf = jnp.zeros((n_local_experts * capacity + 1, D), x.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), cfg.top_k)
    buf = buf.at[slot].add(x[tok_idx] * mine[:, None].astype(x.dtype))
    ex_in = buf[:-1].reshape(n_local_experts, capacity, D)

    # expert FFNs (E_l, C, D) @ (E_l, D, F)
    dt = x.dtype
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ex_in, p["w_gate"].astype(dt)))
    u = jnp.einsum("ecd,edf->ecf", ex_in, p["w_up"].astype(dt))
    ex_out = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(dt))

    # combine: gather back and weight by gate
    flat_out = ex_out.reshape(n_local_experts * capacity, D)
    flat_out = jnp.concatenate([flat_out, jnp.zeros((1, D), dt)], axis=0)
    contrib = flat_out[slot] * (gate_w.reshape(-1, 1).astype(dt))
    y = jnp.zeros((T, D), dt).at[tok_idx].add(contrib * mine[:, None].astype(dt))

    # Switch-style load-balance aux (computed on full routing, replicated)
    frac_tokens = jnp.mean(jax.nn.one_hot(gate_e[:, 0], E, dtype=jnp.float32), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * mean_probs)
    return y, aux


def moe_block(p, x, *, cfg: MoEConfig, mesh, dp_axes: tuple, tp_axis: str = "model"):
    """x: (B, S, D) sharded P(dp_axes, None, None). Returns (y, aux)."""
    B, S, D = x.shape
    tp = mesh.shape[tp_axis]
    dp = int(np.prod([mesh.shape[a] for a in dp_axes]))
    assert cfg.n_experts % tp == 0, (cfg.n_experts, tp)
    n_local = cfg.n_experts // tp
    t_local = (B // dp) * S
    capacity = int(np.ceil(t_local * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    capacity = max(capacity, 1)

    def body(p_l, x_l):
        bl, sl, _ = x_l.shape
        rank = jax.lax.axis_index(tp_axis)
        y, aux = _local_moe(
            {k: (v[0] if k != "router" else v) for k, v in p_l.items()},
            x_l.reshape(bl * sl, D),
            cfg=cfg,
            n_local_experts=n_local,
            expert_offset=rank * n_local,
            capacity=capacity,
        )
        y = jax.lax.psum(y, tp_axis)  # combine expert partials (TP-style)
        aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(bl, sl, D), aux

    # router replicated; experts sharded over tp. Keep a dummy leading dim
    # on expert weights so shard_map slices them per rank.
    p_in = {
        "router": p["router"],
        "w_gate": p["w_gate"].reshape(tp, n_local, D, cfg.d_ff_expert),
        "w_up": p["w_up"].reshape(tp, n_local, D, cfg.d_ff_expert),
        "w_down": p["w_down"].reshape(tp, n_local, cfg.d_ff_expert, D),
    }
    specs_in = {
        "router": P(),
        "w_gate": P(tp_axis),
        "w_up": P(tp_axis),
        "w_down": P(tp_axis),
    }
    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(specs_in, P(dp_axes, None, None)),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False,
    )(p_in, x)
    return y, aux


def moe_block_dense_ref(p, x, *, cfg: MoEConfig):
    """Oracle: dense per-expert compute + exact top-k combine (no capacity
    drops). Used by tests to validate the dispatch path numerically."""
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_e = jax.lax.top_k(probs, cfg.top_k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    dt = x.dtype
    g = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["w_gate"].astype(dt)))
    u = jnp.einsum("td,edf->tef", xf, p["w_up"].astype(dt))
    all_out = jnp.einsum("tef,efd->ted", g * u, p["w_down"].astype(dt))
    combine = jnp.zeros((T, cfg.n_experts), dt)
    for k in range(cfg.top_k):
        combine = combine.at[jnp.arange(T), gate_e[:, k]].add(gate_w[:, k].astype(dt))
    y = jnp.einsum("te,ted->td", combine, all_out)
    return y.reshape(B, S, D)
