"""EGNN — E(n)-equivariant graph network (Satorras et al., arXiv:2102.09844).

Message passing is expressed as gather (edge endpoints) -> edge MLP ->
`jax.ops.segment_sum` scatter — the JAX-native sparse-aggregation pattern
(no SpMM formats needed). Distribution is *edge-parallel*: edge arrays are
sharded across the whole mesh, node states replicated; each shard computes
local partial aggregations and a psum over the edge axes combines them
(see DESIGN.md §5). Padding edges carry src=dst=0 and mask 0.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense, init_dense


@dataclass(frozen=True)
class EGNNConfig:
    name: str = "egnn"
    n_layers: int = 4
    d_hidden: int = 64
    d_feat: int = 16


def _mlp2_init(key, d_in, d_h, d_out):
    k1, k2 = jax.random.split(key)
    return {"l1": init_dense(k1, d_in, d_h), "l2": init_dense(k2, d_h, d_out)}


def _mlp2(p, x):
    return dense(p["l2"], jax.nn.silu(dense(p["l1"], x)))


def init_params(cfg: EGNNConfig, key):
    keys = jax.random.split(key, cfg.n_layers * 3 + 2)
    h = cfg.d_hidden
    layers = []
    for i in range(cfg.n_layers):
        layers.append(
            {
                "edge_mlp": _mlp2_init(keys[3 * i], 2 * h + 1, h, h),
                "coord_mlp": _mlp2_init(keys[3 * i + 1], h, h, 1),
                "node_mlp": _mlp2_init(keys[3 * i + 2], 2 * h, h, h),
            }
        )
    # stack layers for scan
    layers = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "embed": init_dense(keys[-2], cfg.d_feat, h),
        "layers": layers,
        "readout": init_dense(keys[-1], h, 1),
    }


def _egnn_layer(p_l, h, x, src, dst, edge_mask, n_nodes):
    """One EGNN layer on (possibly local) edge arrays; returns partial
    aggregations that must be summed across edge shards before the update."""
    hi, hj = h[src], h[dst]
    dx = x[src] - x[dst]
    d2 = jnp.sum(dx * dx, axis=-1, keepdims=True)
    m = _mlp2(p_l["edge_mlp"], jnp.concatenate([hi, hj, d2], axis=-1))
    m = m * edge_mask[:, None].astype(m.dtype)
    w = _mlp2(p_l["coord_mlp"], m)
    coord_agg = jax.ops.segment_sum(dx * w, src, num_segments=n_nodes)
    msg_agg = jax.ops.segment_sum(m, src, num_segments=n_nodes)
    deg = jax.ops.segment_sum(edge_mask.astype(h.dtype), src, num_segments=n_nodes)
    return msg_agg, coord_agg, deg


def forward(cfg: EGNNConfig, params, feats, coords, src, dst, edge_mask, mesh=None, edge_axes=None):
    """feats (N, F), coords (N, 3), src/dst (E,), edge_mask (E,).
    Returns (node embeddings (N, Dh), coords (N, 3), graph scalar)."""
    n_nodes = feats.shape[0]
    h = dense(params["embed"], feats)

    def apply_layer(carry, p_l):
        h, x = carry
        if mesh is not None:
            def body(p_loc, h_loc, x_loc, s_loc, d_loc, m_loc):
                out = _egnn_layer(p_loc, h_loc, x_loc, s_loc, d_loc, m_loc, n_nodes)
                return tuple(jax.lax.psum(o, edge_axes) for o in out)

            e_spec = P(edge_axes)
            msg_agg, coord_agg, deg = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), P(), P(), e_spec, e_spec, e_spec),
                out_specs=(P(), P(), P()),
                check_vma=False,
            )(p_l, h, x, src, dst, edge_mask)
        else:
            msg_agg, coord_agg, deg = _egnn_layer(p_l, h, x, src, dst, edge_mask, n_nodes)
        denom = jnp.maximum(deg, 1.0)[:, None]
        x = x + coord_agg / denom  # E(n)-equivariant coordinate update
        h = h + _mlp2(p_l["node_mlp"], jnp.concatenate([h, msg_agg / denom], axis=-1))
        return (h, x), ()

    # unroll: few layers; keeps cost_analysis exact (no while-loop body)
    (h, coords), _ = jax.lax.scan(apply_layer, (h, coords), params["layers"], unroll=True)
    energy = dense(params["readout"], h).sum()
    return h, coords, energy


def loss_fn(cfg: EGNNConfig, params, batch, mesh=None, edge_axes=None):
    """Node-level regression (energy-style): MSE of per-node readout."""
    h, _, _ = forward(
        cfg, params, batch["feats"], batch["coords"], batch["src"], batch["dst"],
        batch["edge_mask"], mesh=mesh, edge_axes=edge_axes,
    )
    pred = dense(params["readout"], h)[:, 0]
    mask = batch["node_mask"].astype(pred.dtype)
    err = (pred - batch["targets"]) ** 2 * mask
    return err.sum() / jnp.maximum(mask.sum(), 1.0)


def batched_forward(cfg: EGNNConfig, params, batch):
    """vmap over a batch of small graphs (the `molecule` shape)."""
    def one(feats, coords, src, dst, edge_mask):
        return forward(cfg, params, feats, coords, src, dst, edge_mask)

    return jax.vmap(one)(
        batch["feats"], batch["coords"], batch["src"], batch["dst"], batch["edge_mask"]
    )


def batched_loss(cfg: EGNNConfig, params, batch):
    h, _, _ = batched_forward(cfg, params, batch)
    pred = dense(params["readout"], h)[..., 0]
    mask = batch["node_mask"].astype(pred.dtype)
    err = (pred - batch["targets"]) ** 2 * mask
    return err.sum() / jnp.maximum(mask.sum(), 1.0)
