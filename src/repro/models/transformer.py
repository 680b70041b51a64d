"""Decoder-only transformer LM covering the assigned dense + MoE configs
(StableLM-2-1.6B, CodeQwen1.5-7B, Qwen1.5-32B, Phi-3.5-MoE, Granite-MoE).

* layers are scanned (compact HLO at any depth; remat-friendly);
* GQA with optional QKV bias (Qwen) and partial rotary (StableLM);
* MoE blocks via models/moe.py (expert-parallel over the TP axis);
* Megatron-style tensor parallelism expressed as parameter PartitionSpecs
  (param_pspecs) + logical activation constraints;
* three entry points per config: train_step loss fwd, prefill, decode_step.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import (
    apply_norm,
    attention_block,
    init_attention,
    init_mlp,
    init_norm,
    mlp_block,
)
from repro.models.moe import MoEConfig, init_moe, moe_block, moe_block_dense_ref


@dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    d_head: int | None = None
    qkv_bias: bool = False
    norm: str = "rmsnorm"  # or "layernorm"
    rotary_pct: float = 1.0
    tie_embeddings: bool = False
    moe: MoEConfig | None = None
    remat: bool = True
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        """Megatron-style vocab padding: embedding/lm-head tensors round the
        vocab up to a multiple of 256 so the vocab dim shards over any TP
        degree (e.g. Granite's 49155 would otherwise replicate the logits).
        Logical vocab stays cfg.vocab; pad logits are masked in the loss."""
        return -(-self.vocab // 256) * 256

    def param_count(self) -> int:
        D, H, Kv, Dh, F, V, L = (
            self.d_model, self.n_heads, self.n_kv, self.head_dim,
            self.d_ff, self.vocab, self.n_layers,
        )
        attn = D * H * Dh + 2 * D * Kv * Dh + H * Dh * D
        if self.qkv_bias:
            attn += H * Dh + 2 * Kv * Dh
        if self.moe is not None:
            E, Fe = self.moe.n_experts, self.moe.d_ff_expert
            ffn = D * E + E * (2 * D * Fe + Fe * D)
        else:
            ffn = 3 * D * F
        norms = 2 * D * (2 if self.norm == "layernorm" else 1)
        embed = V * D * (1 if self.tie_embeddings else 2)
        return L * (attn + ffn + norms) + embed + D

    def active_param_count(self) -> int:
        """For MoE: params touched per token (6*N_active*D flops rule)."""
        if self.moe is None:
            return self.param_count()
        D, L = self.d_model, self.n_layers
        E, Fe, k = self.moe.n_experts, self.moe.d_ff_expert, self.moe.top_k
        total = self.param_count()
        ffn_all = L * E * 3 * D * Fe
        ffn_active = L * k * 3 * D * Fe
        return total - ffn_all + ffn_active


def _layer_init(cfg: TransformerConfig, key):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": init_norm(cfg.d_model, cfg.norm),
        "ln2": init_norm(cfg.d_model, cfg.norm),
        "attn": init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim, cfg.qkv_bias
        ),
    }
    if cfg.moe is not None:
        p["moe"] = init_moe(k2, cfg.d_model, cfg.moe)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff)
    return p


def init_params(cfg: TransformerConfig, key):
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: _layer_init(cfg, k))(layer_keys)
    params = {
        "embed": jax.random.normal(ke, (cfg.vocab_padded, cfg.d_model), jnp.float32) * 0.02,
        "layers": layers,
        "final_norm": init_norm(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(kh, (cfg.d_model, cfg.vocab_padded), jnp.float32)
            / np.sqrt(cfg.d_model)
        )
    return params


# --------------------------------------------------------------------------
# sharding rules (Megatron TP over `model`; DP over pod+data)
# --------------------------------------------------------------------------
def param_pspecs(cfg: TransformerConfig, tp: int = 1, stacked: bool = True):
    """PartitionSpec pytree matching init_params. Head-dim projections are
    sharded over `model` when divisible, else replicated (GQA with few KV
    heads, or Qwen's 40 heads on TP=16 — see DESIGN.md)."""
    lead = (None,) if stacked else ()
    m = "model"

    def spec(*axes):
        return P(*(lead + axes))

    q_shard = m if (cfg.n_heads * cfg.head_dim) % tp == 0 else None
    kv_shard = m if (cfg.n_kv * cfg.head_dim) % tp == 0 else None
    ff_shard = m if cfg.d_ff % tp == 0 else None
    attn = {
        "wq": spec(None, q_shard),
        "wk": spec(None, kv_shard),
        "wv": spec(None, kv_shard),
        "wo": spec(q_shard, None),
    }
    if cfg.qkv_bias:
        attn["bq"] = spec(q_shard)
        attn["bk"] = spec(kv_shard)
        attn["bv"] = spec(kv_shard)
    norm_spec = {"scale": spec(None)}
    if cfg.norm == "layernorm":
        norm_spec["bias"] = spec(None)
    layer = {"ln1": dict(norm_spec), "ln2": dict(norm_spec), "attn": attn}
    if cfg.moe is not None:
        e_shard = m if cfg.moe.n_experts % tp == 0 else None
        layer["moe"] = {
            "router": spec(None, None),
            "w_gate": spec(e_shard, None, None),
            "w_up": spec(e_shard, None, None),
            "w_down": spec(e_shard, None, None),
        }
    else:
        layer["mlp"] = {
            "w_gate": spec(None, ff_shard),
            "w_up": spec(None, ff_shard),
            "w_down": spec(ff_shard, None),
        }
    out = {
        "embed": P(m if cfg.vocab_padded % tp == 0 else None, None),
        "layers": layer,
        "final_norm": {"scale": P(None)} if cfg.norm == "rmsnorm" else {"scale": P(None), "bias": P(None)},
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = P(None, m if cfg.vocab_padded % tp == 0 else None)
    return out


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------
def _constrain(x, mesh, spec):
    """Activation sharding constraint (no-op off-mesh). Without these,
    GSPMD propagates FSDP *weight* shardings (data-axis on feature dims)
    into the activations and replicates the batch — observed as 256-batch
    per-device buffers in the qwen32b dry-run."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _block(cfg: TransformerConfig, mesh, dp_axes):
    act_spec = P(tuple(dp_axes), None, None)

    def block(x, p_l, cache_l=None, position=0):
        h, new_cache = attention_block(
            p_l["attn"],
            apply_norm(x, p_l["ln1"], cfg.norm),
            n_heads=cfg.n_heads,
            n_kv=cfg.n_kv,
            d_head=cfg.head_dim,
            rotary_pct=cfg.rotary_pct,
            cache=cache_l,
            position=position,
        )
        x = _constrain(x + h, mesh, act_spec)
        z = apply_norm(x, p_l["ln2"], cfg.norm)
        if cfg.moe is not None:
            if mesh is not None:
                y, aux = moe_block(p_l["moe"], z, cfg=cfg.moe, mesh=mesh, dp_axes=dp_axes)
            else:
                y, aux = moe_block_dense_ref(p_l["moe"], z, cfg=cfg.moe), jnp.float32(0)
        else:
            y, aux = mlp_block(p_l["mlp"], z), jnp.float32(0)
        return _constrain(x + y, mesh, act_spec), new_cache, aux

    return block


def forward(cfg: TransformerConfig, params, tokens, mesh=None, dp_axes=("data",)):
    """tokens (B, S) -> logits (B, S, V). Scan over layers."""
    dt = jnp.dtype(cfg.dtype)
    x = _constrain(params["embed"].astype(dt)[tokens], mesh, P(tuple(dp_axes), None, None))
    block = _block(cfg, mesh, dp_axes)

    def body(carry, p_l):
        x, aux = carry
        y, _, a = block(x, p_l)
        return (y, aux + a), ()

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0)), params["layers"])
    x = apply_norm(x, params["final_norm"], cfg.norm)
    head = params.get("lm_head", params["embed"].T)
    logits = x @ head.astype(dt)
    tp_ok = mesh is not None and cfg.vocab_padded % mesh.shape.get("model", 1) == 0
    logits = _constrain(logits, mesh if tp_ok else None, P(tuple(dp_axes), None, "model"))
    return logits, aux / cfg.n_layers


def lm_loss(cfg: TransformerConfig, params, tokens, targets, mesh=None, dp_axes=("data",)):
    logits, aux = forward(cfg, params, tokens, mesh, dp_axes)
    logits = logits.astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = (jnp.arange(cfg.vocab_padded) >= cfg.vocab) * -1e30
        logits = logits + pad_mask[None, None, :]
    # vocab-sharding friendly CE: logsumexp reduces the sharded V axis with
    # partial reductions; the target logit comes from a one-hot contraction
    # (also a sharded-V reduction) instead of a gather across shards.
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, cfg.vocab_padded, dtype=logits.dtype)
    tgt = jnp.einsum("bsv,bsv->bs", logits, onehot)
    loss = (lse - tgt).mean()
    if cfg.moe is not None:
        loss = loss + 0.01 * aux
    return loss


def lm_grads_microbatched(cfg: TransformerConfig, params, tokens, targets,
                          n_micro: int, mesh=None, dp_axes=("data",),
                          param_pspecs=None, bf16_gather: bool = True):
    """Gradient accumulation: scan over n_micro microbatches, accumulating
    f32 grads sharded like the params. Bounds the remat residual stack to
    one microbatch (L x B_micro x S x D) — the production answer to the
    40-80 GiB stacks a full-batch backward would need (see dry-run log).

    bf16_gather (§Perf hillclimb): cast f32 master params to bf16 *at
    their FSDP-sharded layout* (sharding constraint pins the convert
    before the gather) so every FSDP all-gather moves half the bytes. The
    dry-run showed 5.8 GiB of f32 all-gathers per layer-loop body without
    this."""
    B = tokens.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    tk = tokens.reshape(n_micro, B // n_micro, -1)
    tg = targets.reshape(n_micro, B // n_micro, -1)

    def cast_sharded(p):
        if not (bf16_gather and mesh is not None and param_pspecs is not None):
            return p
        from jax.sharding import NamedSharding

        def leaf(x, s):
            if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype == jnp.float32:
                return jax.lax.with_sharding_constraint(
                    x.astype(jnp.bfloat16), NamedSharding(mesh, s)
                )
            return x

        flat_p, td = jax.tree_util.tree_flatten(p)
        flat_s = jax.tree_util.tree_flatten(
            param_pspecs, is_leaf=lambda x: isinstance(x, P)
        )[0]
        casted = [leaf(x, s) for x, s in zip(flat_p, flat_s)]
        # the barrier pins the convert *before* the FSDP all-gather —
        # without it XLA sinks the bf16 cast past the gather and moves f32
        casted = jax.lax.optimization_barrier(casted)
        return td.unflatten(casted)

    def loss_fn(p, t, y):
        return lm_loss(cfg, cast_sharded(p), t, y, mesh, dp_axes)

    def micro(carry, xs):
        g_acc, l_acc = carry
        t, y = xs
        l, g = jax.value_and_grad(loss_fn)(params, t, y)
        g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32) / n_micro, g_acc, g)
        return (g_acc, l_acc + l / n_micro), ()

    g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    (grads, loss), _ = jax.lax.scan(micro, (g0, jnp.float32(0)), (tk, tg))
    return loss, grads


def prefill(cfg: TransformerConfig, params, tokens, mesh=None, dp_axes=("data",)):
    """tokens (B, S) -> (last-position logits (B, V), stacked KV cache)."""
    dt = jnp.dtype(cfg.dtype)
    x = _constrain(params["embed"].astype(dt)[tokens], mesh, P(tuple(dp_axes), None, None))
    block = _block(cfg, mesh, dp_axes)

    def body(x, p_l):
        y, cache, _ = block(x, p_l)
        return y, cache

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, caches = jax.lax.scan(body_fn, x, params["layers"])
    x = apply_norm(x[:, -1:], params["final_norm"], cfg.norm)
    head = params.get("lm_head", params["embed"].T)
    return (x @ head.astype(dt))[:, 0], caches


def decode_step(cfg: TransformerConfig, params, token, caches, position, mesh=None, dp_axes=("data",)):
    """token (B, 1) + caches (L-stacked k/v (L,B,Smax,Hkv,Dh)) + position
    scalar -> (logits (B, V), updated caches)."""
    dt = jnp.dtype(cfg.dtype)
    x = _constrain(params["embed"].astype(dt)[token], mesh, P(tuple(dp_axes), None, None))
    block = _block(cfg, mesh, dp_axes)

    def body(x, scanned):
        p_l, cache_l = scanned
        y, new_cache, _ = block(x, p_l, cache_l=cache_l, position=position)
        return y, new_cache

    x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
    x = apply_norm(x, params["final_norm"], cfg.norm)
    head = params.get("lm_head", params["embed"].T)
    return (x @ head.astype(dt))[:, 0], new_caches


def init_cache(cfg: TransformerConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               quantized: bool = False):
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv, cfg.head_dim)
    if quantized:
        sshape = shape[:-1]
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_pspecs(cfg: TransformerConfig, tp: int, dp_axes, seq_len: int | None = None,
                 quantized: bool = False):
    """KV-cache sharding: heads over model when divisible; otherwise shard
    the sequence dim over model (softmax over a sharded axis is handled by
    GSPMD partial reductions) — keeps e.g. Qwen-32B's 40-head cache from
    being replicated 16x."""
    if cfg.n_kv % tp == 0:
        s = P(None, dp_axes, None, "model", None)
    elif seq_len is not None and seq_len % tp == 0:
        s = P(None, dp_axes, "model", None, None)
    else:
        s = P(None, dp_axes, None, None, None)
    out = {"k": s, "v": s}
    if quantized:
        out["k_scale"] = P(*tuple(s)[:-1])
        out["v_scale"] = P(*tuple(s)[:-1])
    return out
