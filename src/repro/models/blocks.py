"""Transformer building blocks + the sharding context threaded through models.

``ShardCtx`` is how model code stays mesh-agnostic: layers call
``ctx.shard_act`` / ``ctx.shard_heads`` at the tensor boundaries where a
sharding constraint matters, and the context decides (from the mesh and
divisibility) what constraint, if any, to apply.  On a mesh-less CPU run
everything is the identity.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .attention import attention
from .common import apply_rope, dense_init
from .config import ModelConfig


def kv_cache_spec(shape: tuple[int, ...], dp: int, m: int,
                  batch_axes: Any, model_axis: str) -> P:
    """Sharding of a stacked attention cache, (L, B, S, Hkv, hd) or heads
    flat, (L, B, S, Hkv * hd): batch over the data axes (``dp`` wide) when
    it divides; heads over the model axis (``m`` wide) when they divide;
    otherwise the *sequence* takes the model axis (flash-decode partials
    combine via psum), or the data axes with batch also unshardable
    (long_500k).  A flat row never splits: the decode attention contracts
    over all of it, and XLA would gather each layer's cache to do so."""
    B, S = shape[1], shape[2]
    heads = len(shape) == 5 and m > 1 and shape[3] % m == 0
    b_ax = batch_axes if (B % dp == 0 and B >= dp) else None
    if not heads and m > 1 and S % m == 0:
        s_ax = model_axis
    elif b_ax is None and S % dp == 0:
        s_ax = batch_axes
    else:
        s_ax = None
    return P(None, b_ax, s_ax, model_axis if heads else None,
             *([None] * (len(shape) - 4)))


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh context for activation sharding + manual-collective blocks."""

    mesh: Optional[Mesh] = None
    batch_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    seq_parallel: bool = False     # Megatron-SP: layer-boundary activations
    #                                (and remat residuals) shard their seq
    #                                dim over the model axis

    def _constrain(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def _model_size(self) -> int:
        return self.mesh.shape[self.model_axis] if self.mesh is not None else 1

    def shard_act(self, x: jax.Array) -> jax.Array:
        """(B, S, D) activations: batch over the data axes; with
        seq_parallel the sequence additionally shards over the model axis
        (residuals and remat-saved layer inputs then cost 1/model_size of
        HBM — required to fit the 123B/141B archs' 88/56-layer stacks)."""
        m = self._model_size()
        if (self.seq_parallel and x.ndim >= 3 and m > 1
                and x.shape[1] % m == 0 and x.shape[1] > 1):
            spec = P(self.batch_axes, self.model_axis,
                     *([None] * (x.ndim - 2)))
            return self._constrain(x, spec)
        spec = P(self.batch_axes, *([None] * (x.ndim - 1)))
        return self._constrain(x, spec)

    def heads_shardable(self, h: int) -> bool:
        m = self._model_size()
        return m > 1 and h % m == 0

    def seq_parallel_attn(self, h: int, s: int) -> bool:
        """Sequence-parallel fallback: when heads don't divide the model
        axis (smollm: 15H, gemma3: 4H), shard the *query sequence* over it
        instead — otherwise attention compute replicates model_size-fold
        (measured: 16x redundant FLOPs on the 16x16 mesh)."""
        m = self._model_size()
        return (not self.heads_shardable(h)) and m > 1 and s > 1 and s % m == 0

    def shard_heads(self, x: jax.Array, role: str = "q") -> jax.Array:
        """(B, S, H, hd).  Heads over model when divisible; else the query
        sequence shards over model (role='q') and K/V stay replicated
        across it (role='kv')."""
        if self.mesh is None:
            return x
        h, s = x.shape[2], x.shape[1]
        if self.heads_shardable(h):
            return self._constrain(
                x, P(self.batch_axes, None, self.model_axis, None))
        if role == "q" and self.seq_parallel_attn(h, s):
            return self._constrain(
                x, P(self.batch_axes, self.model_axis, None, None))
        return self._constrain(x, P(self.batch_axes, None, None, None))

    def shard_kv_cache(self, x: jax.Array) -> jax.Array:
        """A stacked attention cache, sharded by ``kv_cache_spec``."""
        if self.mesh is None:
            return x
        dp = 1
        for a in self.batch_axes:
            dp *= self.mesh.shape[a]
        return self._constrain(x, kv_cache_spec(
            x.shape, dp, self._model_size(), self.batch_axes,
            self.model_axis))


# ---------------------------------------------------------------------------
# Parameter builders
# ---------------------------------------------------------------------------


def init_attn_params(key: jax.Array, cfg: ModelConfig) -> dict:
    D, Q, KV = cfg.d_model, cfg.q_dim, cfg.kv_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (D, Q), D),
        "wk": dense_init(k2, (D, KV), D),
        "wv": dense_init(k3, (D, KV), D),
        "wo": dense_init(k4, (Q, D), Q),
    }


def init_mlp_params(key: jax.Array, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (D, F), D),
        "w_up": dense_init(k2, (D, F), D),
        "w_down": dense_init(k3, (F, D), F),
    }


def init_moe_params(key: jax.Array, cfg: ModelConfig) -> dict:
    D, E, F = cfg.d_model, cfg.moe.n_experts, cfg.moe.d_ff_expert
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": dense_init(k1, (D, E), D, dtype=jnp.float32),
        "w_gate": dense_init(k2, (E, D, F), D),
        "w_up": dense_init(k3, (E, D, F), D),
        "w_down": dense_init(k4, (E, F, D), F),
    }


def init_dense_layer(key: jax.Array, cfg: ModelConfig) -> dict:
    ka, km = jax.random.split(key)
    D = cfg.d_model
    return {
        "attn": init_attn_params(ka, cfg),
        "mlp": init_mlp_params(km, cfg),
        "ln1": jnp.zeros((D,), jnp.float32),
        "ln2": jnp.zeros((D,), jnp.float32),
    }


def init_moe_layer(key: jax.Array, cfg: ModelConfig) -> dict:
    ka, km = jax.random.split(key)
    D = cfg.d_model
    return {
        "attn": init_attn_params(ka, cfg),
        "moe": init_moe_params(km, cfg),
        "ln1": jnp.zeros((D,), jnp.float32),
        "ln2": jnp.zeros((D,), jnp.float32),
    }


def init_mamba_layer(key: jax.Array, cfg: ModelConfig) -> dict:
    s = cfg.ssm
    D = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    H = cfg.ssm_heads
    return {
        "in_proj": dense_init(k1, (D, cfg.in_proj_dim), D),
        "conv_w": dense_init(k2, (s.conv_width, cfg.conv_dim), s.conv_width),
        "conv_b": jnp.zeros((cfg.conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(k3, (H,), jnp.float32,
                                       jnp.log(1e-3), jnp.log(1e-1))))),
        "norm_w": jnp.ones((cfg.d_inner,), jnp.float32),
        "out_proj": dense_init(jax.random.fold_in(k1, 7), (cfg.d_inner, D),
                               cfg.d_inner),
    }


def stack_layers(key: jax.Array, cfg: ModelConfig, n: int, kind: str) -> dict:
    """Stacked per-layer params (leading L axis) for lax.scan."""
    init = {"attn": init_dense_layer, "moe": init_moe_layer,
            "mamba": init_mamba_layer}[kind]
    # vmap over the layer keys draws the same values as a per-layer loop,
    # as one program instead of n unrolled copies (a 32-layer init then
    # compiles in seconds, not a minute)
    return jax.vmap(lambda k: init(k, cfg))(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def self_attention_block(
    x: jax.Array, p: dict, cfg: ModelConfig, ctx: ShardCtx, *,
    q_pos: jax.Array, k_pos: jax.Array,
    k_cached: jax.Array | None = None, v_cached: jax.Array | None = None,
    causal: bool = True, window: int | jax.Array = 0,
    q_chunk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """QKV projections + RoPE (unless ``cfg.rope`` is off) + attention.
    Returns (out, k_new, v_new) where k_new/v_new are this step's
    keys/values (pre-cache, post-RoPE).  ``q_chunk`` as in ``attention``;
    None leaves the choice to the sharding and the score size."""
    B, S, D = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(B, S, cfg.n_heads, cfg.hd)
    k = jnp.einsum("bsd,dk->bsk", x, p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    v = jnp.einsum("bsd,dk->bsk", x, p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    if cfg.rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)   # new keys carry current positions
    q = ctx.shard_heads(q, role="q")
    # GQA sharding repair: when Hq shards over the model axis but Hkv does
    # not (kv=8 on a 16-wide axis), the (Hkv, G) grouping reshape breaks
    # the head sharding of the score tensor and GSPMD falls back to full
    # rematerialization (measured: ~1 TiB/dev score all-gathers).
    # Materializing the KV head repeat costs ~MBs and keeps every
    # attention tensor cleanly model-sharded.  (The Pallas kernel does GQA
    # without the repeat on real TPU — this is the GSPMD-graph trade.)
    if (ctx.heads_shardable(cfg.n_heads)
            and not ctx.heads_shardable(cfg.n_kv_heads)
            and cfg.n_heads != cfg.n_kv_heads):
        rep = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        k = ctx.shard_heads(k, role="q")
        v = ctx.shard_heads(v, role="q")
    else:
        k = ctx.shard_heads(k, role="kv")
        v = ctx.shard_heads(v, role="kv")
    if k_cached is not None:
        k_all, v_all = k_cached, v_cached
    else:
        k_all, v_all = k, v
    # query chunking is a memory fallback for *unsharded* attention only:
    # with heads (or the query sequence) sharded over the model axis the
    # score workspace is already bounded, and the chunk scan's extra
    # sharding transitions trigger involuntary full rematerialization in
    # GSPMD (measured: 4.2 TiB/dev of score all-gathers on
    # mistral-large train_4k)
    if q_chunk is None and (ctx.heads_shardable(cfg.n_heads)
                            or ctx.seq_parallel_attn(cfg.n_heads, S)):
        q_chunk = 0
    out = attention(q, k_all, v_all, q_pos=q_pos, k_pos=k_pos,
                    causal=causal, window=window, q_chunk=q_chunk,
                    scale=cfg.attention_multiplier)
    out = out.reshape(B, S, cfg.q_dim)
    return jnp.einsum("bsq,qd->bsd", out, p["wo"]), k, v

