"""Encoder-decoder backbone (seamless-m4t style, audio frontend stubbed).

The speech encoder consumes precomputed frame embeddings (the assignment
stubs the modality frontend); the text decoder attends causally to itself
and bidirectionally to the encoder output.  Both stacks scan over stacked
layer params.  At serve time the encoder output's K/V projections are
precomputed once per request ("bulk" staging of the cross-attention
operands — see DESIGN.md section 2) and decode steps only touch the self
cache.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import ffn as ffn_lib
from .attention import (attention, cache_positions_full, cache_update_full)
from .blocks import ShardCtx, init_attn_params, init_mlp_params
from .common import apply_rope, cross_entropy_loss, dense_init, embed_init, rms_norm
from .config import ModelConfig
from .lm import _remat


def init_encdec(cfg: ModelConfig, key: jax.Array) -> dict:
    cfg.validate()
    keys = jax.random.split(key, 6)
    D, V = cfg.d_model, cfg.vocab

    def enc_layer(k):
        ka, km = jax.random.split(k)
        return {"attn": init_attn_params(ka, cfg),
                "mlp": init_mlp_params(km, cfg),
                "ln1": jnp.zeros((D,), jnp.float32),
                "ln2": jnp.zeros((D,), jnp.float32)}

    def dec_layer(k):
        ka, kc, km = jax.random.split(k, 3)
        return {"attn": init_attn_params(ka, cfg),
                "cross": init_attn_params(kc, cfg),
                "mlp": init_mlp_params(km, cfg),
                "ln1": jnp.zeros((D,), jnp.float32),
                "ln2": jnp.zeros((D,), jnp.float32),
                "ln3": jnp.zeros((D,), jnp.float32)}

    enc = [enc_layer(k) for k in jax.random.split(keys[0], cfg.enc_layers)]
    dec = [dec_layer(k) for k in jax.random.split(keys[1], cfg.n_layers)]
    return {
        "embed": embed_init(keys[2], (V, D)),
        "enc_layers": jax.tree.map(lambda *xs: jnp.stack(xs), *enc),
        "dec_layers": jax.tree.map(lambda *xs: jnp.stack(xs), *dec),
        "enc_norm": jnp.zeros((D,), jnp.float32),
        "final_norm": jnp.zeros((D,), jnp.float32),
        "lm_head": dense_init(keys[3], (D, V), D),
        "frame_proj": dense_init(keys[4], (D, D), D),  # frontend stub adapter
    }


def _proj_qkv(h, p, cfg, ctx, positions, rope=True):
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dq->bsq", h, p["wq"]).reshape(B, S, cfg.n_heads, cfg.hd)
    k = jnp.einsum("bsd,dk->bsk", h, p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    v = jnp.einsum("bsd,dk->bsk", h, p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return ctx.shard_heads(q), ctx.shard_heads(k), ctx.shard_heads(v)


def encode(params: dict, cfg: ModelConfig, frames: jax.Array, ctx: ShardCtx
           ) -> jax.Array:
    """frames: (B, S_enc, D) stub embeddings -> encoder states."""
    x = ctx.shard_act(jnp.einsum("bsd,de->bse",
                                 frames.astype(jnp.bfloat16),
                                 params["frame_proj"]))
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    def body(h, lp):
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = _proj_qkv(hn, lp["attn"], cfg, ctx, positions)
        out = attention(q, k, v, q_pos=positions, k_pos=positions,
                        causal=False)
        B = h.shape[0]
        h = ctx.shard_act(
            h + jnp.einsum("bsq,qd->bsd", out.reshape(B, S, cfg.q_dim),
                           lp["attn"]["wo"]))
        h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
        h = ctx.shard_act(h + ffn_lib.swiglu(h2, lp["mlp"]["w_gate"],
                                             lp["mlp"]["w_up"],
                                             lp["mlp"]["w_down"]))
        return h, None

    body = _remat(body, cfg.remat)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _decoder_stack(params, cfg, x, enc_out, ctx):
    S = x.shape[1]
    S_enc = enc_out.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    enc_positions = jnp.arange(S_enc, dtype=jnp.int32)

    def body(h, lp):
        B = h.shape[0]
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = _proj_qkv(hn, lp["attn"], cfg, ctx, positions)
        out = attention(q, k, v, q_pos=positions, k_pos=positions,
                        causal=True)
        h = ctx.shard_act(
            h + jnp.einsum("bsq,qd->bsd", out.reshape(B, S, cfg.q_dim),
                           lp["attn"]["wo"]))
        # cross attention (no rope; encoder memory is position-agnostic here)
        hc = rms_norm(h, lp["ln2"], cfg.norm_eps)
        qc = jnp.einsum("bsd,dq->bsq", hc, lp["cross"]["wq"]).reshape(
            B, S, cfg.n_heads, cfg.hd)
        kc = jnp.einsum("bsd,dk->bsk", enc_out, lp["cross"]["wk"]).reshape(
            B, S_enc, cfg.n_kv_heads, cfg.hd)
        vc = jnp.einsum("bsd,dk->bsk", enc_out, lp["cross"]["wv"]).reshape(
            B, S_enc, cfg.n_kv_heads, cfg.hd)
        out = attention(ctx.shard_heads(qc), ctx.shard_heads(kc),
                        ctx.shard_heads(vc), q_pos=positions,
                        k_pos=enc_positions, causal=False)
        h = ctx.shard_act(
            h + jnp.einsum("bsq,qd->bsd", out.reshape(B, S, cfg.q_dim),
                           lp["cross"]["wo"]))
        h2 = rms_norm(h, lp["ln3"], cfg.norm_eps)
        h = ctx.shard_act(h + ffn_lib.swiglu(h2, lp["mlp"]["w_gate"],
                                             lp["mlp"]["w_up"],
                                             lp["mlp"]["w_down"]))
        return h, None

    body = _remat(body, cfg.remat)
    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    return x


def forward_encdec(params: dict, cfg: ModelConfig, frames: jax.Array,
                   dec_tokens: jax.Array, ctx: ShardCtx) -> jax.Array:
    enc_out = encode(params, cfg, frames, ctx)
    x = ctx.shard_act(params["embed"][dec_tokens])
    x = _decoder_stack(params, cfg, x, enc_out, ctx)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


def encdec_loss(params: dict, cfg: ModelConfig, batch: dict, ctx: ShardCtx
                ) -> tuple[jax.Array, dict]:
    logits = forward_encdec(params, cfg, batch["frames"], batch["tokens"], ctx)
    ce = cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"))
    return ce, {"ce": ce}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def cross_kv(params: dict, cfg: ModelConfig, enc_out: jax.Array, ctx: ShardCtx
             ) -> tuple[jax.Array, jax.Array]:
    """Precompute every decoder layer's cross K/V from encoder states —
    bulk-staged once per request.  Returns (L, B, S_enc, Hkv, hd) x 2."""
    B, S_enc, _ = enc_out.shape
    kc = jnp.einsum("bsd,ldk->lbsk", enc_out, params["dec_layers"]["cross"]["wk"])
    vc = jnp.einsum("bsd,ldk->lbsk", enc_out, params["dec_layers"]["cross"]["wv"])
    shape = (cfg.n_layers, B, S_enc, cfg.n_kv_heads, cfg.hd)
    kc = kc.reshape(shape).astype(jnp.bfloat16)
    vc = vc.reshape(shape).astype(jnp.bfloat16)
    if ctx.mesh is not None:
        kc, vc = ctx.shard_kv_cache(kc), ctx.shard_kv_cache(vc)
    return kc, vc


def init_encdec_cache(cfg: ModelConfig, batch: int, max_len: int,
                      enc_len: int, ctx: Optional[ShardCtx] = None) -> dict:
    ctx = ctx or ShardCtx()
    L = cfg.n_layers
    kv = jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    ckv = jnp.zeros((L, batch, enc_len, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    return {
        "pos": jnp.zeros((), jnp.int32),
        "k": ctx.shard_kv_cache(kv),
        "v": ctx.shard_kv_cache(kv),
        "cross_k": ctx.shard_kv_cache(ckv),
        "cross_v": ctx.shard_kv_cache(ckv),
    }


def encdec_decode_step(params: dict, cfg: ModelConfig, cache: dict,
                       tokens: jax.Array, ctx: ShardCtx
                       ) -> tuple[jax.Array, dict]:
    """One decoder token against (self cache, precomputed cross K/V)."""
    pos = cache["pos"]
    x = ctx.shard_act(params["embed"][tokens])
    B = x.shape[0]
    q_pos = jnp.broadcast_to(pos, (1,)).astype(jnp.int32)
    s_self = cache["k"].shape[2]
    s_enc = cache["cross_k"].shape[2]
    enc_positions = jnp.arange(s_enc, dtype=jnp.int32)

    def body(h, xs):
        lp, k_l, v_l, ck_l, cv_l = xs
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dq->bsq", hn, lp["attn"]["wq"]).reshape(
            B, 1, cfg.n_heads, cfg.hd)
        k = jnp.einsum("bsd,dk->bsk", hn, lp["attn"]["wk"]).reshape(
            B, 1, cfg.n_kv_heads, cfg.hd)
        v = jnp.einsum("bsd,dk->bsk", hn, lp["attn"]["wv"]).reshape(
            B, 1, cfg.n_kv_heads, cfg.hd)
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
        k_l, v_l = cache_update_full(k_l, v_l, k, v, pos)
        k_pos = cache_positions_full(s_self, pos)
        out = attention(q, k_l, v_l, q_pos=q_pos, k_pos=k_pos, causal=True)
        h = h + jnp.einsum("bsq,qd->bsd", out.reshape(B, 1, cfg.q_dim),
                           lp["attn"]["wo"])
        hc = rms_norm(h, lp["ln2"], cfg.norm_eps)
        qc = jnp.einsum("bsd,dq->bsq", hc, lp["cross"]["wq"]).reshape(
            B, 1, cfg.n_heads, cfg.hd)
        out = attention(qc, ck_l, cv_l, q_pos=q_pos, k_pos=enc_positions,
                        causal=False)
        h = h + jnp.einsum("bsq,qd->bsd", out.reshape(B, 1, cfg.q_dim),
                           lp["cross"]["wo"])
        h2 = rms_norm(h, lp["ln3"], cfg.norm_eps)
        h = h + ffn_lib.swiglu(h2, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                               lp["mlp"]["w_down"])
        return h, (k_l, v_l)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["dec_layers"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = k_new, v_new
    new_cache["pos"] = pos + 1
    return logits, new_cache
