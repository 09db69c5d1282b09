"""Decoder-only language models: dense / MoE / SSM / hybrid / VLM.

Every homogeneous layer stack runs as ``jax.lax.scan`` over stacked layer
parameters, so compile time (and the dry-run matrix) is O(1) in depth.
Heterogeneity is handled without breaking the scan:

* per-layer attention windows (gemma3's 5:1 local:global) ride through the
  scan as an ``int32`` xs array feeding the mask,
* zamba2's shared attention block (one set of weights applied every
  ``attn_every`` layers) splits the Mamba stack into segments, scanning
  each segment and applying the shared block between segments,
* decode caches enter the scan as read-only xs; each layer's new K/V
  leaves as a small ys and is written into the stacked cache once after
  the scan (in place where the caller donates the cache), keeping
  serve_step compile-time flat.  Attention caches hold each position's
  heads flat, (L, B, S, Hkv * hd): the TPU lays a (.., S, Hkv, hd) cache
  out with S minor-most whenever hd is not a multiple of 128, so that one
  position's write touches every tile, and reads it per head more slowly
  than the flat rows even where hd is 128 (PERF.md, PR 14).

Remat policy (cfg.remat): 'full' checkpoints each layer body (only layer
boundaries persist for backward), 'dots' saves matmul outputs, 'none'
stores everything.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import ssm as ssm_lib
from .attention import (attention_decode, cache_positions_full,
                        cache_positions_ring)
from .blocks import (ShardCtx, dense_layer_apply, init_dense_layer,
                     init_mamba_layer, init_moe_layer, moe_layer_apply,
                     stack_layers)
from .common import (apply_rope, cross_entropy_loss, dense_init, embed_init,
                     rms_norm)
from .config import ModelConfig


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_lm(cfg: ModelConfig, key: jax.Array) -> dict:
    cfg.validate()
    keys = jax.random.split(key, 8)
    D, V = cfg.d_model, cfg.vocab
    params: dict[str, Any] = {"embed": embed_init(keys[0], (V, D))}
    kind = {"dense": "attn", "vlm": "attn", "moe": "moe",
            "ssm": "mamba", "hybrid": "mamba"}[cfg.family]
    params["layers"] = stack_layers(keys[1], cfg, cfg.n_layers, kind)
    if cfg.family == "hybrid":
        shared = init_dense_layer(keys[2], cfg)
        params["shared_attn"] = shared
    if cfg.family in ("ssm", "hybrid"):
        # mamba layers need a pre-norm scale
        params["layers"]["ln"] = jnp.zeros((cfg.n_layers, D), jnp.float32)
    if cfg.frontend:
        params["projector"] = {
            "w1": dense_init(keys[3], (D, D), D),
            "w2": dense_init(keys[4], (D, D), D),
        }
    params["final_norm"] = jnp.zeros((D,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[5], (D, V), D)
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _embed_inputs(params: dict, cfg: ModelConfig, tokens: jax.Array,
                  ctx: ShardCtx, extra_embeds: Optional[jax.Array]) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.frontend:
        assert extra_embeds is not None, "frontend arch needs stub embeddings"
        fe = extra_embeds.astype(x.dtype)
        h = jnp.einsum("bnd,de->bne", fe, params["projector"]["w1"])
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        fe = jnp.einsum("bnd,de->bne", h, params["projector"]["w2"])
        x = jnp.concatenate([fe, x], axis=1)
    return ctx.shard_act(x)


def _logits(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head)


def _mamba_layer_apply(x, lp, cfg, ctx):
    h = rms_norm(x, lp["ln"], cfg.norm_eps)
    y = ssm_lib.mamba_block_train(h, lp, cfg, impl=ctx.impl,
                                  shard_heads=ctx.shard_heads)
    return ctx.shard_act(x + y)


def _scan_stack(x, layers, cfg, ctx, positions, windows, body_kind,
                n_layers=None):
    """Scan a homogeneous stack.  Returns (x, lb_sum, z_sum)."""

    def dense_body(carry, xs):
        h, lb, z = carry
        lp, w = xs
        h = dense_layer_apply(h, lp, cfg, ctx, positions=positions, window=w)
        return (h, lb, z), None

    def moe_body(carry, xs):
        h, lb, z = carry
        lp, w = xs
        h, lbi, zi = moe_layer_apply(h, lp, cfg, ctx, positions=positions,
                                     window=w)
        return (h, lb + lbi, z + zi), None

    def mamba_body(carry, xs):
        h, lb, z = carry
        lp, w = xs
        h = _mamba_layer_apply(h, lp, cfg, ctx)
        return (h, lb, z), None

    body = {"attn": dense_body, "moe": moe_body, "mamba": mamba_body}[body_kind]
    body = _remat(body, cfg.remat)
    zero = jnp.zeros((), jnp.float32)
    (x, lb, z), _ = jax.lax.scan(body, (x, zero, zero), (layers, windows))
    return x, lb, z


def forward_lm(params: dict, cfg: ModelConfig, tokens: jax.Array,
               ctx: ShardCtx, *, extra_embeds: Optional[jax.Array] = None
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (logits, lb_loss, z_loss)."""
    x = _embed_inputs(params, cfg, tokens, ctx, extra_embeds)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    windows = jnp.asarray(cfg.layer_windows(), jnp.int32)

    if cfg.family in ("dense", "vlm"):
        x, lb, z = _scan_stack(x, params["layers"], cfg, ctx, positions,
                               windows, "attn")
    elif cfg.family == "moe":
        x, lb, z = _scan_stack(x, params["layers"], cfg, ctx, positions,
                               windows, "moe")
    elif cfg.family == "ssm":
        x, lb, z = _scan_stack(x, params["layers"], cfg, ctx, positions,
                               windows, "mamba")
    elif cfg.family == "hybrid":
        x, lb, z = _hybrid_forward(params, cfg, x, ctx, positions, windows)
    else:
        raise ValueError(cfg.family)
    return _logits(params, cfg, x), lb, z


def _segment_bounds(n_layers: int, every: int) -> list[tuple[int, int]]:
    bounds, start = [], 0
    while start < n_layers:
        bounds.append((start, min(start + every, n_layers)))
        start += every
    return bounds


def _slice_layers(layers: dict, lo: int, hi: int) -> dict:
    return jax.tree.map(lambda a: jax.lax.slice_in_dim(a, lo, hi, axis=0),
                        layers)


def _hybrid_forward(params, cfg, x, ctx, positions, windows):
    """Zamba2 pattern: Mamba segments with a shared attention block between
    (same weights at every application site)."""
    zero = jnp.zeros((), jnp.float32)
    lb = z = zero
    shared_window = cfg.window  # 0 (full) normally; ring window for long ctx
    for lo, hi in _segment_bounds(cfg.n_layers, cfg.attn_every or cfg.n_layers):
        seg = _slice_layers(params["layers"], lo, hi)
        x, lbi, zi = _scan_stack(x, seg, cfg, ctx, positions,
                                 windows[lo:hi], "mamba")
        lb, z = lb + lbi, z + zi
        if hi < cfg.n_layers or hi == cfg.n_layers:
            x = dense_layer_apply(x, params["shared_attn"], cfg, ctx,
                                  positions=positions, window=shared_window)
    return x, lb, z


# ---------------------------------------------------------------------------
# Prefill (serving: forward + cache population)
# ---------------------------------------------------------------------------


def _ring_pack(k_full: jax.Array, window: int) -> jax.Array:
    """Arrange the last `window` steps of (B, S, ...) into ring-slot order."""
    S = k_full.shape[1]
    if S <= window:
        pad = [(0, 0)] * k_full.ndim
        pad[1] = (0, window - S)
        return jnp.pad(k_full, pad)
    j = jnp.arange(window)
    p = (S - 1) - jnp.mod((S - 1) - j, window)
    return jnp.take(k_full, p, axis=1)


def prefill_lm(params: dict, cfg: ModelConfig, tokens: jax.Array,
               ctx: ShardCtx, max_len: int,
               extra_embeds: Optional[jax.Array] = None
               ) -> tuple[jax.Array, dict]:
    """Run the prompt through the stack, returning (last-token logits,
    populated decode cache).  This is the serving 'bulk' phase: the cache
    is staged once, decode then streams against it."""
    x = _embed_inputs(params, cfg, tokens, ctx, extra_embeds)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    windows = jnp.asarray(cfg.layer_windows(), jnp.int32)
    cache = init_lm_cache(cfg, B, max_len, ctx)
    ring = cache_kind(cfg) == "ring"
    s_cache = _attn_cache_len(cfg, max_len)

    if cfg.family in ("dense", "vlm", "moe"):
        is_moe = cfg.family == "moe"

        def body(carry, xs):
            h = carry
            lp, w = xs
            hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
            from .blocks import self_attention_block
            attn_out, k_new, v_new = self_attention_block(
                hn, lp["attn"], cfg, ctx, q_pos=positions, k_pos=positions,
                causal=True, window=w)
            h = ctx.shard_act(h + attn_out)
            h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
            from . import ffn as ffn_lib
            if is_moe:
                moe_p = lp["moe"]
                impl = ctx.choose_moe(cfg)
                if impl == "ep":
                    y, _, _ = ffn_lib.moe_ep(h2, moe_p["router"],
                                             moe_p["w_gate"], moe_p["w_up"],
                                             moe_p["w_down"], cfg=cfg,
                                             mesh=ctx.mesh,
                                             batch_axes=ctx.batch_axes,
                                             model_axis=ctx.model_axis)
                elif impl == "tp":
                    y, _, _ = ffn_lib.moe_tp(h2, moe_p["router"],
                                             moe_p["w_gate"], moe_p["w_up"],
                                             moe_p["w_down"], cfg=cfg,
                                             mesh=ctx.mesh,
                                             batch_axes=ctx.batch_axes,
                                             model_axis=ctx.model_axis)
                else:
                    y, _, _ = ffn_lib.moe_ref(h2, moe_p["router"],
                                              moe_p["w_gate"], moe_p["w_up"],
                                              moe_p["w_down"], cfg=cfg)
            else:
                y = ffn_lib.swiglu(h2, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                                   lp["mlp"]["w_down"])
            h = ctx.shard_act(h + y)
            k_new = k_new.reshape(B, S, cfg.kv_dim)
            v_new = v_new.reshape(B, S, cfg.kv_dim)
            if ring:
                k_c = _ring_pack(k_new, s_cache)
                v_c = _ring_pack(v_new, s_cache)
            else:
                pad = [(0, 0)] * 3
                pad[1] = (0, max_len - S)
                k_c = jnp.pad(k_new, pad)
                v_c = jnp.pad(v_new, pad)
            return h, (k_c.astype(jnp.bfloat16), v_c.astype(jnp.bfloat16))

        body = _remat(body, cfg.remat)
        x, (k_all, v_all) = jax.lax.scan(body, x, (params["layers"], windows))
        cache["k"], cache["v"] = k_all, v_all

    elif cfg.family == "ssm":
        def body(h, xs):
            lp, w = xs
            hn = rms_norm(h, lp["ln"], cfg.norm_eps)
            y, st = ssm_lib.mamba_block_train(
                hn, lp, cfg, impl=ctx.impl, shard_heads=ctx.shard_heads,
                return_state=True)
            return ctx.shard_act(h + y), (st.conv, st.ssm)

        body = _remat(body, cfg.remat)
        x, (conv_all, ssm_all) = jax.lax.scan(body, x,
                                              (params["layers"], windows))
        cache["mamba"] = ssm_lib.MambaState(conv=conv_all, ssm=ssm_all)

    elif cfg.family == "hybrid":
        x, cache = _hybrid_prefill(params, cfg, x, ctx, positions, windows,
                                   cache, s_cache)
    else:
        raise ValueError(cfg.family)

    cache["pos"] = jnp.asarray(S, jnp.int32)
    logits = _logits(params, cfg, x[:, -1:, :])
    return logits, cache


def _hybrid_prefill(params, cfg, x, ctx, positions, windows, cache, s_cache):
    from .blocks import self_attention_block
    from . import ffn as ffn_lib
    B, S = x.shape[:2]
    conv_out, ssm_out, k_sites, v_sites = [], [], [], []

    def seg_body(h, xs):
        lp, w = xs
        hn = rms_norm(h, lp["ln"], cfg.norm_eps)
        y, st = ssm_lib.mamba_block_train(
            hn, lp, cfg, impl=ctx.impl, shard_heads=ctx.shard_heads,
            return_state=True)
        return ctx.shard_act(h + y), (st.conv, st.ssm)

    seg_body = _remat(seg_body, cfg.remat)
    for lo, hi in _segment_bounds(cfg.n_layers, cfg.attn_every or cfg.n_layers):
        seg = _slice_layers(params["layers"], lo, hi)
        x, (conv_n, ssm_n) = jax.lax.scan(seg_body, x, (seg, windows[lo:hi]))
        conv_out.append(conv_n)
        ssm_out.append(ssm_n)
        sp = params["shared_attn"]
        hn = rms_norm(x, sp["ln1"], cfg.norm_eps)
        attn_out, k_new, v_new = self_attention_block(
            hn, sp["attn"], cfg, ctx, q_pos=positions, k_pos=positions,
            causal=True, window=cfg.window)
        x = ctx.shard_act(x + attn_out)
        h2 = rms_norm(x, sp["ln2"], cfg.norm_eps)
        x = ctx.shard_act(x + ffn_lib.swiglu(h2, sp["mlp"]["w_gate"],
                                             sp["mlp"]["w_up"],
                                             sp["mlp"]["w_down"]))
        k_new = k_new.reshape(B, S, cfg.kv_dim)
        v_new = v_new.reshape(B, S, cfg.kv_dim)
        if cfg.window > 0:
            k_sites.append(_ring_pack(k_new, s_cache).astype(jnp.bfloat16))
            v_sites.append(_ring_pack(v_new, s_cache).astype(jnp.bfloat16))
        else:
            pad = [(0, 0)] * 3
            pad[1] = (0, cache["shared_k"].shape[2] - S)
            k_sites.append(jnp.pad(k_new, pad).astype(jnp.bfloat16))
            v_sites.append(jnp.pad(v_new, pad).astype(jnp.bfloat16))

    cache["mamba"] = ssm_lib.MambaState(conv=jnp.concatenate(conv_out, 0),
                                        ssm=jnp.concatenate(ssm_out, 0))
    cache["shared_k"] = jnp.stack(k_sites)
    cache["shared_v"] = jnp.stack(v_sites)
    return x, cache


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(params: dict, cfg: ModelConfig, batch: dict, ctx: ShardCtx
            ) -> tuple[jax.Array, dict]:
    logits, lb, z = forward_lm(params, cfg, batch["tokens"], ctx,
                               extra_embeds=batch.get("extra_embeds"))
    labels = batch["labels"]
    if cfg.frontend:
        # frontend positions carry no labels: score only the token tail
        logits = logits[:, -labels.shape[1]:]
    ce = cross_entropy_loss(logits, labels, batch.get("loss_mask"))
    aux = {"ce": ce, "load_balance": lb, "router_z": z}
    total = ce
    if cfg.moe:
        total = total + cfg.moe.load_balance_coef * lb + cfg.moe.router_z_coef * z
    return total, aux


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------


def cache_kind(cfg: ModelConfig) -> str:
    """'ring' when every attention layer is windowed (mixtral SWA);
    'full' otherwise (per-layer windows still masked inside a full cache)."""
    if cfg.window > 0 and cfg.global_every == 0:
        return "ring"
    return "full"


def _attn_cache_len(cfg: ModelConfig, max_len: int) -> int:
    return min(cfg.window, max_len) if cache_kind(cfg) == "ring" else max_len


def init_lm_cache(cfg: ModelConfig, batch: int, max_len: int,
                  ctx: Optional[ShardCtx] = None) -> dict:
    """Decode cache pytree.  Shapes are static; `pos` tracks the clock."""
    ctx = ctx or ShardCtx()
    cache: dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    L = cfg.n_layers
    if cfg.family in ("dense", "vlm", "moe"):
        s = _attn_cache_len(cfg, max_len)
        kv = jnp.zeros((L, batch, s, cfg.kv_dim), jnp.bfloat16)
        cache["k"] = ctx.shard_kv_cache(kv)
        cache["v"] = ctx.shard_kv_cache(kv)
    elif cfg.family in ("ssm", "hybrid"):
        st = ssm_lib.init_mamba_state(cfg, batch)
        cache["mamba"] = ssm_lib.MambaState(
            conv=jnp.zeros((L,) + st.conv.shape, st.conv.dtype),
            ssm=jnp.zeros((L,) + st.ssm.shape, st.ssm.dtype),
        )
        if cfg.family == "hybrid":
            n_sites = len(_segment_bounds(cfg.n_layers,
                                          cfg.attn_every or cfg.n_layers))
            s = min(cfg.window, max_len) if cfg.window > 0 else max_len
            kv = jnp.zeros((n_sites, batch, s, cfg.kv_dim), jnp.bfloat16)
            cache["shared_k"] = ctx.shard_kv_cache(kv)
            cache["shared_v"] = ctx.shard_kv_cache(kv)
    return cache


def _decode_attn_block(x, lp, cfg, ctx, k_cache, v_cache, pos, window,
                       ring: bool):
    """One decode step through one attention layer, reading its cache
    (B, S, Hkv * hd), which does not hold step ``pos`` yet.  Returns
    (x_out, k, v): the step's key and value, (B, 1, Hkv * hd) in the
    cache's dtype, for the caller to store (``_store_step``)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    B = x.shape[0]
    q_pos = jnp.broadcast_to(pos, (1,)).astype(jnp.int32)
    q = jnp.einsum("bsd,dq->bsq", h, lp["attn"]["wq"]).reshape(
        B, 1, cfg.n_heads, cfg.hd)
    k = jnp.einsum("bsd,dk->bsk", h, lp["attn"]["wk"]).reshape(
        B, 1, cfg.n_kv_heads, cfg.hd)
    v = jnp.einsum("bsd,dk->bsk", h, lp["attn"]["wv"]).reshape(
        B, 1, cfg.n_kv_heads, cfg.hd)
    q = apply_rope(q, q_pos, cfg.rope_theta)
    k = apply_rope(k, q_pos, cfg.rope_theta)
    positions = cache_positions_ring if ring else cache_positions_full
    # the slots as writing step `pos` would leave them, less the step
    # itself, which attends as its own column
    k_pos = positions(k_cache.shape[1], pos)
    k_pos = jnp.where(k_pos < pos, k_pos, -1)
    out = attention_decode(q, k_cache, v_cache, k, v, q_pos=q_pos,
                           k_pos=k_pos, window=window)
    out = out.reshape(B, 1, cfg.q_dim)
    x = x + jnp.einsum("bsq,qd->bsd", out, lp["attn"]["wo"])
    return (x, k.reshape(B, 1, cfg.kv_dim).astype(k_cache.dtype),
            v.reshape(B, 1, cfg.kv_dim).astype(v_cache.dtype))


def _store_step(k_cache, v_cache, k_step, v_step, pos, ring: bool):
    """Write every layer's step-``pos`` K/V, (L, B, 1, Hkv * hd), into the
    stacked caches (L, B, S, Hkv * hd): one update each, at slot ``pos``
    or, in a ring, ``pos`` modulo the cache's own slot count."""
    slot = jnp.mod(pos, k_cache.shape[2]) if ring else pos
    at = (0, 0, slot, 0)
    return (jax.lax.dynamic_update_slice(k_cache, k_step, at),
            jax.lax.dynamic_update_slice(v_cache, v_step, at))


def lm_decode_step(params: dict, cfg: ModelConfig, cache: dict,
                   tokens: jax.Array, ctx: ShardCtx
                   ) -> tuple[jax.Array, dict]:
    """One new token per sequence.  tokens: (B, 1).  Returns (logits, cache')."""
    from . import ffn as ffn_lib

    pos = cache["pos"]
    x = ctx.shard_act(params["embed"][tokens])
    new_cache = dict(cache)
    ring = cache_kind(cfg) == "ring"
    windows = jnp.asarray(cfg.layer_windows(), jnp.int32)

    if cfg.family in ("dense", "vlm", "moe"):
        is_moe = cfg.family == "moe"

        def body(h, xs):
            lp, k_l, v_l, w = xs
            h, k_t, v_t = _decode_attn_block(h, lp, cfg, ctx, k_l, v_l, pos,
                                             w, ring)
            h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
            if is_moe:
                moe = lp["moe"]
                impl = ctx.choose_moe(cfg)
                if impl == "ep":
                    y, _, _ = ffn_lib.moe_ep(h2, moe["router"], moe["w_gate"],
                                             moe["w_up"], moe["w_down"],
                                             cfg=cfg, mesh=ctx.mesh,
                                             batch_axes=ctx.batch_axes,
                                             model_axis=ctx.model_axis)
                elif impl == "tp":
                    y, _, _ = ffn_lib.moe_tp(h2, moe["router"], moe["w_gate"],
                                             moe["w_up"], moe["w_down"],
                                             cfg=cfg, mesh=ctx.mesh,
                                             batch_axes=ctx.batch_axes,
                                             model_axis=ctx.model_axis)
                else:
                    y, _, _ = ffn_lib.moe_ref(h2, moe["router"], moe["w_gate"],
                                              moe["w_up"], moe["w_down"],
                                              cfg=cfg)
            else:
                y = ffn_lib.swiglu(h2, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                                   lp["mlp"]["w_down"])
            return h + y, (k_t, v_t)

        x, (k_step, v_step) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], windows))
        new_cache["k"], new_cache["v"] = _store_step(
            cache["k"], cache["v"], k_step, v_step, pos, ring)

    elif cfg.family == "ssm":
        def body(h, xs):
            lp, conv_l, ssm_l = xs
            hn = rms_norm(h, lp["ln"], cfg.norm_eps)
            y, st = ssm_lib.mamba_block_decode(
                hn, lp, cfg, ssm_lib.MambaState(conv=conv_l, ssm=ssm_l))
            return h + y, (st.conv, st.ssm)

        x, (conv_new, ssm_new) = jax.lax.scan(
            body, x, (params["layers"], cache["mamba"].conv,
                      cache["mamba"].ssm))
        new_cache["mamba"] = ssm_lib.MambaState(conv=conv_new, ssm=ssm_new)

    elif cfg.family == "hybrid":
        x, new_cache = _hybrid_decode(params, cfg, cache, x, ctx, pos)

    else:
        raise ValueError(cfg.family)

    logits = _logits(params, cfg, x)
    new_cache["pos"] = pos + 1
    return logits, new_cache


def _hybrid_decode(params, cfg, cache, x, ctx, pos):
    from . import ffn as ffn_lib

    new_cache = dict(cache)
    bounds = _segment_bounds(cfg.n_layers, cfg.attn_every or cfg.n_layers)
    ring = cfg.window > 0
    conv_all, ssm_all = cache["mamba"].conv, cache["mamba"].ssm
    conv_out, ssm_out = [], []
    k_sites, v_sites = [], []

    def seg_body(h, xs):
        lp, conv_l, ssm_l = xs
        hn = rms_norm(h, lp["ln"], cfg.norm_eps)
        y, st = ssm_lib.mamba_block_decode(
            hn, lp, cfg, ssm_lib.MambaState(conv=conv_l, ssm=ssm_l))
        return h + y, (st.conv, st.ssm)

    for i, (lo, hi) in enumerate(bounds):
        seg = _slice_layers(params["layers"], lo, hi)
        conv_seg = jax.lax.slice_in_dim(conv_all, lo, hi, axis=0)
        ssm_seg = jax.lax.slice_in_dim(ssm_all, lo, hi, axis=0)
        x, (conv_n, ssm_n) = jax.lax.scan(seg_body, x, (seg, conv_seg, ssm_seg))
        conv_out.append(conv_n)
        ssm_out.append(ssm_n)
        # shared attention block at the segment boundary
        sp = params["shared_attn"]
        x, k_t, v_t = _decode_attn_block(x, sp, cfg, ctx, cache["shared_k"][i],
                                         cache["shared_v"][i], pos,
                                         cfg.window, ring)
        h2 = rms_norm(x, sp["ln2"], cfg.norm_eps)
        x = x + ffn_lib.swiglu(h2, sp["mlp"]["w_gate"], sp["mlp"]["w_up"],
                               sp["mlp"]["w_down"])
        k_sites.append(k_t)
        v_sites.append(v_t)

    new_cache["mamba"] = ssm_lib.MambaState(
        conv=jnp.concatenate(conv_out, axis=0),
        ssm=jnp.concatenate(ssm_out, axis=0))
    new_cache["shared_k"], new_cache["shared_v"] = _store_step(
        cache["shared_k"], cache["shared_v"], jnp.stack(k_sites),
        jnp.stack(v_sites), pos, ring)
    return x, new_cache
