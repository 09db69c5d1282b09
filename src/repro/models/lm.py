"""Decoder-only language models: dense / MoE / SSM / hybrid / VLM.

Every homogeneous layer stack runs as ``jax.lax.scan`` over stacked layer
parameters, so compile time (and the dry-run matrix) is O(1) in depth.
Heterogeneity is handled without breaking the scan:

* per-layer attention windows (gemma3's 5:1 local:global) ride through the
  scan as an ``int32`` xs array feeding the mask,
* a hybrid (``ModelConfig.hybrid_schedule``: zamba2's one shared
  attention block every ``attn_every`` layers, granite's attention layers
  at uneven positions, each with its own weights) splits the Mamba stack
  into runs, scanning each run and applying attention between them; the
  ssm family is a hybrid with no attention.  Mamba states, stacked over
  the Mamba layers, ride through those scans as a carry that each layer
  updates in place,
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

from . import ffn as ffn_lib
from . import ssm as ssm_lib
from .attention import (attention_decode, cache_positions_full,
                        cache_positions_ring)
from .blocks import (ShardCtx, dense_layer_apply, init_mlp_params,
                     moe_layer_apply, self_attention_block, stack_layers)
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
    params["layers"] = stack_layers(keys[1], cfg, cfg.n_mamba, kind)
    if cfg.family in ("ssm", "hybrid"):
        # mamba layers need a pre-norm scale
        params["layers"]["ln"] = jnp.zeros((cfg.n_mamba, D), jnp.float32)
    if cfg.family == "hybrid":
        params["attn_layers"] = stack_layers(keys[2], cfg,
                                             cfg.attn_weight_sets, "attn")
        if cfg.layer_types:            # an MLP after every mixer
            params["layers"]["mlp"] = jax.vmap(
                lambda k: init_mlp_params(k, cfg))(
                    jax.random.split(keys[6], cfg.n_mamba))
            params["layers"]["ln2"] = jnp.zeros((cfg.n_mamba, D), jnp.float32)
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


def _embed(params: dict, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _embed_inputs(params: dict, cfg: ModelConfig, tokens: jax.Array,
                  ctx: ShardCtx, extra_embeds: Optional[jax.Array]) -> jax.Array:
    x = _embed(params, cfg, tokens)
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
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _add(x, y, cfg):
    """The residual add, its branch scaled by ``residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _mlp_residual(x, lp, cfg, ctx):
    """x + MLP(RMSNorm(x)): the second half of a layer."""
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    y = ffn_lib.swiglu(h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                       lp["mlp"]["w_down"])
    return ctx.shard_act(_add(x, y, cfg))


def _mamba_layer_apply(x, lp, cfg, ctx, return_state: bool = False):
    """A Mamba layer: x + Mamba2(RMSNorm(x)), then an MLP where the layer
    has one; with ``return_state`` also the layer's MambaState."""
    h = rms_norm(x, lp["ln"], cfg.norm_eps)
    out = ssm_lib.mamba_block_train(h, lp, cfg, shard_heads=ctx.shard_heads,
                                    return_state=return_state)
    y, st = out if return_state else (out, None)
    x = ctx.shard_act(_add(x, y, cfg))
    if "mlp" in lp:
        x = _mlp_residual(x, lp, cfg, ctx)
    return (x, st) if return_state else x


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

    body = {"attn": dense_body, "moe": moe_body}[body_kind]
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
    elif cfg.family in ("ssm", "hybrid"):
        x = _hybrid_forward(params, cfg, x, ctx, positions)
        lb = z = jnp.zeros((), jnp.float32)
    else:
        raise ValueError(cfg.family)
    return _logits(params, cfg, x), lb, z


def _layer(layers: dict, i) -> dict:
    """Layer ``i`` (static or traced) of stacked layer parameters."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), layers)


def _scan_layers(body, carry, layers: dict, lo: int, hi: int):
    """``carry = body(carry, layer lo's params, lo)`` ... through hi - 1,
    as one scan over the layer index: each layer's parameters are read out
    of the whole stack in the loop, where a static slice of the stack
    would be copied once per run of layers."""
    def step(c, l):
        return body(c, _layer(layers, l), l), None
    return jax.lax.scan(step, carry, jnp.arange(lo, hi, dtype=jnp.int32))[0]


def _attn_site(x, lp, cfg, ctx, positions, q_chunk=None):
    """A hybrid's attention layer over a whole sequence: attention, then
    its MLP.  Returns (x, k, v), the keys and values (B, S, Hkv, hd)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    out, k, v = self_attention_block(
        h, lp["attn"], cfg, ctx, q_pos=positions, k_pos=positions,
        causal=True, window=cfg.window, q_chunk=q_chunk)
    x = ctx.shard_act(_add(x, out, cfg))
    return _mlp_residual(x, lp, cfg, ctx), k, v


def _hybrid_forward(params, cfg, x, ctx, positions):
    """The ssm and hybrid stacks: runs of Mamba layers with attention
    layers between them, in ``cfg.hybrid_schedule()`` order."""
    body = _remat(lambda h, lp, _: _mamba_layer_apply(h, lp, cfg, ctx),
                  cfg.remat)
    for kind, a, b in cfg.hybrid_schedule():
        if kind == "mamba":
            x = _scan_layers(body, x, params["layers"], a, b)
        else:
            x, _, _ = _attn_site(x, _layer(params["attn_layers"], a), cfg,
                                 ctx, positions)
    return x


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
            attn_out, k_new, v_new = self_attention_block(
                hn, lp["attn"], cfg, ctx, q_pos=positions, k_pos=positions,
                causal=True, window=w)
            h = ctx.shard_act(h + attn_out)
            h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
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

    elif cfg.family in ("ssm", "hybrid"):
        x, cache = _hybrid_prefill(params, cfg, x, ctx, positions, cache,
                                   s_cache)
    else:
        raise ValueError(cfg.family)

    cache["pos"] = jnp.asarray(S, jnp.int32)
    logits = _logits(params, cfg, x[:, -1:, :])
    return logits, cache


#: a hybrid attention layer's prefill scores are evaluated in query chunks
#: of at most this many f32 elements (B * Hq * chunk * S; 1 GiB)
SITE_SCORE_ELEMS = 1 << 28


def _site_q_chunk(batch: int, cfg: ModelConfig, seq: int) -> Optional[int]:
    """Query chunk for a hybrid attention layer's prefill: None (the
    attention's own choice) while the whole score tensor is within
    ``SITE_SCORE_ELEMS``, else the largest halving of the sequence that
    is, or 128."""
    per_query = batch * cfg.n_heads * seq
    if per_query * seq <= SITE_SCORE_ELEMS:
        return None
    chunk = seq
    while chunk > 128 and chunk % 2 == 0 and per_query * chunk > SITE_SCORE_ELEMS:
        chunk //= 2
    return chunk


def _pack_kv(kv: jax.Array, ring: bool, s_cache: int) -> jax.Array:
    """A prefill's (B, S, Hkv, hd) keys or values as cache rows
    (B, s_cache, Hkv * hd): ring slots in a ring, else padded."""
    B, S = kv.shape[:2]
    kv = kv.reshape(B, S, -1)
    if ring:
        return _ring_pack(kv, s_cache).astype(jnp.bfloat16)
    return jnp.pad(kv, ((0, 0), (0, s_cache - S), (0, 0))).astype(jnp.bfloat16)


def _state_at(st: "ssm_lib.MambaState", l) -> "ssm_lib.MambaState":
    """Layer ``l``'s state out of the stacked states."""
    return ssm_lib.MambaState(
        conv=jax.lax.dynamic_index_in_dim(st.conv, l, keepdims=False),
        ssm=jax.lax.dynamic_index_in_dim(st.ssm, l, keepdims=False))


def _state_put(st: "ssm_lib.MambaState", new: "ssm_lib.MambaState", l
               ) -> "ssm_lib.MambaState":
    """The stacked states with layer ``l``'s replaced, in place."""
    return ssm_lib.MambaState(
        conv=jax.lax.dynamic_update_index_in_dim(
            st.conv, new.conv.astype(st.conv.dtype), l, 0),
        ssm=jax.lax.dynamic_update_index_in_dim(
            st.ssm, new.ssm.astype(st.ssm.dtype), l, 0))


#: a hybrid prefill runs its batch in pieces of at most this many tokens.
#: Compiled for a TPU v5e, granite-4.0-h-micro's prefill of 32 x 2048
#: tokens holds 9.6 GB of temporaries whole (19.1 GB live with weights and
#: cache), and 4.9, 3.5 and 2.7 GB in pieces of 16384, 8192 and 4096
PREFILL_TOKENS = 8192


def _hybrid_prefill(params, cfg, x, ctx, positions, cache, s_cache):
    """``_hybrid_forward`` that also fills the cache, in pieces of the
    batch of at most ``PREFILL_TOKENS`` tokens each.  Returns (the last
    position's hidden state (B, 1, D), cache)."""
    B, S = x.shape[:2]
    rows = B
    while rows > 1 and rows * S > PREFILL_TOKENS and rows % 2 == 0:
        rows //= 2
    if rows == B:
        x, cache = _hybrid_prefill_rows(params, cfg, x, ctx, positions,
                                        cache, s_cache)
        return x[:, -1:], cache

    def piece(cache, i):
        at = i * rows
        part = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, at, rows, 1)
            if a.ndim else a, cache)
        h, part = _hybrid_prefill_rows(
            params, cfg, jax.lax.dynamic_slice_in_dim(x, at, rows, 0), ctx,
            positions, part, s_cache)
        cache = jax.tree.map(
            lambda a, p: jax.lax.dynamic_update_slice_in_dim(a, p, at, 1)
            if a.ndim else a, cache, part)
        return cache, h[:, -1:]

    cache, last = jax.lax.scan(piece, cache, jnp.arange(B // rows))
    return last.reshape((B,) + last.shape[2:]), cache


def _hybrid_prefill_rows(params, cfg, x, ctx, positions, cache, s_cache):
    """``_hybrid_forward`` that also fills the cache: each Mamba layer's
    final state into the stacked states, each attention site's keys and
    values into its own cache."""
    B, S = x.shape[:2]
    ring = cache_kind(cfg) == "ring"
    q_chunk = _site_q_chunk(B, cfg, S)
    k_sites, v_sites = [], []

    def body(carry, lp, l):
        h, states = carry
        h, st = _mamba_layer_apply(h, lp, cfg, ctx, return_state=True)
        return h, _state_put(states, st, l)

    body = _remat(body, cfg.remat)
    states = cache["mamba"]
    for kind, a, b in cfg.hybrid_schedule():
        if kind == "mamba":
            x, states = _scan_layers(body, (x, states), params["layers"], a, b)
        else:
            x, k, v = _attn_site(x, _layer(params["attn_layers"], a), cfg,
                                 ctx, positions, q_chunk)
            k_sites.append(_pack_kv(k, ring, s_cache))
            v_sites.append(_pack_kv(v, ring, s_cache))
    cache["mamba"] = states
    if k_sites:
        cache["attn_k"] = ctx.shard_kv_cache(jnp.stack(k_sites))
        cache["attn_v"] = ctx.shard_kv_cache(jnp.stack(v_sites))
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
    s = _attn_cache_len(cfg, max_len)
    if cfg.family in ("dense", "vlm", "moe"):
        kv = jnp.zeros((cfg.n_layers, batch, s, cfg.kv_dim), jnp.bfloat16)
        cache["k"] = ctx.shard_kv_cache(kv)
        cache["v"] = ctx.shard_kv_cache(kv)
    elif cfg.family in ("ssm", "hybrid"):
        # two kinds of state side by side: every Mamba layer's conv and
        # SSM state, and the K/V of every attention site
        st = ssm_lib.init_mamba_state(cfg, batch)
        L = cfg.n_mamba
        cache["mamba"] = ssm_lib.MambaState(
            conv=jnp.zeros((L,) + st.conv.shape, st.conv.dtype),
            ssm=jnp.zeros((L,) + st.ssm.shape, st.ssm.dtype),
        )
        if cfg.family == "hybrid":
            kv = jnp.zeros((cfg.attn_sites, batch, s, cfg.kv_dim),
                           jnp.bfloat16)
            cache["attn_k"] = ctx.shard_kv_cache(kv)
            cache["attn_v"] = ctx.shard_kv_cache(kv)
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
    if cfg.rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    positions = cache_positions_ring if ring else cache_positions_full
    # the slots as writing step `pos` would leave them, less the step
    # itself, which attends as its own column
    k_pos = positions(k_cache.shape[1], pos)
    k_pos = jnp.where(k_pos < pos, k_pos, -1)
    out = attention_decode(q, k_cache, v_cache, k, v, q_pos=q_pos,
                           k_pos=k_pos, window=window,
                           scale=cfg.attention_multiplier)
    out = out.reshape(B, 1, cfg.q_dim)
    x = _add(x, jnp.einsum("bsq,qd->bsd", out, lp["attn"]["wo"]), cfg)
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
    pos = cache["pos"]
    x = ctx.shard_act(_embed(params, cfg, tokens))
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

    elif cfg.family in ("ssm", "hybrid"):
        x, new_cache = _hybrid_decode(params, cfg, cache, x, ctx, pos)

    else:
        raise ValueError(cfg.family)

    logits = _logits(params, cfg, x)
    new_cache["pos"] = pos + 1
    return logits, new_cache


def _hybrid_decode(params, cfg, cache, x, ctx, pos):
    """One decode step through the ssm and hybrid stacks, in
    ``cfg.hybrid_schedule()`` order: each Mamba layer reads and rewrites
    its state in the stacked states, carried through the scans; each
    attention site reads its cache, and one write after the stack stores
    every site's step K/V (``_store_step``)."""
    new_cache = dict(cache)
    ring = cache_kind(cfg) == "ring"
    k_sites, v_sites = [], []

    def body(carry, lp, l):
        h, states = carry
        hn = rms_norm(h, lp["ln"], cfg.norm_eps)
        with jax.named_scope("ssm_step"):
            st = _state_at(states, l)
        y, st = ssm_lib.mamba_block_decode(hn, lp, cfg, st)
        with jax.named_scope("ssm_step"):
            states = _state_put(states, st, l)
        h = _add(h, y, cfg)
        if "mlp" in lp:
            h = _mlp_residual(h, lp, cfg, ctx)
        return h, states

    states = cache["mamba"]
    for kind, a, b in cfg.hybrid_schedule():
        if kind == "mamba":
            x, states = _scan_layers(body, (x, states), params["layers"], a, b)
        else:
            lp = _layer(params["attn_layers"], a)
            x, k_t, v_t = _decode_attn_block(
                x, lp, cfg, ctx, cache["attn_k"][b], cache["attn_v"][b], pos,
                cfg.window, ring)
            x = _mlp_residual(x, lp, cfg, ctx)
            k_sites.append(k_t)
            v_sites.append(v_t)
    new_cache["mamba"] = states
    if k_sites:
        new_cache["attn_k"], new_cache["attn_v"] = _store_step(
            cache["attn_k"], cache["attn_v"], jnp.stack(k_sites),
            jnp.stack(v_sites), pos, ring)
    return x, new_cache
