"""A plain float32 forward of the ssm and hybrid language models.

Written from the published layer equations, not from the program: no
cache, no chunked scan, no kernels, no batching tricks.  It takes the
program's parameter tree as stored (every RMSNorm scale as its offset
from 1, Mamba-2's gated-norm weight as is) and casts it to float32.

    h0 = embedding_multiplier * E[tok]
    Granite-4.0-H, for each layer in ``layer_types`` order:
        h = h + m * Mixer(RMSNorm(h))          # Mixer: Mamba-2 or attention
        h = h + m * W_down(silu(h W_gate) * h W_up)   # after RMSNorm
    Zamba2 (as this repository models it): Mamba-2 layers without an MLP,
        and after every ``attn_every`` of them and after the last, the one
        shared attention + MLP block
    logits = RMSNorm(h) @ head / logits_scaling

Attention is causal GQA, with RoPE unless the config turns it off, its
scores scaled by ``attention_multiplier`` (1 / sqrt(hd) when unset).  The
Mamba-2 mixer runs its SSM as the per-token recurrence

    state_t = exp(dt_t A) state_{t-1} + dt_t x_t B_t^T
    y_t     = state_t C_t + D x_t

after the causal depthwise conv over (x, B, C) with silu, dt =
softplus(dt + dt_bias), A = -exp(A_log), and the gated RMSNorm
norm(y * silu(z)) * w before the out-projection.

Departures from the published models: the weights are random; Zamba2's
shared block is applied to the hidden state alone (the published one also
takes the original embeddings and per-site LoRA adapters, which the
program does not model).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + offset)


def _rope(x, theta):
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, cfg):
    B, T, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, T, H, hd)
    k = (x @ p["wk"]).reshape(B, T, Hkv, hd)
    v = (x @ p["wv"]).reshape(B, T, Hkv, hd)
    if cfg.rope:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    scale = (cfg.attention_multiplier if cfg.attention_multiplier is not None
             else hd ** -0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    pos = jnp.arange(T)
    keep = pos[None, :] <= pos[:, None]
    if cfg.window:
        keep &= pos[None, :] > pos[:, None] - cfg.window
    s = jnp.where(keep, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, T, H * hd) @ p["wo"]


def mlp(x, p):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def mamba2(x, p, cfg):
    s = cfg.ssm
    B, T, _ = x.shape
    E, H, P, N, G = cfg.d_inner, cfg.ssm_heads, s.head_dim, s.d_state, s.n_groups
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :E]
    xbc = zxbcdt[..., E:2 * E + 2 * G * N]
    dt = zxbcdt[..., 2 * E + 2 * G * N:]
    W = s.conv_width
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + T] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :E].reshape(B, T, H, P)
    Bm = jnp.repeat(xbc[..., E:E + G * N].reshape(B, T, G, N), H // G, 2)
    Cm = jnp.repeat(xbc[..., E + G * N:].reshape(B, T, G, N), H // G, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # (B, T, H)
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp                             # (B,H,P) ...
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + dt_t[..., None, None] * x_t[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), F32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (xs, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + xs * p["D"][:, None]
    y = y.reshape(B, T, E) * jax.nn.silu(z)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    return (y * p["norm_w"]) @ p["out_proj"]


def _order(cfg):
    """(kind, weight index) of each layer in execution order."""
    if cfg.family == "ssm":
        return [("mamba", i) for i in range(cfg.n_layers)]
    if cfg.layer_types:
        out, m, a = [], 0, 0
        for kind in cfg.layer_types:
            if kind == "mamba":
                out.append(("mamba", m))
                m += 1
            else:
                out.append(("attention", a))
                a += 1
        return out
    out = []
    for i in range(cfg.n_layers):
        out.append(("mamba", i))
        if (i + 1) % cfg.attn_every == 0 or i + 1 == cfg.n_layers:
            out.append(("attention", 0))
    return out


def logits(params, cfg, tokens):
    """Logits (B, T, V) of every position, in float32."""
    p = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
    m, eps = cfg.residual_multiplier, cfg.norm_eps
    with jax.default_matmul_precision("highest"):
        h = cfg.embedding_multiplier * p["embed"][tokens]
        for kind, i in _order(cfg):
            if kind == "mamba":
                lp = jax.tree.map(lambda a: a[i], p["layers"])
                h = h + m * mamba2(_rms(h, lp["ln"], eps), lp, cfg)
            else:
                lp = jax.tree.map(lambda a: a[i], p["attn_layers"])
                h = h + m * attention(_rms(h, lp["ln1"], eps), lp["attn"], cfg)
            if "mlp" in lp:
                h = h + m * mlp(_rms(h, lp["ln2"], eps), lp["mlp"])
        head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
        return (_rms(h, p["final_norm"], eps) @ head) / cfg.logits_scaling
