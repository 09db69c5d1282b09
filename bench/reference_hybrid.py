"""Plain float32 reference of a Mamba-2 and attention hybrid (Granite-4.0-H,
``model_type: granitemoehybrid``), written from the published description.

It imports nothing of the program and takes nothing it made: weights come
from ``weights_hybrid.py`` and the seed, one layer at a time, and the
batch goes through in blocks of sequences, so that the reference fits on
the chip beside nothing else.  Every matrix product runs at
``jax.default_matmul_precision("highest")``.

    h0 = embedding_multiplier * E[tok]
    for each layer, in ``layer_types`` order:
        h = h + residual_multiplier * Mixer(RMSNorm(h))
        h = h + residual_multiplier * W_down(silu(h W_gate) * h W_up)  # RMSNorm'd
    logits = RMSNorm(h) @ E^T / logits_scaling

The attention mixer is causal GQA with no position embedding, its scores
scaled by ``attention_multiplier``.  The Mamba-2 mixer projects to (z, x,
B, C, dt), runs a causal depthwise conv with bias and silu over (x, B, C),
and then the SSM as a per-token recurrence, not chunked:

    state_t = exp(dt_t A) state_{t-1} + dt_t x_t B_t^T,   dt = softplus(dt + dt_bias)
    y_t     = state_t C_t + D x_t,                        A = -exp(A_log)

then the gated RMSNorm norm(y * silu(z)) * w and the out-projection.
RMSNorm scales are stored as their offset from 1 (the program's layout).
Departure from the published model: the weights are random.

``quant="fp8"`` is the control: every matrix product takes float8 (e4m3)
operands, each scaled per tensor, as ``reference.py``'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
import weights_hybrid as WH
from reference import _mm, hf_items, nest, rms_norm, thaw

F32 = jnp.float32

#: sequences per block, and queries per block of the attention
SEQ_BLOCK, QUERY_BLOCK = 16, 256


def _drawn(key, hf, stack, layer):
    flat = WH.draw_layer(key, hf, stack, layer)
    return nest({k: v.astype(F32) for k, v in flat.items()})[stack]


def _mlp(x, p, hf, quant):
    h = rms_norm(x, 1.0 + p["ln2"], hf["rms_norm_eps"])
    g = _mm(h, p["mlp"]["w_gate"], quant)
    u = _mm(h, p["mlp"]["w_up"], quant)
    return _mm(jax.nn.silu(g) * u, p["mlp"]["w_down"], quant)


def _mamba(x, p, hf, quant):
    d = WH.dims(hf)
    B, T, _ = x.shape
    E, H, P, N, G, Wc = d["E"], d["Hm"], d["P"], d["N"], d["G"], d["W"]
    zxbcdt = _mm(rms_norm(x, 1.0 + p["ln"], hf["rms_norm_eps"]),
                 p["in_proj"], quant)
    z = zxbcdt[..., :E]
    xbc = zxbcdt[..., E:2 * E + 2 * G * N]
    dt = zxbcdt[..., 2 * E + 2 * G * N:]
    padded = jnp.pad(xbc, ((0, 0), (Wc - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + T] * p["conv_w"][i] for i in range(Wc))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :E].reshape(B, T, H, P)
    Bm = jnp.repeat(xbc[..., E:E + G * N].reshape(B, T, G, N), H // G, 2)
    Cm = jnp.repeat(xbc[..., E + G * N:].reshape(B, T, G, N), H // G, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # (B, T, H)
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + dt_t[..., None, None] * x_t[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), F32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (xs, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + xs * p["D"][:, None]
    y = y.reshape(B, T, E) * jax.nn.silu(z)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + hf["rms_norm_eps"])
    return _mm(y * p["norm_w"], p["out_proj"], quant)


def _attention(x, p, hf, quant):
    d = WH.dims(hf)
    B, T, _ = x.shape
    H, Hkv, hd = d["H"], d["Hkv"], d["hd"]
    h = rms_norm(x, 1.0 + p["ln1"], hf["rms_norm_eps"])
    q = _mm(h, p["attn"]["wq"], quant).reshape(B, T, H, hd)
    k = jnp.repeat(_mm(h, p["attn"]["wk"], quant).reshape(B, T, Hkv, hd),
                   H // Hkv, axis=2)
    v = jnp.repeat(_mm(h, p["attn"]["wv"], quant).reshape(B, T, Hkv, hd),
                   H // Hkv, axis=2)
    pos = jnp.arange(T)
    outs = []
    for q0 in range(0, T, QUERY_BLOCK):          # causal, in query blocks
        qb = q[:, q0:q0 + QUERY_BLOCK]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * hf["attention_multiplier"]
        keep = pos[None, :] <= pos[q0:q0 + QUERY_BLOCK, None]
        s = jnp.where(keep, s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, axis=1).reshape(B, T, H * hd)
    return _mm(o, p["attn"]["wo"], quant)


@functools.partial(jax.jit, static_argnames=("hf_items", "kind", "quant"))
def _layer(x, key, layer, *, hf_items, kind, quant):
    hf = _full(hf_items)
    m = hf["residual_multiplier"]
    if kind == "mamba":
        p = _drawn(key, hf, "layers", layer)
        x = x + m * _mamba(x, p, hf, quant)
    else:
        p = _drawn(key, hf, "attn_layers", layer)
        x = x + m * _attention(x, p, hf, quant)
    return x + m * _mlp(x, p, hf, quant)


@functools.partial(jax.jit, static_argnames=("hf_items",))
def _embed(tokens, key, *, hf_items):
    hf = _full(hf_items)
    table = WH.draw_unstacked(key, hf)["embed"].astype(F32)
    return hf["embedding_multiplier"] * table[tokens]


@functools.partial(jax.jit, static_argnames=("hf_items", "quant", "first"))
def _head(x, key, *, hf_items, quant, first):
    hf = _full(hf_items)
    p = WH.draw_unstacked(key, hf)
    h = rms_norm(x[:, first:], 1.0 + p["final_norm"].astype(F32),
                 hf["rms_norm_eps"])
    head = p["embed"].T if hf["tie_word_embeddings"] else p["lm_head"]
    return _mm(h, head.astype(F32), quant) / hf["logits_scaling"]


def _items(hf: dict) -> tuple:
    """``hf_items`` with the layer types, as a hashable static argument."""
    return hf_items(hf) + (("layer_types", tuple(hf["layer_types"])),)


def _full(items: tuple) -> dict:
    hf = thaw(items)
    hf["layer_types"] = list(hf["layer_types"])
    return hf


def hybrid_logits(hf: dict, seed: int, tokens, first: int,
                  quant: str = "f32"):
    """Logits (B, T - first, V) of positions ``first..T-1``, in float32,
    computed ``SEQ_BLOCK`` sequences at a time (a caller that holds little
    memory passes no more than that)."""
    key = W.run_key(seed)
    items = _items(hf)
    tokens = np.asarray(tokens)
    out = []
    with jax.default_matmul_precision("highest"):
        for b0 in range(0, tokens.shape[0], SEQ_BLOCK):
            x = _embed(jnp.asarray(tokens[b0:b0 + SEQ_BLOCK]), key,
                       hf_items=items)
            n = {"mamba": 0, "attention": 0}
            for kind in hf["layer_types"]:
                x = _layer(x, key, n[kind], hf_items=items, kind=kind,
                           quant=quant)
                n[kind] += 1
            out.append(_head(x, key, hf_items=items, quant=quant,
                             first=first))
            del x
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)
