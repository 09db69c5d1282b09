"""Plain float32 references, written from the published descriptions.

They import nothing of the program and take nothing it made: weights come
from ``weights.py`` and the seed, layer by layer, so that a reference fits
on the chip beside nothing else.  Every matrix product runs at
``jax.default_matmul_precision("highest")``.

* ``dense_logits``: a pre-norm decoder (Phi-3: RMSNorm, RoPE with halves
  rotated, causal attention with a sliding window, SwiGLU), full forward
  over prompt and served tokens.  ``quant="fp8"`` is the control: every
  matrix product takes float8 (e4m3) operands, each scaled per tensor.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import weights as W

F32 = jnp.float32


def _mm(a, b, quant):
    """a @ b over the last axis of a, in float32 or with fp8 operands."""
    if quant == "fp8":
        fp8 = jnp.float8_e4m3fn
        sa = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        sb = jnp.maximum(jnp.max(jnp.abs(b)), 1e-30) / 448.0
        qa = (a / sa).astype(fp8).astype(F32)
        qb = (b / sb).astype(fp8).astype(F32)
        return jnp.einsum("...k,kn->...n", qa, qb) * (sa * sb)
    return jnp.einsum("...k,kn->...n", a, b)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv                 # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ---------------------------------------------------------------------------
# Dense decoder (served model)
# ---------------------------------------------------------------------------


def _dense_block(x, p, hf, quant):
    """One pre-norm decoder layer; ``p`` as the program nests it."""
    d = W.dims(hf)
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    window = hf.get("sliding_window") or 0
    B, T, D = x.shape
    H, Hkv, hd = d["H"], d["Hkv"], d["hd"]
    pos = jnp.arange(T)
    h = rms_norm(x, 1.0 + p["ln1"], eps)
    q = _mm(h, p["attn"]["wq"], quant).reshape(B, T, H, hd)
    k = _mm(h, p["attn"]["wk"], quant).reshape(B, T, Hkv, hd)
    v = _mm(h, p["attn"]["wv"], quant).reshape(B, T, Hkv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    keep = pos[None, :] <= pos[:, None]
    if window:
        keep &= pos[None, :] > pos[:, None] - window
    s = jnp.where(keep, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + _mm(o.reshape(B, T, H * hd), p["attn"]["wo"], quant)
    h = rms_norm(x, 1.0 + p["ln2"], eps)
    g = _mm(h, p["mlp"]["w_gate"], quant)
    u = _mm(h, p["mlp"]["w_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, p["mlp"]["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("hf_items", "quant"))
def _dense_layer(x, key, layer, *, hf_items, quant):
    hf = thaw(hf_items)
    drawn = {k: v.astype(F32) for k, v in W.draw_layer(key, hf, layer).items()}
    return _dense_block(x, nest(drawn)["layers"], hf, quant)


@functools.partial(jax.jit, static_argnames=("hf_items",))
def _dense_embed(tokens, key, *, hf_items):
    hf = thaw(hf_items)
    return W.draw_unstacked(key, hf)["embed"].astype(F32)[tokens]


@functools.partial(jax.jit, static_argnames=("hf_items", "quant", "first"))
def _dense_head(x, key, *, hf_items, quant, first):
    hf = thaw(hf_items)
    p = W.draw_unstacked(key, hf)
    h = rms_norm(x[:, first:], 1.0 + p["final_norm"].astype(F32),
                 hf["rms_norm_eps"])
    head = (p["embed"].T if hf["tie_word_embeddings"] else p["lm_head"])
    return _mm(h, head.astype(F32), quant)


def thaw(items: tuple) -> dict:
    return dict(items)


def hf_items(hf: dict) -> tuple:
    """A config's top-level numbers and names as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in hf.items()
                        if not isinstance(v, (dict, list))))


def dense_logits(hf: dict, seed: int, tokens, first: int, quant: str = "f32"):
    """Logits (B, T - first, V) of positions ``first..T-1``, in float32."""
    key = W.run_key(seed)
    items = hf_items(hf)
    with jax.default_matmul_precision("highest"):
        x = _dense_embed(jnp.asarray(tokens), key, hf_items=items)
        for layer in range(W.dims(hf)["L"]):
            x = _dense_layer(x, key, layer, hf_items=items, quant=quant)
        return _dense_head(x, key, hf_items=items, quant=quant, first=first)


def nest(flat: dict) -> dict:
    """``{"layers/attn/wq": a}`` -> ``{"layers": {"attn": {"wq": a}}}``."""
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out
