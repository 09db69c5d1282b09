"""Decode attention — single-token flash-decode Pallas kernel.

One new query token per sequence against a long KV cache.  Grid
(B, Hkv, nk): all G query heads of one KV head process together, so the
score block is (G, bk) — MXU-shaped when G >= 8 — and the online-softmax
state (m, l, acc) persists in VMEM scratch across the sequential k-block
axis.  Ring caches and partial fills are handled by an explicit
``k_pos`` operand (absolute position per slot, -1 = empty) and the query
position ``q_pos`` — identical semantics to the model's cache masks.
``q_pos`` sits whole in SMEM (one scalar per sequence, read at the grid's
batch index); ``k_pos`` is viewed as (B, 1, S) so its (1, bk) block is
lane-dense for any batch size.

VMEM per step (G<=16, bk=512, hd<=256): k/v blocks 2*512*256*2B = 512 KiB,
scores G*512*4B <= 32 KiB — small; the kernel is HBM-bandwidth-bound by
design (reads the cache once), which is the roofline-ideal decode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, G: int, bk: int, nk: int,
                   scale: float, window: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bk)

    k_pos = kpos_ref[0]                                    # (1, bk) i32
    q_pos = qpos_ref[b]                                    # scalar i32
    keep = jnp.logical_and(k_pos >= 0, k_pos <= q_pos)
    if window > 0:
        keep = jnp.logical_and(keep, k_pos > q_pos - window)
    keep = jnp.broadcast_to(keep, (G, bk))
    s = jnp.where(keep, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]                # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention_bhd(q: jax.Array, k: jax.Array, v: jax.Array,
                         k_pos: jax.Array, q_pos: jax.Array, *,
                         window: int = 0, bk: int = 512,
                         interpret: bool = False) -> jax.Array:
    """q: (B, Hq, hd); k/v: (B, Hkv, S, hd); k_pos: (B, S) i32;
    q_pos: (B,) i32 -> (B, Hq, hd)."""
    B, Hq, hd = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    bk = min(bk, S)
    assert S % bk == 0
    nk = S // bk
    scale = hd ** -0.5
    qg = q.reshape(B, Hkv, G, hd)

    kernel = functools.partial(_decode_kernel, G=G, bk=bk, nk=nk,
                               scale=scale, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos, qg, k, v, k_pos.reshape(B, 1, S))
    return out.reshape(B, Hq, hd)
