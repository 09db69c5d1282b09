"""Flash attention (fwd) — Pallas TPU kernel with explicit VMEM tiling.

Blocked online-softmax attention: grid (B, Hq, nq, nk); the minor-most
``nk`` axis iterates sequentially on TPU, so the running max / sum /
accumulator live in VMEM scratch across k-blocks and the output block is
written once at the last k-step.  GQA is expressed in the K/V BlockSpec
index maps (q-head h reads kv-head h // G) — no materialized repeat.

Supports causal and sliding-window masking via absolute block positions.
The pure-jnp oracle is :func:`repro.kernels.ref.attention_ref` (which the
model's `_attn_core` also uses); tests sweep shapes/dtypes in interpret
mode on the CPU, and ``tests/test_tpu_compile.py`` compiles the kernel
for a TPU v5e at SmolLM-360M widths.

VMEM budget per grid step (defaults bq=bk=256, hd<=256, f32 scratch):
q/k/v blocks 3*256*256*2B = 384 KiB, scores 256*256*4B = 256 KiB,
acc 256*256*4B = 256 KiB — comfortably under the ~16 MiB/core VMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, nk: int, scale: float, causal: bool,
                  window: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        keep = jnp.logical_and(keep, k_pos <= q_pos)
    if window > 0:
        keep = jnp.logical_and(keep, k_pos > q_pos - window)
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(keep, p, 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret", "scale"))
def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         bq: int = 256, bk: int = 256,
                         interpret: bool = False,
                         scale: float | None = None) -> jax.Array:
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Sk, hd) -> (B, Hq, Sq, hd); the
    scores scaled by ``scale``, 1 / sqrt(hd) when None."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = hd ** -0.5 if scale is None else scale

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk,
                               scale=scale, causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pl.MemorySpace.ANY if False else _vmem((bq, hd), jnp.float32),
            _vmem((bq,), jnp.float32),
            _vmem((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
