"""SSD (Mamba2) scan — Pallas TPU kernel.

Full chunked SSD in one kernel: grid (B, H, nc) with the minor-most chunk
axis sequential, so the recurrent (P, N) state lives in VMEM scratch and
flows across chunks — the inter-chunk recurrence costs zero HBM traffic.
Per chunk the dual quadratic form runs on the MXU:

    y_intra = (tril(exp(cum_i - cum_j)) * dt_j * (C_i . B_j)) @ x
    y_inter = exp(cum_i) * (C_i @ state_in)
    state   = exp(total) * state_in + B^T @ (exp(total - cum) * dt * x)

The state after the last chunk leaves as a second output, (B, H, P, N)
f32: a prefill hands it to decode.  The pure-jnp oracle is
:func:`repro.models.ssm.ssd_chunked`; tests sweep (B, S, H, P, N, chunk)
in interpret mode.

VMEM per step (Q=256, P=64, N<=128): x (Q,P) 64 KiB, B/C (Q,N) 128 KiB,
L/CB (Q,Q) f32 256 KiB each, state (P,N) 32 KiB — well under budget.
``dt`` moves as a lane-dense (1, Q) row per chunk and the per-head decay
``A`` sits whole in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, final_ref,
                state_ref, *, Q: int, nc: int):
    h = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)         # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)       # (1, Q) row
    A = a_ref[h]                                   # f32 scalar (per head)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)        # (Q, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)        # (Q, N)

    # the chunk's prefix sums in both orientations as exact-f32 matmuls
    # against the causal mask (Mosaic has no cumsum, and no (1, Q) ->
    # (Q, 1) relayout); the identity turns the dt row into a column
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    mask = row >= col
    tril = mask.astype(jnp.float32)
    eye = (row == col).astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    dA = dt * A                                    # (1, Q) negatives
    cum = jax.lax.dot_general(dA, tril, nt, precision=hi)       # (1, Q)
    cum_c = jax.lax.dot_general(tril, dA, nt, precision=hi)     # (Q, 1)
    dt_c = jax.lax.dot_general(eye, dt, nt, precision=hi)       # (Q, 1)
    total = jnp.sum(dA)                            # chunk's whole decay

    L = jnp.where(mask, jnp.exp(cum_c - cum), 0.0) * dt
    CB = jax.lax.dot_general(Cm, Bm, nt)                         # (Q, Q)
    W = CB * L
    y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())))      # (Q, P)

    # inter-chunk: y += exp(cum) * (C @ state_in);  state: (P, N)
    state = state_ref[...]
    y = y + jnp.exp(cum_c) * jax.lax.dot_general(Cm, state, nt)
    # state update
    wdt = jnp.exp(total - cum_c) * dt_c                          # (Q, 1)
    state = jnp.exp(total) * state + jax.lax.dot_general(
        x * wdt, Bm, (((0,), (0,)), ((), ())))                   # (P, N)
    state_ref[...] = state

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        final_ref[0, 0] = state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_bhsd(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                  Cm: jax.Array, *, chunk: int = 256,
                  interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (B, H, S, P); dt: (B, H, S) f32; A: (H,) f32;
    Bm/Cm: (B, G, S, N) (groups broadcast to heads) -> (y (B, H, S, P),
    final state (B, H, P, N) f32)."""
    B, H, S, P = x.shape
    G, N = Bm.shape[1], Bm.shape[3]
    assert H % G == 0
    rep = H // G
    assert S % chunk == 0
    nc = S // chunk

    xc = x.reshape(B, H, nc, chunk, P)
    dtc = dt.reshape(B, H, nc, 1, chunk)
    Bc = Bm.reshape(B, G, nc, chunk, N)
    Cc = Cm.reshape(B, G, nc, chunk, N)

    kernel = functools.partial(_ssd_kernel, Q=chunk, nc=nc)
    y, final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h // rep, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            # one block per (b, h), resident across the chunks
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, nc, chunk, P), x.dtype),
                   jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xc, dtc, A, Bc, Cc)
    return y.reshape(B, H, S, P), final
