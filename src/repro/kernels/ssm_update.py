"""One-token SSD (Mamba-2) state update — Pallas TPU kernel.

A decode step updates one layer's recurrent state and reads it out:

    new[h]  = exp(dt_h A_h) * state[h] + (dt_h x_h) B_g^T     (P x N)
    y[h, p] = sum_n new[h, p, n] C_g[n]

with g the group of head h.  The kernel takes the *stacked* f32 states
of every layer, (L, B, H, P, N), and the layer index ``l`` as a
scalar-prefetch operand: its index map visits only layer ``l``'s blocks,
and the states are aliased from input to output, so each state element
is read once and written once, in place, and the other layers are never
touched.  A slice of the layer and a write back into the stack would
each be a further pass over the layer's state.

All arithmetic is f32 on the vector unit: as a contraction on the matrix
unit the state would be rounded to bf16 at the default precision.  A
block holds ``bb`` batch rows of ``hb`` heads, taken a group's heads at
a time: each head's (P, N) tile takes its decay as a scalar, ``dt x`` as
a column (P on sublanes) and its group's B and C as rows (N on lanes),
and ``y`` leaves with P on lanes.

Blocks hold up to ``BLOCK_BYTES`` of state: a block costs a grid step of
fixed overhead, so fewer, larger blocks keep the pass at the memory's
bandwidth.  The pure-jnp oracle is :func:`repro.models.ssm.ssd_decode_step`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: state bytes per block: one batch row of 64 heads x 64 x 128 in f32
BLOCK_BYTES = 2 << 20


def _tile_bytes(P: int, N: int) -> int:
    """One head's (P, N) f32 state as the TPU lays it out, in (8, 128)
    tiles."""
    return -(-P // 8) * 8 * -(-N // 128) * 128 * 4


def block_shape(B: int, H: int, P: int, N: int, G: int) -> tuple[int, int]:
    """(batch rows, heads) of a block: all of a row's heads and as many
    rows as fit ``BLOCK_BYTES``, else as many of one row's heads as fit
    that lie in one group or span whole groups."""
    head, rep = _tile_bytes(P, N), H // G
    if H * head <= BLOCK_BYTES:
        return max(d for d in range(1, B + 1)
                   if B % d == 0 and d * H * head <= BLOCK_BYTES), H
    return 1, max(d for d in range(1, H + 1)
                  if H % d == 0 and (d * head <= BLOCK_BYTES or d == 1)
                  and (rep % d == 0 or d % rep == 0))


def _update_kernel(l_ref, state_ref, dec_ref, dtx_ref, b_ref, c_ref,
                   new_ref, y_ref, *, hb: int, rep: int, one_block: bool):
    del l_ref                        # the index maps' alone
    seg = min(hb, rep)               # heads of one group in the block
    for k in range(hb // seg):
        first = k * seg              # of the block's heads
        g = (first // rep if one_block
             else (pl.program_id(1) * hb + first) // rep)
        heads = slice(first, first + seg)
        dec = dec_ref[:, 0, heads]                           # (bb, seg, 1)
        dtx = dtx_ref[:, 0, heads, :]                        # (bb, seg, P)
        bm = b_ref[:, g, :][:, None, None, :]                # (bb,1,1,N)
        cm = c_ref[:, g, :][:, None, None, :]
        new = (state_ref[0, :, heads] * dec[..., None]
               + dtx[..., None] * bm)                        # (bb,seg,P,N)
        new_ref[0, :, heads] = new
        y_ref[:, 0, heads, :] = jnp.sum(new * cm, axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_update_stacked(states: jax.Array, l: jax.Array, x: jax.Array,
                       dt: jax.Array, A: jax.Array, Bm: jax.Array,
                       Cm: jax.Array, *, interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array]:
    """states: (L, B, H, P, N) f32, layer ``l`` (int32 scalar) updated;
    x: (B, H, P); dt: (B, H) f32 (post-softplus); A: (H,) f32;
    Bm/Cm: (B, G, N) -> (y (B, H, P) in x's dtype, states)."""
    L, B, H, P, N = states.shape
    G = Bm.shape[1]
    assert H % G == 0
    bb, hb = block_shape(B, H, P, N, G)
    nh = H // hb

    # the step's small operands, per head block (each block's last two
    # dimensions whole, as the TPU's tiling asks)
    dec = jnp.exp(dt * A[None, :]).reshape(B, nh, hb, 1)
    dtx = (dt[:, :, None] * x.astype(jnp.float32)).reshape(B, nh, hb, P)
    Bf = Bm.astype(jnp.float32)
    Cf = Cm.astype(jnp.float32)
    at = jnp.reshape(l, (1,)).astype(jnp.int32)

    kernel = functools.partial(_update_kernel, hb=hb, rep=H // G,
                               one_block=nh == 1)
    state_spec = pl.BlockSpec((1, bb, hb, P, N),
                              lambda b, h, at: (at[0], b, h, 0, 0))
    new, y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // bb, nh),
            in_specs=[
                state_spec,
                pl.BlockSpec((bb, 1, hb, 1), lambda b, h, at: (b, h, 0, 0)),
                pl.BlockSpec((bb, 1, hb, P), lambda b, h, at: (b, h, 0, 0)),
                pl.BlockSpec((bb, G, N), lambda b, h, at: (b, 0, 0)),
                pl.BlockSpec((bb, G, N), lambda b, h, at: (b, 0, 0)),
            ],
            out_specs=[
                state_spec,
                pl.BlockSpec((bb, 1, hb, P), lambda b, h, at: (b, h, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct((B, nh, hb, P), jnp.float32)],
        input_output_aliases={1: 0},
        # the state's block in and out, each double-buffered, and the
        # block's values
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, 8 * bb * hb * _tile_bytes(P, N))),
        interpret=interpret,
    )(at, states, dec, dtx, Bf, Cf)
    return y.reshape(B, H, P).astype(x.dtype), new
