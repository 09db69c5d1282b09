"""Blockwise int8 quantize/dequantize — Pallas kernel.

The compute half of the compressed gradient collective
(parallel/collectives.compressed_psum): symmetric per-block int8 with f32
scales.  Tiled so each grid step quantizes a (tile, block) panel from
VMEM; the oracle is optim/compression.quantize_int8_blockwise.  Scales
move as an (nb, 1) column inside the kernels, so each (tile, 1) block
keeps the TPU's 2-D tiling; the public API keeps them flat (nb,).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)            # (tile, block)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0   # (tile, 1)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[...]).astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def quantize_int8(x: jax.Array, *, block: int = 256, tile: int = 8,
                  interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """flat-able x -> (q int8 (nb, block), scales f32 (nb,)); nb padded to
    a multiple of ``tile``."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % (block * tile)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    panels = flat.reshape(-1, block)              # (nb, block)
    nb = panels.shape[0]
    grid = (nb // tile,)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0)),
                   pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=interpret,
    )(panels)
    return q, s.reshape(nb)


@functools.partial(jax.jit, static_argnames=("shape", "tile", "interpret"))
def dequantize_int8(q: jax.Array, s: jax.Array, shape: tuple[int, ...], *,
                    tile: int = 8, interpret: bool = False) -> jax.Array:
    nb, block = q.shape
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(nb // tile,),
        in_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0)),
                  pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=interpret,
    )(q, s.reshape(nb, 1))
    n = 1
    for d in shape:
        n *= d
    return out.reshape(-1)[:n].reshape(shape)
