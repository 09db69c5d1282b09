"""Blockwise streaming digest — Pallas kernel.

The compute half of accelerator-placed integrity
(:mod:`repro.core.integrity`): the paper budgets checksum/encryption
*inside* the staged data path (§3.4), and "Demystifying the Performance
of Data Transfers" shows the hash pinned to the wrong resource (the host
CPU) dominating end-to-end rates.  This kernel moves the digest onto the
accelerator: each grid step reduces a (tile, block) panel of uint32
words to one 32-bit lattice digest per block row, streaming at memory
bandwidth instead of host hash rate.

The digest is a weighted word sum with position-dependent odd weights
(multiplicative lattice hash): ``d = sum_j x_j * (2j+1) * GOLDEN mod
2^32``.  Odd weights are invertible mod 2^32, so swapping or zeroing a
word changes the digest; the mod-2^32 wraparound is the natural uint32
arithmetic on both the VPU and the jnp oracle, making the kernel's
output bit-identical to :func:`digest_ref` (asserted in
``tests/test_zero_copy.py`` in interpret mode, and by ``chip_smoke.py``
compiled on the TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: 2**32 / golden ratio, the classic multiplicative-hash constant; odd,
#: so every derived weight (2j+1)*GOLDEN is odd and invertible mod 2^32
GOLDEN = 0x9E3779B1


def _weights(shape: tuple[int, ...]) -> jax.Array:
    j = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    return (jnp.uint32(2) * j + jnp.uint32(1)) * jnp.uint32(GOLDEN)


def _digest_kernel(x_ref, d_ref):
    # Mosaic has no unsigned reductions: the words arrive bitcast to
    # int32, and two's-complement multiply and add wrap mod 2^32 exactly
    # as uint32 does, so the bits match the oracle's
    x = x_ref[...]                                   # (tile, block) int32
    j = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    w = (2 * j + 1) * jnp.int32(GOLDEN - (1 << 32))
    d_ref[...] = jnp.sum(x * w, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def block_digest(panels: jax.Array, *, tile: int = 8,
                 interpret: bool = False) -> jax.Array:
    """uint32 panels (nb, block) -> one uint32 lattice digest per block.

    ``nb`` must be a multiple of ``tile`` (callers zero-pad; a zero block
    digests to 0, which the item-level fold discards by slicing to the
    real block count)."""
    nb, block = panels.shape
    d = pl.pallas_call(
        _digest_kernel,
        grid=(nb // tile,),
        in_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1), jnp.int32),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(panels, jnp.int32))
    return jax.lax.bitcast_convert_type(d.reshape(nb), jnp.uint32)


@jax.jit
def digest_ref(panels: jax.Array) -> jax.Array:
    """jnp oracle for :func:`block_digest` — same lattice hash, pure XLA.

    The kernel is gated bit-exact against it, compiled on TPU and in
    interpret mode elsewhere.  Off the TPU the accel digest placement
    (:class:`repro.core.integrity.StreamDigest`) computes with this
    oracle, because the interpreted kernel is far slower and gives the
    same bits."""
    return jnp.sum(panels * _weights(panels.shape), axis=1,
                   dtype=jnp.uint32)
