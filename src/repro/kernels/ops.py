"""Jitted public wrappers for the Pallas kernels — the one backend seam.

Model code calls a prefill's SSD and a decode's state update where
:func:`use_kernel` says so (the flash and decode-attention kernels have no
model caller yet), and the integrity layer calls :func:`block_digest`.
:func:`on_tpu` alone decides how a kernel runs: compiled by Mosaic on a
TPU backend, in interpret mode (kernel bodies run as Python over numpy,
for correctness) on any other.
Layout conversions between the model's (B, S, H, hd) convention and the
kernels' (B, H, S, hd) happen here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_bhd
from .digest import block_digest as _block_digest
from .flash_attention import flash_attention_bhsd
from .quantize import dequantize_int8, quantize_int8
from .ssd_scan import ssd_scan_bhsd
from .ssm_update import ssm_update_stacked


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_kernel(mesh) -> bool:
    """Whether model code runs a Pallas kernel where it has one: on a TPU,
    and not under a mesh, over which XLA cannot partition a kernel's
    custom call (it would gather the operands to every device)."""
    return on_tpu() and mesh is None


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None) -> jax.Array:
    """(B, S, H, hd) layout in/out."""
    if q.shape[1] % 128 != 0 or k.shape[1] % 128 != 0:
        raise NotImplementedError("flash kernel needs seq % 128 == 0")
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    ot = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                              interpret=not on_tpu(), scale=scale)
    return jnp.swapaxes(ot, 1, 2)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_pos: jax.Array, q_pos: jax.Array, *,
                     window: int = 0) -> jax.Array:
    """q: (B, 1, H, hd); k/v: (B, S, Hkv, hd) caches -> (B, 1, H, hd)."""
    qt = jnp.swapaxes(q, 1, 2)[:, :, 0]         # (B, H, hd)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = decode_attention_bhd(qt, kt, vt, k_pos, q_pos, window=window,
                             interpret=not on_tpu())
    return o[:, None].swapaxes(1, 1).reshape(q.shape)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 256) -> tuple[jax.Array, jax.Array]:
    """Model layout: x (B, S, H, P); dt (B, S, H); Bm/Cm (B, S, G, N) ->
    (y (B, S, H, P), final state (B, H, P, N) f32)."""
    xt = jnp.moveaxis(x, 2, 1)
    dtt = jnp.moveaxis(dt, 2, 1)
    Bt = jnp.moveaxis(Bm, 2, 1)
    Ct = jnp.moveaxis(Cm, 2, 1)
    y, final = ssd_scan_bhsd(xt, dtt.astype(jnp.float32),
                             A.astype(jnp.float32), Bt, Ct, chunk=chunk,
                             interpret=not on_tpu())
    return jnp.moveaxis(y, 1, 2), final


def ssm_update(states: jax.Array, l: jax.Array, x: jax.Array, dt: jax.Array,
               A: jax.Array, Bm: jax.Array, Cm: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """One-token SSD step of layer ``l`` in the stacked f32 states
    (L, B, H, P, N), in place: x (B, H, P); dt (B, H); Bm/Cm (B, G, N) ->
    (y (B, H, P), states)."""
    return ssm_update_stacked(states, l, x, dt.astype(jnp.float32),
                              A.astype(jnp.float32), Bm, Cm,
                              interpret=not on_tpu())


def quantize(x: jax.Array, *, block: int = 256):
    return quantize_int8(x, block=block, interpret=not on_tpu())


def dequantize(q: jax.Array, s: jax.Array, shape: tuple[int, ...]):
    return dequantize_int8(q, s, shape, interpret=not on_tpu())


#: panel rows per digest grid step (one sublane tile)
DIGEST_TILE = 8


def block_digest(panels: jax.Array) -> jax.Array:
    """uint32 panels (nb, block) -> one lattice digest per block row; rows
    zero-pad up to a multiple of ``DIGEST_TILE`` and the pad's digests
    drop."""
    nb = panels.shape[0]
    pad = (-nb) % DIGEST_TILE
    if pad:
        panels = jnp.pad(jnp.asarray(panels), ((0, pad), (0, 0)))
    return _block_digest(panels, tile=DIGEST_TILE,
                         interpret=not on_tpu())[:nb]
