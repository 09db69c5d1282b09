"""Collective schedules: compressed and hierarchical gradient exchange.

The paper's §3.2 finding transposed to ICI/DCN: the *transport algorithm*
(CCA there, collective schedule here) matters less than path balance —
but when a path **is** collective-bound (the cross-pod DCN hop), reducing
bytes on the wire is the lever.  Two tools:

* :func:`compressed_psum` — int8 block-quantized all-reduce: a
  reduce-scatter-shaped ``all_to_all`` of int8 chunks, local fp32
  reduction, then an int8 ``all_gather`` of results.  Wire bytes are
  ~ ``(2 (g-1)/g) * 1 B/elem`` vs ``(2 (g-1)/g) * 2 B/elem`` for a bf16
  ring all-reduce — a 2x (4x vs fp32) cut on the dominant term.
  Deterministic, so it composes exactly with error feedback
  (optim/compression.py).

* :func:`hierarchical_psum` — reduce-scatter intra-pod (cheap ICI),
  exchange only shards across pods (expensive DCN), all-gather intra-pod.
  Cross-pod traffic drops by the pod size (16x here).

Both run inside ``shard_map`` (manual-collective regions embedded in the
auto-sharded program, like the MoE paths).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.optim.compression import (dequantize_int8_blockwise,
                                     quantize_int8_blockwise)


def compressed_psum(x: jax.Array, axis_name: str, *, block: int = 256
                    ) -> jax.Array:
    """int8-wire psum over ``axis_name`` (call inside shard_map).

    Algorithm (g = axis size):
      1. quantize local tensor blockwise -> (q int8, scales f32)
      2. all_to_all chunk exchange: device i receives chunk i of every
         peer's q (reduce-scatter data movement, int8 on the wire)
      3. local fp32 dequant + sum of the g received chunks
      4. re-quantize the reduced chunk; all_gather int8 + scales
      5. dequant -> full reduced tensor
    """
    g = jax.lax.axis_size(axis_name)
    if g == 1:
        return x
    orig_shape, orig_dtype = x.shape, x.dtype
    q, s = quantize_int8_blockwise(x, block)          # (nb, block), (nb,)
    nb = q.shape[0]
    pad_blocks = (-nb) % g
    if pad_blocks:
        q = jnp.pad(q, ((0, pad_blocks), (0, 0)))
        s = jnp.pad(s, (0, pad_blocks))
    nb_p = q.shape[0]
    # 2. exchange: split blocks axis into g chunks, one per peer
    q_recv = jax.lax.all_to_all(q.reshape(g, nb_p // g, block), axis_name,
                                split_axis=0, concat_axis=0, tiled=False)
    s_recv = jax.lax.all_to_all(s.reshape(g, nb_p // g), axis_name,
                                split_axis=0, concat_axis=0, tiled=False)
    # q_recv: (g, nb_p/g, block) — peer-major chunks of my shard
    chunk = (q_recv.astype(jnp.float32) * s_recv[..., None]).sum(axis=0)
    # 4. requantize the reduced shard and gather all shards
    qr, sr = quantize_int8_blockwise(chunk, block)
    q_all = jax.lax.all_gather(qr, axis_name, axis=0, tiled=True)
    s_all = jax.lax.all_gather(sr, axis_name, axis=0, tiled=True)
    flat = (q_all.astype(jnp.float32) * s_all[:, None]).reshape(-1)
    n = 1
    for d in orig_shape:
        n *= d
    return flat[:n].reshape(orig_shape).astype(orig_dtype)


def hierarchical_psum(x: jax.Array, *, intra_axis: str, inter_axis: str,
                      compress_inter: bool = False, block: int = 256
                      ) -> jax.Array:
    """Two-level all-reduce (call inside shard_map).

    reduce-scatter over ``intra_axis`` (ICI), psum the shard over
    ``inter_axis`` (DCN; optionally int8-compressed), all-gather back over
    ``intra_axis``.
    """
    g = jax.lax.axis_size(intra_axis)
    flat = x.reshape(-1)
    pad = (-flat.size) % g
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = jax.lax.psum_scatter(flat.reshape(g, -1), intra_axis,
                                 scatter_dimension=0, tiled=False)
    if compress_inter:
        shard = compressed_psum(shard, inter_axis, block=block)
    else:
        shard = jax.lax.psum(shard, inter_axis)
    full = jax.lax.all_gather(shard, intra_axis, axis=0, tiled=False)
    out = full.reshape(-1)[: x.size].reshape(x.shape)
    return out.astype(x.dtype)
