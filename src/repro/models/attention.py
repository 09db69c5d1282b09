"""Attention: GQA with causal / sliding-window / local:global masking.

One implementation covers every assigned pattern:

* full causal (phi3, smollm, mistral-large, qwen3, llava, seamless-dec),
* sliding window (mixtral, window=4096),
* 5:1 local:global interleave (gemma3 — per-layer window passed as data
  through the layer scan, so the stacked-layer scan stays homogeneous),
* bidirectional (seamless encoder), cross-attention (seamless decoder),
* single-query decode against a (possibly ring) KV cache that it only
  reads (``attention_decode``).

Positions are explicit everywhere: a KV slot with position < 0 is invalid
(empty ring-buffer slot).  Window masking is relative: key valid iff
``q_pos - window < k_pos <= q_pos`` (window == 0 means unbounded), which
makes ring-buffer caches correct without any index shuffling.

``impl="pallas"`` routes the train/prefill path through the Pallas flash
kernel (kernels/flash_attention.py); ``"ref"`` is the pure-jnp oracle the
kernel is validated against.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _build_mask(
    q_pos: jax.Array,        # (B?, Sq) or (Sq,)
    k_pos: jax.Array,        # (B?, Sk) or (Sk,)
    *,
    causal: bool,
    window: int | jax.Array = 0,
) -> jax.Array:
    """Boolean keep-mask broadcastable to (..., Sq, Sk)."""
    qp = q_pos[..., :, None].astype(jnp.int32)
    kp = k_pos[..., None, :].astype(jnp.int32)
    keep = kp >= 0
    if causal:
        keep = jnp.logical_and(keep, kp <= qp)
    # window as traced scalar supports per-layer windows through scan
    w = jnp.asarray(window, jnp.int32)
    keep = jnp.logical_and(keep, jnp.where(w > 0, kp > qp - w, True))
    return keep


# score tensors above this many elements trigger query-chunked evaluation
# (bounds the live (Sq x Sk) softmax workspace — the pure-jnp analogue of
# flash attention's tiling; the Pallas kernel does this in VMEM natively)
ATTN_CHUNK_ELEMS = 1 << 22


def _attn_core(q, k, v, *, q_pos, k_pos, causal, window, scale=None
               ) -> jax.Array:
    # named_scope tags every op in here as belonging to a region a fused
    # flash-attention kernel replaces on TPU: core/fidelity.py separates
    # these bytes so the roofline can report raw vs. kernel-fused memory
    # traffic (the Pallas kernel in kernels/flash_attention.py is the
    # fused implementation; this is its oracle).
    with jax.named_scope("flashable_attention"):
        B, Sq, Hq, hd = q.shape
        _, Sk, Hkv, _ = k.shape
        assert Hq % Hkv == 0, (Hq, Hkv)
        G = Hq // Hkv
        qg = q.reshape(B, Sq, Hkv, G, hd)
        scale = hd ** -0.5 if scale is None else scale
        # mixed-precision dot: bf16 operands, f32 accumulation — native on
        # the TPU MXU (avoids materializing f32 casts of the K cache)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                            preferred_element_type=jnp.float32) * scale
        mask = _build_mask(q_pos, k_pos, causal=causal, window=window)
        # mask broadcast: (.., Sq, Sk) -> (B?, 1, 1, Sq, Sk)
        while mask.ndim < scores.ndim:
            mask = mask[..., None, :, :] if mask.ndim >= 2 else mask
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        return out.reshape(B, Sq, Hq, hd)


def attention(
    q: jax.Array,            # (B, Sq, Hq, hd)
    k: jax.Array,            # (B, Sk, Hkv, hd)
    v: jax.Array,            # (B, Sk, Hkv, hd)
    *,
    q_pos: jax.Array,        # (Sq,) or (B, Sq)
    k_pos: jax.Array,        # (Sk,) or (B, Sk)
    causal: bool = True,
    window: int | jax.Array = 0,
    impl: str = "ref",
    q_chunk: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Grouped-query attention; returns (B, Sq, Hq, hd).  ``scale``
    multiplies the scores: None is 1 / sqrt(hd).

    q_chunk: None = auto (chunk when the score workspace is large),
    0 = never chunk (caller bounds memory another way, e.g. sequence-
    parallel sharding), >0 = explicit chunk length.
    """
    if impl == "pallas":
        out = _try_pallas(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                          window=window, scale=scale)
        if out is not None:
            return out
    B, Sq, Hq, hd = q.shape
    Sk = k.shape[1]
    if (q_chunk == 0 or q_pos.ndim != 1
            or (q_chunk is None and Sq * Sk <= ATTN_CHUNK_ELEMS)):
        return _attn_core(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                          window=window, scale=scale)
    # query-chunked evaluation: scan over Sq blocks; the body is
    # checkpointed so backward recomputes each block's scores instead of
    # saving the full (Sq, Sk) probability tensor.
    if q_chunk is None:
        q_chunk = max(128, ATTN_CHUNK_ELEMS // Sk)
    while Sq % q_chunk:
        q_chunk //= 2
    nq = Sq // q_chunk
    qc = jnp.moveaxis(q.reshape(B, nq, q_chunk, Hq, hd), 1, 0)
    qpc = q_pos.reshape(nq, q_chunk)

    @jax.checkpoint
    def body(_, inp):
        qi, qpi = inp
        return None, _attn_core(qi, k, v, q_pos=qpi, k_pos=k_pos,
                                causal=causal, window=window, scale=scale)

    _, outs = jax.lax.scan(body, None, (qc, qpc))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Hq, hd)


def attention_decode(
    q: jax.Array,            # (B, 1, Hq, hd)
    k_cache: jax.Array,      # (B, Sk, Hkv * hd)
    v_cache: jax.Array,      # (B, Sk, Hkv * hd)
    k_new: jax.Array,        # (B, 1, Hkv, hd)
    v_new: jax.Array,        # (B, 1, Hkv, hd)
    *,
    q_pos: jax.Array,        # (1,)
    k_pos: jax.Array,        # (Sk,) cache slot positions, -1 invalid
    window: int | jax.Array = 0,
    scale: float | None = None,
) -> jax.Array:
    """Single-query decode attention over a cache it only reads, with the
    step's own key and value as one more column of the same softmax;
    returns (B, 1, Hq, hd).

    ``k_pos`` must mark invalid whatever the step would overwrite: then
    the keys and mask are those of writing the step into the cache and
    attending to it, so the caller can store ``k_new``/``v_new`` for every
    layer in one write.  The cache keeps each position's heads flat, so
    that write is one contiguous row per sequence whatever ``hd``; the
    queries enter as a block-diagonal (Hkv * hd, Hq) matrix, and each
    head's output is its own block of P.V, so the cache is read as
    stored, never relaid out.  That costs Hkv times the two products'
    FLOPs: Hq FLOPs per cache byte read.  Both P.V products accumulate in
    f32 and round once, as one einsum would.
    """
    with jax.named_scope("flashable_attention"):
        B, _, Hq, hd = q.shape
        Hkv = k_new.shape[2]
        G = Hq // Hkv
        qg = q.reshape(B, Hkv, G, hd)
        # an exact product (each term is q times 1 or 0)
        q_bd = jnp.einsum("bhgd,hk->bkdhg", qg, jnp.eye(Hkv, dtype=q.dtype)
                          ).reshape(B, Hkv * hd, Hq)
        k_new = k_new.reshape(B, Hkv, hd).astype(k_cache.dtype)
        v_new = v_new.reshape(B, Hkv, hd).astype(v_cache.dtype)
        scale = hd ** -0.5 if scale is None else scale
        s_cache = jnp.einsum("bsc,bcj->bjs", k_cache, q_bd,
                             preferred_element_type=jnp.float32) * scale
        mask = _build_mask(q_pos, k_pos, causal=True, window=window)
        s_cache = jnp.where(mask, s_cache, NEG_INF)
        s_new = jnp.einsum("bhgd,bhd->bhg", qg, k_new,
                           preferred_element_type=jnp.float32
                           ).reshape(B, Hq, 1) * scale
        m = jnp.maximum(jnp.max(s_cache, axis=-1, keepdims=True), s_new)
        e_cache = jnp.exp(s_cache - m)
        e_new = jnp.exp(s_new - m)
        denom = jnp.sum(e_cache, axis=-1, keepdims=True) + e_new
        pv = jnp.einsum("bjs,bsc->bjc", (e_cache / denom).astype(q.dtype),
                        v_cache, preferred_element_type=jnp.float32)
        # each head's own block, selected exactly (one nonzero term)
        diag = jnp.eye(Hkv, dtype=bool)[:, None, :, None]
        out = jnp.where(diag, pv.reshape(B, Hkv, G, Hkv, hd), 0.).sum(axis=3)
        out = out + jnp.einsum(
            "bhg,bhd->bhgd",
            (e_new / denom).astype(q.dtype).reshape(B, Hkv, G), v_new,
            preferred_element_type=jnp.float32)
        return out.astype(q.dtype).reshape(B, 1, Hq, hd)


def _try_pallas(q, k, v, *, q_pos, k_pos, causal, window, scale
                ) -> Optional[jax.Array]:
    """Route to the Pallas flash kernel when the shape regime fits it
    (train/prefill: Sq == Sk a multiple of 128, a static window); any
    other regime takes the reference.  A kernel error propagates."""
    if q.shape[1] != k.shape[1] or q.shape[1] % 128:
        return None
    if isinstance(window, jax.Array):
        return None                  # per-layer window traced through scan
    from repro.kernels import ops as kops
    return kops.flash_attention(q, k, v, causal=causal, window=int(window),
                                scale=scale)


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------


def cache_update_full(k_cache: jax.Array, v_cache: jax.Array,
                      k_new: jax.Array, v_new: jax.Array,
                      pos: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Write step-`pos` K/V into a full-length cache (B, S_max, Hkv, hd)."""
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
    return k_cache, v_cache


def cache_positions_full(s_max: int, pos: jax.Array) -> jax.Array:
    """Absolute positions of full-cache slots; > pos slots invalid (-1)."""
    idx = jnp.arange(s_max, dtype=jnp.int32)
    return jnp.where(idx <= pos, idx, -1)


def cache_positions_ring(window: int, pos: jax.Array) -> jax.Array:
    """Absolute position held by each ring slot after writing step `pos`.

    Slot j holds the largest p <= pos with p === j (mod window); slots that
    would be negative are invalid (-1).
    """
    j = jnp.arange(window, dtype=jnp.int32)
    p = pos - jnp.mod(pos - j, window)
    return jnp.where(p >= 0, p, -1)
