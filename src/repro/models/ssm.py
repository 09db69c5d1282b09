"""Mamba2 / SSD (state-space duality) blocks [arXiv:2405.21060].

The SSD layer computes, per head h with scalar decay ``a_t = exp(dt_t A)``:

    state_t = a_t * state_{t-1} + dt_t * B_t x_t^T        (N x P state)
    y_t     = C_t . state_t + D * x_t

Training uses the chunked SSD algorithm: the sequence splits into chunks
of length Q; within a chunk the dual quadratic (attention-like) form is
used, and a single inter-chunk recurrence over ``S/Q`` steps carries the
state — O(S Q) work, sub-quadratic in S, and TPU-friendly (the intra-chunk
form is batched matmuls on the MXU).  ``repro.kernels.ssd_scan`` holds the
Pallas kernel of the whole chunked scan; ``ssd_chunked`` here is the
pure-jnp implementation the kernel is validated against.  A prefill on a
TPU runs the kernel; training runs ``ssd_chunked``, since the kernel has
no backward pass.

Decode is O(1) in sequence length: one multiply-accumulate against the
(H, P, N) state — this is why the ssm/hybrid archs run the ``long_500k``
cell that pure-attention archs skip.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

from .config import ModelConfig


class MambaState(NamedTuple):
    conv: jax.Array         # (B, conv_width-1, conv_dim) rolling conv input (f32)
    ssm: jax.Array          # (B, H, P, N) recurrent state (f32)


def _split_proj(cfg: ModelConfig, zxbcdt: jax.Array):
    s = cfg.ssm
    d_in, H = cfg.d_inner, cfg.ssm_heads
    gn = s.n_groups * s.d_state
    z, x, B, C, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + gn, 2 * d_in + 2 * gn], axis=-1)
    return z, x, B, C, dt


def _dt_activation(dt: jax.Array, dt_bias: jax.Array) -> jax.Array:
    return jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))


def _gated_norm(y: jax.Array, z: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Mamba2's gated RMSNorm: norm(y * silu(z)) * w."""
    y32 = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(y32), axis=-1, keepdims=True)
    return (y32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(y.dtype)


# ---------------------------------------------------------------------------
# Chunked SSD (training / prefill)
# ---------------------------------------------------------------------------


def ssd_chunked(
    x: jax.Array,       # (B, S, H, P)
    dt: jax.Array,      # (B, S, H) — post-softplus, f32
    A: jax.Array,       # (H,) negative, f32
    Bm: jax.Array,      # (B, S, G, N)
    Cm: jax.Array,      # (B, S, G, N)
    chunk: int,
    *,
    initial_state: jax.Array | None = None,   # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    B_, S, H, Pd = x.shape
    G, N = Bm.shape[-2], Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rep = H // G

    xc = x.reshape(B_, nc, chunk, H, Pd)
    dtc = dt.reshape(B_, nc, chunk, H)
    Bc = Bm.reshape(B_, nc, chunk, G, N)
    Cc = Cm.reshape(B_, nc, chunk, G, N)

    dA = dtc * A[None, None, None, :]                     # (B,nc,Q,H) negatives
    cum = jnp.cumsum(dA, axis=2)                          # inclusive cumsum
    total = cum[:, :, -1, :]                              # (B,nc,H)

    # intra-chunk (dual quadratic form): L[i,j] = exp(cum_i - cum_j) * dt_j,
    # j <= i.  The exponent is formed only there: above the diagonal it
    # grows to hundreds, and exp's gradient would be inf times the mask's 0
    li = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # (B,nc,Q,Q,H)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    L = jnp.exp(jnp.where(mask, li, -jnp.inf))
    L = L * dtc[:, :, None, :, :]                             # x dt_j
    # scores_ij = C_i . B_j (group-shared across rep heads)
    CB = jnp.einsum("bnigx,bnjgx->bnijg", Cc.astype(jnp.float32),
                    Bc.astype(jnp.float32))                   # (B,nc,Q,Q,G)
    CB = jnp.repeat(CB, rep, axis=-1)                         # (B,nc,Q,Q,H)
    W = CB * L                                                # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bnijh,bnjhp->bnihp", W, xc.astype(jnp.float32))

    # chunk states: S_c = sum_j exp(total - cum_j) dt_j B_j x_j^T
    decay_to_end = jnp.exp(total[:, :, None, :] - cum)    # (B,nc,Q,H)
    wdt = decay_to_end * dtc                              # (B,nc,Q,H)
    Bh = jnp.repeat(Bc, rep, axis=-2)                     # (B,nc,Q,H,N)
    Sc = jnp.einsum("bcqh,bcqhn,bcqhp->bchpn",
                    wdt, Bh.astype(jnp.float32), xc.astype(jnp.float32))

    # inter-chunk recurrence over nc (sequential scan over chunk states)
    chunk_decay = jnp.exp(total)                          # (B,nc,H)
    init = (initial_state.astype(jnp.float32) if initial_state is not None
            else jnp.zeros((B_, H, Pd, N), jnp.float32))

    def step(state, inp):
        dec, s_c = inp                                    # (B,H), (B,H,P,N)
        new = state * dec[:, :, None, None] + s_c
        return new, state                                 # emit state *entering* chunk

    final, entering = jax.lax.scan(
        step, init,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(Sc, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)               # (B,nc,H,P,N)

    # inter-chunk contribution: y_i += C_i exp(cum_i) . state_entering
    Ch = jnp.repeat(Cc, rep, axis=-2)                     # (B,nc,Q,H,N)
    decay_in = jnp.exp(cum)                               # (B,nc,Q,H)
    y_inter = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp",
                         Ch.astype(jnp.float32), entering, decay_in)

    y = (y_intra + y_inter).reshape(B_, S, H, Pd)
    return y.astype(x.dtype), final


def ssd_decode_step(
    x: jax.Array,       # (B, H, P)
    dt: jax.Array,      # (B, H) f32 (post-softplus)
    A: jax.Array,       # (H,)
    Bm: jax.Array,      # (B, G, N)
    Cm: jax.Array,      # (B, G, N)
    state: jax.Array,   # (B, H, P, N) f32
) -> tuple[jax.Array, jax.Array]:
    """One-token SSD update (O(1) in sequence length)."""
    H = x.shape[1]
    G = Bm.shape[1]
    rep = H // G
    dec = jnp.exp(dt * A[None, :])                        # (B,H)
    Bh = jnp.repeat(Bm, rep, axis=1).astype(jnp.float32)  # (B,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1).astype(jnp.float32)
    # elementwise in f32: as a contraction the state would be rounded to
    # the matrix unit's bf16 on a TPU at the default precision
    upd = (dt[:, :, None, None] * x.astype(jnp.float32)[..., None]
           * Bh[:, :, None, :])
    new_state = state * dec[:, :, None, None] + upd
    y = jnp.sum(new_state * Ch[:, :, None, :], axis=-1)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Full Mamba2 block (projections + conv + SSD + gate)
# ---------------------------------------------------------------------------


def _conv(taps: list[jax.Array], w: jax.Array, b: jax.Array) -> jax.Array:
    """silu of the causal depthwise conv, taps oldest first, elementwise
    in f32: on a TPU an f32 contraction would round its operands to the
    matrix unit's bf16 at the default precision."""
    acc = b.astype(jnp.float32)
    for i, tap in enumerate(taps):
        acc = acc + tap.astype(jnp.float32) * w[i].astype(jnp.float32)
    return jax.nn.silu(acc)


def mamba_block_train(x: jax.Array, p: dict, cfg: ModelConfig,
                      *, shard_heads=None, return_state: bool = False):
    """(B, S, D) -> (B, S, D)  [or (y, MambaState) with return_state].

    With ``return_state`` (a prefill) the SSD runs the Pallas kernel on a
    TPU; otherwise, and for training, ``ssd_chunked``."""
    s = cfg.ssm
    Bsz, S, D = x.shape
    H, Pd, N, G = cfg.ssm_heads, s.head_dim, s.d_state, s.n_groups
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xin, Bm, Cm, dt = _split_proj(cfg, zxbcdt)

    # causal depthwise conv over (x, B, C)
    xbc_raw = jnp.concatenate([xin, Bm, Cm], axis=-1)     # (B,S,conv_dim)
    pad = jnp.pad(xbc_raw, ((0, 0), (s.conv_width - 1, 0), (0, 0)))
    xbc = _conv([pad[:, i:i + S] for i in range(s.conv_width)],
                p["conv_w"], p["conv_b"]).astype(x.dtype)
    xin, Bm, Cm = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + G * N], axis=-1)

    xh = xin.reshape(Bsz, S, H, Pd)
    if shard_heads is not None:
        xh = shard_heads(xh)
    Bg = Bm.reshape(Bsz, S, G, N)
    Cg = Cm.reshape(Bsz, S, G, N)
    dtf = _dt_activation(dt, p["dt_bias"])                   # (B,S,H) f32
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    # the whole SSD, chunk states included, whichever implementation runs;
    # a sequence that ends inside a chunk is padded with dt = 0, which
    # leaves the state as the last real position left it
    with jax.named_scope("flashable_ssd"):
        pad = (-S) % s.chunk
        args = [jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                for a in (xh, dtf, Bg, Cg)] if pad else [xh, dtf, Bg, Cg]
        if return_state and kops.on_tpu():
            y, final_state = kops.ssd_scan(args[0], args[1], A, *args[2:],
                                           chunk=s.chunk)
        else:
            y, final_state = ssd_chunked(args[0], args[1], A, *args[2:],
                                         s.chunk)
        y = y[:, :S]
    y = y + xh * p["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(Bsz, S, cfg.d_inner)
    y = _gated_norm(y, z, p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    if return_state:
        conv_state = xbc_raw[:, S - (s.conv_width - 1):, :].astype(jnp.float32)
        return out, MambaState(conv=conv_state, ssm=final_state)
    return out


def mamba_block_decode(x: jax.Array, p: dict, cfg: ModelConfig,
                       state: MambaState) -> tuple[jax.Array, MambaState]:
    """(B, 1, D) one-token step with rolling conv + SSM state."""
    s = cfg.ssm
    Bsz = x.shape[0]
    H, Pd, N, G = cfg.ssm_heads, s.head_dim, s.d_state, s.n_groups
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])[:, 0]
    z, xin, Bm, Cm, dt = _split_proj(cfg, zxbcdt)

    # the step's conv and state update: what reads and writes the state
    with jax.named_scope("ssm_step"):
        xbc_new = jnp.concatenate([xin, Bm, Cm], axis=-1)     # (B, conv_dim)
        conv_in = jnp.concatenate([state.conv, xbc_new[:, None, :]], axis=1)
        xbc = _conv([conv_in[:, i] for i in range(s.conv_width)],
                    p["conv_w"], p["conv_b"]).astype(x.dtype)
        new_conv = conv_in[:, 1:, :]

        xin, Bm, Cm = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + G * N],
                                axis=-1)
        xh = xin.reshape(Bsz, H, Pd)
        Bg = Bm.reshape(Bsz, G, N)
        Cg = Cm.reshape(Bsz, G, N)
        dtf = _dt_activation(dt, p["dt_bias"])                   # (B,H)
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        y, new_ssm = ssd_decode_step(xh, dtf, A, Bg, Cg, state.ssm)
    y = y + xh * p["D"].astype(x.dtype)[None, :, None]
    y = y.reshape(Bsz, cfg.d_inner)
    y = _gated_norm(y, z, p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y, p["out_proj"])[:, None, :]
    return out, MambaState(conv=new_conv, ssm=new_ssm)


def init_mamba_state(cfg: ModelConfig, batch: int) -> MambaState:
    s = cfg.ssm
    return MambaState(
        # f32, as the SSM state: the conv's inputs exactly, whatever the
        # weights' type, at a fortieth of the SSM state's bytes
        conv=jnp.zeros((batch, s.conv_width - 1, cfg.conv_dim), jnp.float32),
        ssm=jnp.zeros((batch, cfg.ssm_heads, s.head_dim, s.d_state), jnp.float32),
    )

