"""Feed-forward blocks: SwiGLU MLP and Mixture-of-Experts.

Two production MoE data paths (chosen per arch by expert-count/mesh
divisibility — DESIGN.md section 5):

* :func:`moe_ep` — **expert parallelism** via ``shard_map``: tokens are
  sequence-split across the model axis, dispatched into per-expert
  capacity buffers by a sort-based router, exchanged with
  ``all_to_all`` over the model axis, computed on the owning shard, and
  all_to_all'd back.  This is the DeepSpeed-MoE/Tutel pattern; the
  collective volume it generates is a first-class flow of the drainage
  basin (an aggregation "tributary" converging on expert shards).
  Used when ``n_experts %% model_axis == 0`` (qwen3: 128 experts).

* :func:`moe_tp` — **tensor parallelism inside experts**: tokens are
  all-gathered across the model axis, every shard routes identically and
  computes all experts against its ``d_ff`` slice, and outputs return via
  ``psum_scatter``.  Megatron-style; used when the expert count does not
  divide the model axis (mixtral: 8 experts on a 16-wide axis).

:func:`moe_ref` is the dense no-drop oracle used by tests: with a
generous capacity factor the sparse paths must match it.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from .config import ModelConfig


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """SwiGLU MLP: (x W_g) SiLU * (x W_u) -> W_d."""
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


# ---------------------------------------------------------------------------
# Routing (shared by every MoE path)
# ---------------------------------------------------------------------------


def route(x: jax.Array, w_router: jax.Array, top_k: int
          ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top-k routing.  x: (T, D) -> (gates (T,k), experts (T,k) i32,
    probs (T,E) f32, logits f32)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return gate_vals, expert_idx, probs, logits


def aux_losses(probs: jax.Array, expert_idx: jax.Array, n_experts: int,
               logits: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Local (tokens-per-expert, prob-mass, z-loss) sums.  Callers must
    reduce count and mass SEPARATELY before multiplying: the global
    load-balance term is count_global x mass_global, and a per-shard
    sum of products is a different (biased) estimator."""
    one_hot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.float32)  # (T,k,E)
    tokens_per_expert = one_hot.sum(axis=(0, 1))        # (E,)
    prob_mass = probs.sum(axis=0)                       # (E,)
    z_num = jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return tokens_per_expert, prob_mass, z_num


def _local_dispatch(x: jax.Array, expert_idx: jax.Array, gates: jax.Array,
                    n_experts: int, capacity: int
                    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort-based capacity dispatch of local tokens into (E, C, D) buffers.

    Returns (buffer, sorted_experts, sorted_token_ids, sorted_positions,
    keep_mask) — the latter four drive the inverse combine.
    """
    t, d = x.shape
    k = expert_idx.shape[-1]
    e_flat = expert_idx.reshape(t * k)
    tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(e_flat, stable=True)
    se = e_flat[order]
    st = tok_flat[order]
    counts = jnp.bincount(se, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < capacity
    safe_pos = jnp.where(keep, pos, 0)
    buf = jnp.zeros((n_experts, capacity, d), x.dtype)
    contrib = jnp.where(keep[:, None], x[st], jnp.zeros_like(x[st]))
    buf = buf.at[se, safe_pos].add(contrib)
    return buf, se, st, safe_pos, keep


def _local_combine(y: jax.Array, se: jax.Array, st: jax.Array,
                   pos: jax.Array, keep: jax.Array, gates: jax.Array,
                   order_gates: jax.Array, t: int) -> jax.Array:
    """Inverse of :func:`_local_dispatch` with gate weighting."""
    gathered = y[se, pos]                       # (t*k, D)
    weighted = gathered * (order_gates * keep)[:, None].astype(y.dtype)
    out = jnp.zeros((t, y.shape[-1]), y.dtype)
    return out.at[st].add(weighted)


def _capacity(tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    return max(1, math.ceil(tokens * top_k * cf / n_experts))


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map + all_to_all)
# ---------------------------------------------------------------------------


def _token_axes(total_tokens: int, mesh: Mesh,
                batch_axes: tuple[str, ...], model_axis: str
                ) -> tuple[str, ...]:
    """Widest axis tuple that evenly divides the token count.  Decode
    shapes (a handful of tokens) degrade gracefully: tokens replicate over
    the axes they cannot split across (redundant-but-correct dispatch)."""
    full = batch_axes + (model_axis,)
    def prod(axes):
        out = 1
        for a in axes:
            out *= mesh.shape[a]
        return out
    if total_tokens % prod(full) == 0 and total_tokens >= prod(full):
        return full
    if total_tokens % prod(batch_axes) == 0 and total_tokens >= prod(batch_axes):
        return batch_axes
    return ()


def moe_ep(
    x: jax.Array,                 # (B, S, D)
    w_router: jax.Array,          # (D, E)
    w_gate: jax.Array,            # (E, D, F)
    w_up: jax.Array,              # (E, D, F)
    w_down: jax.Array,            # (E, F, D)
    *,
    cfg: ModelConfig,
    mesh: Mesh,
    batch_axes: tuple[str, ...],
    model_axis: str = "model",
    fsdp_axis: Optional[str] = "data",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Expert-parallel MoE layer.  Returns (y, lb_loss, z_loss)."""
    moe = cfg.moe
    B, S, D = x.shape
    ep = mesh.shape[model_axis]
    assert moe.n_experts % ep == 0, (moe.n_experts, ep)
    tok_axes = _token_axes(B * S, mesh, batch_axes, model_axis)
    tok_shards = 1
    for a in tok_axes:
        tok_shards *= mesh.shape[a]
    t_local = max(1, B * S // tok_shards)
    cap = _capacity(t_local, moe.top_k, moe.n_experts, moe.capacity_factor)
    total_tokens = float(B * S)

    fsdp = fsdp_axis if (fsdp_axis and mesh.shape.get(fsdp_axis, 1) > 1) else None

    def local(xl, wr, wg, wu, wd):
        # xl: (t_local, D) — tokens split over tok_axes (replicated on the
        # rest: decode shapes dispatch redundantly but correctly)
        if fsdp:
            wg = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp, axis=2, tiled=True)
        gates, eidx, probs, logits = route(xl, wr, moe.top_k)
        buf, se, st, pos, keep = _local_dispatch(
            xl, eidx, gates, moe.n_experts, cap)
        order_gates = gates.reshape(-1)[jnp.argsort(eidx.reshape(-1), stable=True)]
        # exchange: (E, C, D) -> (E/ep, C*ep, D) on the expert's owner
        recv = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                  concat_axis=1, tiled=True)
        g = jnp.einsum("ecd,edf->ecf", recv, wg)
        u = jnp.einsum("ecd,edf->ecf", recv, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(recv.dtype) * u
        yl = jnp.einsum("ecf,efd->ecd", h, wd)
        back = jax.lax.all_to_all(yl, model_axis, split_axis=1,
                                  concat_axis=0, tiled=True)
        out = _local_combine(back, se, st, pos, keep, gates, order_gates,
                             xl.shape[0])
        # aux losses: reduce count/mass over the token-split axes, then
        # combine (global estimator — see aux_losses docstring)
        counts, mass, z_num = aux_losses(probs, eidx, moe.n_experts, logits)
        if tok_axes:
            counts = jax.lax.psum(counts, tok_axes)
            mass = jax.lax.psum(mass, tok_axes)
            z_num = jax.lax.psum(z_num, tok_axes)
        lb = moe.n_experts * jnp.sum(counts * mass) / (
            total_tokens * total_tokens * moe.top_k)
        z = z_num / total_tokens
        return out, lb, z

    tok_spec = P(tok_axes if tok_axes else None, None)
    gate_up_spec = P(model_axis, fsdp, None)
    down_spec = P(model_axis, None, fsdp)
    y, lb, z = jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec, P(None, None), gate_up_spec, gate_up_spec, down_spec),
        out_specs=(tok_spec, P(), P()),
        check_vma=False,
    )(x.reshape(B * S, D), w_router, w_gate, w_up, w_down)
    return y.reshape(B, S, D), lb, z


# ---------------------------------------------------------------------------
# Tensor-parallel-experts path (all_gather + psum_scatter)
# ---------------------------------------------------------------------------


def moe_tp(
    x: jax.Array,                 # (B, S, D)
    w_router: jax.Array,          # (D, E)
    w_gate: jax.Array,            # (E, D, F)  — F sharded over model
    w_up: jax.Array,
    w_down: jax.Array,            # (E, F, D)
    *,
    cfg: ModelConfig,
    mesh: Mesh,
    batch_axes: tuple[str, ...],
    model_axis: str = "model",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """TP-inside-experts MoE (expert count need not divide the mesh)."""
    moe = cfg.moe
    B, S, D = x.shape
    m = mesh.shape[model_axis]
    tok_axes = _token_axes(B * S, mesh, batch_axes, model_axis)
    seq_split = model_axis in tok_axes
    tok_shards = 1
    for a in tok_axes:
        tok_shards *= mesh.shape[a]
    t_local = max(1, B * S // tok_shards)
    t_row = t_local * m if seq_split else t_local
    cap = _capacity(t_row, moe.top_k, moe.n_experts, moe.capacity_factor)
    total_tokens = float(B * S)
    row_axes = tuple(a for a in tok_axes if a != model_axis)

    def local(xl, wr, wg, wu, wd):
        # gather this data-row's tokens across the model axis (when split)
        xr = (jax.lax.all_gather(xl, model_axis, axis=0, tiled=True)
              if seq_split else xl)                    # (t_row, D)
        gates, eidx, probs, logits = route(xr, wr, moe.top_k)
        buf, se, st, pos, keep = _local_dispatch(xr, eidx, gates,
                                                 moe.n_experts, cap)
        order_gates = gates.reshape(-1)[jnp.argsort(eidx.reshape(-1), stable=True)]
        g = jnp.einsum("ecd,edf->ecf", buf, wg)      # F sliced over model
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
        y_part = jnp.einsum("ecf,efd->ecd", h, wd)   # partial over F slice
        out_row = _local_combine(y_part, se, st, pos, keep, gates,
                                 order_gates, t_row)
        if seq_split:
            out = jax.lax.psum_scatter(out_row, model_axis,
                                       scatter_dimension=0, tiled=True)
        else:
            out = jax.lax.psum(out_row, model_axis)
        counts, mass, z_num = aux_losses(probs, eidx, moe.n_experts, logits)
        if row_axes:
            counts = jax.lax.psum(counts, row_axes)
            mass = jax.lax.psum(mass, row_axes)
            z_num = jax.lax.psum(z_num, row_axes)
        lb = moe.n_experts * jnp.sum(counts * mass) / (
            total_tokens * total_tokens * moe.top_k)
        z = z_num / total_tokens
        return out, lb, z

    tok_spec = P(tok_axes if tok_axes else None, None)
    y, lb, z = jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec, P(None, None),
                  P(None, None, model_axis), P(None, None, model_axis),
                  P(None, model_axis, None)),
        out_specs=(tok_spec, P(), P()),
        check_vma=False,
    )(x.reshape(B * S, D), w_router, w_gate, w_up, w_down)
    return y.reshape(B, S, D), lb, z


# ---------------------------------------------------------------------------
# Dense oracle (tests / tiny shapes only)
# ---------------------------------------------------------------------------


def moe_ref(
    x: jax.Array, w_router: jax.Array, w_gate: jax.Array, w_up: jax.Array,
    w_down: jax.Array, *, cfg: ModelConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """No-drop dense-compute MoE: every expert on every token, masked.
    O(T*E*F) — the correctness oracle for the sparse paths."""
    moe = cfg.moe
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    gates, eidx, probs, logits = route(xt, w_router, moe.top_k)
    g = jnp.einsum("td,edf->tef", xt, w_gate)
    u = jnp.einsum("td,edf->tef", xt, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y_all = jnp.einsum("tef,efd->ted", h, w_down)           # (T, E, D)
    mask = jax.nn.one_hot(eidx, moe.n_experts, dtype=jnp.float32)  # (T,k,E)
    w = (mask * gates[..., None]).sum(axis=1)               # (T, E)
    y = jnp.einsum("ted,te->td", y_all.astype(jnp.float32), w).astype(x.dtype)
    counts, mass, z_num = aux_losses(probs, eidx, moe.n_experts, logits)
    total = float(B * S)
    lb = moe.n_experts * jnp.sum(counts * mass) / (total * total * moe.top_k)
    z = z_num / total
    return y.reshape(B, S, D), lb, z


def choose_moe_impl(cfg: ModelConfig, mesh: Mesh, model_axis: str = "model") -> str:
    """EP when experts divide the model axis, else TP-inside-experts."""
    m = mesh.shape.get(model_axis, 1)
    if cfg.moe and cfg.moe.n_experts % m == 0:
        return "ep"
    return "tp"
