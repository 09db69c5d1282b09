"""Step factories: build the jitted train/prefill/serve steps for an
(arch x mesh x plan) combination, with shardings and donation wired.

These are shared by the trainer, the server, and the dry-run — the
dry-run lowers exactly what production would execute.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.codesign import CodesignPlan
from repro.models.api import ModelApi, ShapeSpec
from repro.models.blocks import ShardCtx, kv_cache_spec
from repro.optim.adamw import AdamWState, adamw_init, adamw_update, warmup_cosine
from repro.parallel.sharding import batch_axes_of, param_shardings


@dataclasses.dataclass(frozen=True)
class StepBundle:
    """Everything the driver needs for one configuration."""

    api: ModelApi
    mesh: Mesh
    ctx: ShardCtx
    plan: CodesignPlan
    param_sharding: Any            # tree of NamedSharding
    state_sharding: Any            # for AdamWState
    train_step: Any                # jitted (params, opt, batch) -> ...
    serve_step: Optional[Any] = None
    prefill_step: Optional[Any] = None


def make_ctx(api: ModelApi, mesh: Optional[Mesh], plan: CodesignPlan
             ) -> ShardCtx:
    axes = batch_axes_of(mesh) if mesh is not None else ("data",)
    return ShardCtx(mesh=mesh, batch_axes=axes, model_axis="model",
                    seq_parallel=plan.seq_parallel)


def abstract_params(api: ModelApi) -> Any:
    return jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))


def make_train_step(api: ModelApi, mesh: Mesh, plan: CodesignPlan,
                    *, lr_peak: float = 3e-4, warmup: int = 100,
                    total_steps: int = 10000):
    """Returns (jitted train_step, param_shardings, state_shardings, ctx).

    train_step(params, opt_state, batch) -> (params', opt_state', metrics)
    — full forward+backward+AdamW update (what the dry-run compiles).
    """
    cfg = api.cfg
    ctx = make_ctx(api, mesh, plan)
    fsdp = plan.sharding in ("fsdp", "fsdp_tp")

    p_abs = abstract_params(api)
    p_shard = param_shardings(p_abs, cfg, mesh, fsdp=fsdp)
    s_abs = jax.eval_shape(adamw_init, p_abs)
    s_shard = param_shardings(s_abs, cfg, mesh, fsdp=fsdp)

    def loss_fn(params, batch):
        loss, aux = api.loss(params, batch, ctx)
        return loss, aux

    def step(params, opt_state, batch):
        if plan.microbatches > 1:
            grads, (loss, aux) = _accumulated_grads(
                loss_fn, params, batch, plan.microbatches)
        else:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        # step counter is pre-increment: schedule on step+1 so the very
        # first update trains at a nonzero warmup rate
        lr = warmup_cosine(opt_state.step + 1, peak_lr=lr_peak,
                           warmup=warmup, total=total_steps)
        params, opt_state, om = adamw_update(grads, opt_state, params, lr=lr)
        metrics = {"loss": loss, **{k: v for k, v in aux.items()}, **om}
        return params, opt_state, metrics

    batch_shard = _batch_shardings(api, mesh)
    jitted = jax.jit(
        step,
        in_shardings=(p_shard, s_shard, batch_shard),
        out_shardings=(p_shard, s_shard, None),
        donate_argnums=(0, 1),
    )
    return jitted, p_shard, s_shard, ctx


def _accumulated_grads(loss_fn, params, batch, n_micro: int):
    """Gradient accumulation over microbatches (lax.scan over splits)."""

    def split(v):
        b = v.shape[0]
        return v.reshape(n_micro, b // n_micro, *v.shape[1:])

    micro = jax.tree.map(split, batch)
    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, mb):
        acc, loss_sum = carry
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc, g)
        return (acc, loss_sum + loss), aux

    (acc, loss_sum), auxs = jax.lax.scan(body, (zero_g, 0.0), micro)
    grads = jax.tree.map(lambda a: a / n_micro, acc)
    aux = jax.tree.map(lambda a: a[-1], auxs)
    return grads, (loss_sum / n_micro, aux)


def _batch_shardings(api: ModelApi, mesh: Mesh) -> Any:
    axes = batch_axes_of(mesh)
    spec = api.train_input_specs(
        ShapeSpec("probe", 8, 8, "train"))   # structure only

    def shard(v):
        return NamedSharding(mesh, P(axes, *([None] * (len(v.shape) - 1))))

    return jax.tree.map(shard, spec)


def make_serve_step(api: ModelApi, mesh: Mesh, plan: CodesignPlan,
                    shape: ShapeSpec):
    """Returns (jitted serve_step, cache_shardings, ctx).

    serve_step(params, cache, tokens) -> (logits, cache') — one decode
    token against a seq_len-deep cache (what decode_* / long_* lower).
    """
    cfg = api.cfg
    ctx = make_ctx(api, mesh, plan)
    fsdp = plan.sharding in ("fsdp", "fsdp_tp")
    p_abs = abstract_params(api)
    p_shard = param_shardings(p_abs, cfg, mesh, fsdp=fsdp)

    cache_abs, _ = api.decode_input_specs(shape, ctx)
    cache_shard = cache_shardings(cache_abs, mesh)

    def step(params, cache, tokens):
        return api.decode_step(params, cache, tokens, ctx)

    tok_shard = NamedSharding(
        mesh, P(batch_axes_of(mesh), None)
        if shape.global_batch % _dp(mesh) == 0 else P(None, None))
    jitted = jax.jit(step,
                     in_shardings=(p_shard, cache_shard, tok_shard),
                     out_shardings=(None, cache_shard),
                     donate_argnums=(1,))
    return jitted, cache_shard, ctx


def make_prefill_step(api: ModelApi, mesh: Mesh, plan: CodesignPlan,
                      shape: ShapeSpec):
    """prefill_step(params, batch) -> (last logits, populated cache)."""
    ctx = make_ctx(api, mesh, plan)
    fsdp = plan.sharding in ("fsdp", "fsdp_tp")
    p_abs = abstract_params(api)
    p_shard = param_shardings(p_abs, api.cfg, mesh, fsdp=fsdp)
    batch_shard = _batch_shardings(api, mesh)

    def step(params, batch):
        return api.prefill(params, batch, ctx, max_len=shape.seq_len)

    jitted = jax.jit(step, in_shardings=(p_shard, batch_shard))
    return jitted, ctx


def _dp(mesh: Mesh) -> int:
    out = 1
    for a in batch_axes_of(mesh):
        out *= mesh.shape[a]
    return out


def cache_shardings(cache_abs: Any, mesh: Mesh) -> Any:
    """Decode-cache shardings by leaf kind.

    Attention caches, (L, B, S, H, hd) or heads flat (L, B, S, H * hd):
    ``blocks.kv_cache_spec``.  Mamba states (L, B, ...): batch over data,
    feature dims over model when divisible.  Scalars replicated.
    """
    axes = batch_axes_of(mesh)
    dp = _dp(mesh)
    m = mesh.shape["model"]

    def leaf(path, v) -> NamedSharding:
        nd = len(v.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        name = str(getattr(path[-1], "key", getattr(path[-1], "name", "")))
        if nd == 5 or name in ("k", "v", "attn_k", "attn_v"):
            return NamedSharding(mesh, kv_cache_spec(v.shape, dp, m, axes,
                                                     "model"))
        if nd == 4 and name in ("conv", ""):   # (L, B, W, C) conv state
            L, B, W, C = v.shape
            b_ax = axes if (B % dp == 0 and B >= dp) else None
            c_ax = "model" if C % m == 0 else None
            return NamedSharding(mesh, P(None, b_ax, None, c_ax))
        if nd >= 3:          # (L, B, H, P, N) ssm state and friends
            B = v.shape[1]
            b_ax = axes if (B % dp == 0 and B >= dp) else None
            spec = [None, b_ax] + [None] * (nd - 2)
            if v.shape[2] % m == 0:
                spec[2] = "model"
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P(*([None] * nd)))

    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache_abs)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf(p, v) for p, v in leaves])
