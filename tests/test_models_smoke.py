"""Per-assigned-architecture smoke tests (reduced same-family configs).

For each of the 10 archs: instantiate the reduced config, run one forward
/ train step on CPU, assert output shapes and no NaNs — per the
assignment's smoke-test rule.  The FULL configs are exercised only via
the dry-run (ShapeDtypeStruct, no allocation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, get_smoke_config
from repro.models import ShardCtx, build

pytestmark = pytest.mark.slow

B, S = 2, 64


def _batch(cfg, rng):
    s_text = S - cfg.frontend_len if cfg.frontend else S
    batch = {
        "tokens": rng.integers(0, cfg.vocab, (B, s_text)).astype(np.int32),
        "labels": rng.integers(0, cfg.vocab, (B, s_text)).astype(np.int32),
    }
    if cfg.family == "encdec":
        batch["tokens"] = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
        batch["labels"] = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
        batch["frames"] = rng.standard_normal((B, S, cfg.d_model)).astype(np.float32)
    if cfg.frontend and cfg.family != "encdec":
        batch["extra_embeds"] = rng.standard_normal(
            (B, cfg.frontend_len, cfg.d_model)).astype(np.float32)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_forward_and_grad(arch, rng):
    cfg = get_smoke_config(arch)
    cfg.validate()
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, rng)
    ctx = ShardCtx()

    def loss_fn(p):
        loss, aux = api.loss(p, batch, ctx)
        return loss

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, f"{arch}: bad grads"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_train_step_updates_params(arch, rng):
    from repro.core.codesign import CodesignPlan
    from repro.launch.mesh import make_host_mesh
    from repro.launch import steps as steps_lib
    from repro.optim.adamw import adamw_init

    cfg = get_smoke_config(arch)
    api = build(cfg)
    mesh = make_host_mesh()
    plan = CodesignPlan(sharding="dp", microbatches=1, remat="none",
                        seq_parallel=False)
    step, p_shard, s_shard, ctx = steps_lib.make_train_step(api, mesh, plan)
    params = jax.jit(api.init, out_shardings=p_shard)(jax.random.PRNGKey(0))
    opt = jax.jit(adamw_init, out_shardings=s_shard)(params)
    before = [np.asarray(x, np.float32).copy()
              for x in jax.tree.leaves(opt.master)]
    batch = _batch(cfg, rng)
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"])), arch
    # one warmup step moves the fp32 master weights (warmup-scale deltas
    # are below bf16/allclose resolution — exact any-leaf comparison)
    after = [np.asarray(x, np.float32) for x in jax.tree.leaves(opt2.master)]
    moved = any(not np.array_equal(a, b) for a, b in zip(before, after))
    assert moved, f"{arch}: no master weight moved"
    assert int(opt2.step) == 1


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_decode_shapes_and_finiteness(arch, rng):
    cfg = get_smoke_config(arch)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    ctx = ShardCtx()
    batch = _batch(cfg, rng)
    logits, cache = jax.jit(
        lambda p, b: api.prefill(p, b, ctx, max_len=S + 8))(params, batch)
    assert logits.shape[0] == B and logits.shape[-1] == cfg.vocab
    tok = jnp.zeros((B, 1), jnp.int32)
    logits2, cache2 = jax.jit(
        lambda p, c, t: api.decode_step(p, c, t, ctx))(params, cache, tok)
    assert logits2.shape == (B, 1, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits2, np.float32))), arch
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


def test_full_configs_match_published_sizes():
    expected = {
        "phi3-mini-3.8b": (3.6e9, 4.0e9),
        "mistral-large-123b": (118e9, 126e9),
        "mixtral-8x22b": (135e9, 147e9),
        "qwen3-moe-30b-a3b": (29e9, 32e9),
        "mamba2-1.3b": (1.2e9, 1.5e9),
        "zamba2-1.2b": (1.0e9, 1.3e9),
        "granite-4.0-h-micro": (3.1e9, 3.3e9),
        "llava-next-mistral-7b": (7.0e9, 7.6e9),
        "smollm-360m": (0.3e9, 0.5e9),
        "gemma3-1b": (1.0e9, 1.4e9),
        "seamless-m4t-large-v2": (1.9e9, 2.4e9),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:.3e} outside [{lo:.2e}, {hi:.2e}]"


def test_moe_active_params():
    qwen = get_config("qwen3-moe-30b-a3b")
    assert 2.5e9 <= qwen.active_param_count() <= 4e9   # "A3B"
    mix = get_config("mixtral-8x22b")
    assert 35e9 <= mix.active_param_count() <= 45e9    # ~39B active
