"""Distributed semantics on an emulated 8-device CPU mesh.

jax pins the device count at first init, so these checks run in one
subprocess that sets ``xla_force_host_platform_device_count=8`` before
importing jax (the same mechanism as the dry-run; conftest must NOT set
it globally).  The subprocess asserts internally; the host test checks
its exit code and marker output.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import _make_mesh
mesh = _make_mesh((2, 4), ("data", "model"))

# --- 1. sharding rules: specs valid + divisible ---------------------------
from repro.configs import get_smoke_config, get_config
from repro.models import build, ShardCtx
from repro.parallel.sharding import param_specs, param_shardings
cfg = get_smoke_config("qwen3-moe-30b-a3b")
api = build(cfg)
p_abs = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
specs = param_specs(p_abs, cfg, mesh, fsdp=True)
import jax.tree_util as jtu
for (path, v), (_, s) in zip(jtu.tree_flatten_with_path(p_abs)[0],
                             jtu.tree_flatten_with_path(specs)[0]):
    for dim, ax in zip(v.shape, tuple(s) + (None,) * 10):
        if ax is not None:
            size = mesh.shape[ax] if isinstance(ax, str) else 1
            assert dim % size == 0, (path, v.shape, s)
print("MARKER sharding-rules-ok")

# --- 2. dense train step distributes + matches single-device loss ---------
from repro.core.codesign import CodesignPlan
from repro.launch import steps as steps_lib
from repro.optim.adamw import adamw_init
dcfg = get_smoke_config("smollm-360m")
dapi = build(dcfg)
plan = CodesignPlan(sharding="fsdp_tp", microbatches=1, remat="none",
                    seq_parallel=False)
step, ps, ss, ctx = steps_lib.make_train_step(dapi, mesh, plan)
params = jax.jit(dapi.init, out_shardings=ps)(jax.random.PRNGKey(0))
opt = jax.jit(adamw_init, out_shardings=ss)(params)
rng = np.random.default_rng(0)
batch = {"tokens": rng.integers(0, dcfg.vocab, (4, 64)).astype(np.int32),
         "labels": rng.integers(0, dcfg.vocab, (4, 64)).astype(np.int32)}
p2, o2, m = step(params, opt, batch)
dist_loss = float(m["loss"])

params1 = dapi.init(jax.random.PRNGKey(0))
single_loss = float(dapi.loss(params1, {k: jnp.asarray(v) for k, v in batch.items()},
                              ShardCtx())[0])
assert abs(dist_loss - single_loss) < 0.02 * single_loss, (dist_loss, single_loss)
print("MARKER dense-distributed-ok", dist_loss, single_loss)

# --- 3. moe_ep and moe_tp match the dense oracle --------------------------
from repro.models import ffn as ffn_lib
from repro.models.config import ModelConfig, MoEConfig
mcfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=32, n_heads=4,
                   n_kv_heads=2, head_dim=8, d_ff=64, vocab=64,
                   moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=64,
                                 capacity_factor=8.0))
k = jax.random.PRNGKey(1)
x = jax.random.normal(k, (2, 16, 32), jnp.float32)
wr = jax.random.normal(jax.random.fold_in(k, 1), (32, 8)) * 0.5
wg = jax.random.normal(jax.random.fold_in(k, 2), (8, 32, 64)) * 0.1
wu = jax.random.normal(jax.random.fold_in(k, 3), (8, 32, 64)) * 0.1
wd = jax.random.normal(jax.random.fold_in(k, 4), (8, 64, 32)) * 0.1
y_ref, lb_ref, z_ref = ffn_lib.moe_ref(x, wr, wg, wu, wd, cfg=mcfg)
y_ep, lb_ep, z_ep = jax.jit(lambda *a: ffn_lib.moe_ep(
    *a, cfg=mcfg, mesh=mesh, batch_axes=("data",), fsdp_axis="data"))(
    x, wr, wg, wu, wd)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                           atol=2e-4, rtol=2e-4)
np.testing.assert_allclose(float(lb_ep), float(lb_ref), rtol=1e-3)
y_tp, lb_tp, _ = jax.jit(lambda *a: ffn_lib.moe_tp(
    *a, cfg=mcfg, mesh=mesh, batch_axes=("data",)))(x, wr, wg, wu, wd)
np.testing.assert_allclose(np.asarray(y_tp), np.asarray(y_ref),
                           atol=2e-4, rtol=2e-4)
np.testing.assert_allclose(float(lb_tp), float(lb_ref), rtol=1e-3)
print("MARKER moe-parity-ok")

# --- 4. compressed + hierarchical psum match plain psum -------------------
from repro.parallel.collectives import compressed_psum, hierarchical_psum
data = jax.random.normal(jax.random.PRNGKey(4), (4, 512))
exact = jax.shard_map(lambda v: jax.lax.psum(v, "model"), mesh=mesh,
                      in_specs=P("model", None), out_specs=P(None, None))(data)
approx = jax.shard_map(lambda v: compressed_psum(v, "model", block=64),
                       mesh=mesh, in_specs=P("model", None),
                       out_specs=P(None, None), check_vma=False)(data)
rel = np.abs(np.asarray(approx) - np.asarray(exact)).max() / (
    np.abs(np.asarray(exact)).max() + 1e-9)
assert rel < 0.05, rel
hier = jax.shard_map(lambda v: hierarchical_psum(
    v, intra_axis="model", inter_axis="data"), mesh=mesh,
    in_specs=P(("data", "model"), None), out_specs=P(None, None),
    check_vma=False)(jnp.tile(data, (2, 1)))
exact2 = jax.shard_map(lambda v: jax.lax.psum(v, ("data", "model")),
                       mesh=mesh, in_specs=P(("data", "model"), None),
                       out_specs=P(None, None))(jnp.tile(data, (2, 1)))
np.testing.assert_allclose(np.asarray(hier), np.asarray(exact2),
                           atol=1e-4, rtol=1e-4)
print("MARKER collectives-ok", rel)

# --- 5. pipeline_forward matches sequential ---------------------------------
from repro.parallel.pipeline import pipeline_forward
pmesh = _make_mesh((4,), ("pod",))
L, D = 8, 16
wkey = jax.random.PRNGKey(5)
ws = jax.random.normal(wkey, (L, D, D)) * 0.3

def layer_fn(w_stage, h):          # w_stage: (L/4, D, D)
    def body(hh, w):
        return jnp.tanh(hh @ w), None
    out, _ = jax.lax.scan(body, h, w_stage)
    return out

xmb = jax.random.normal(jax.random.fold_in(wkey, 1), (6, 4, D))  # 6 microbatches
got = pipeline_forward(layer_fn, ws, xmb, mesh=pmesh, stage_axis="pod",
                       layers_per_stage=2)
def seq(h):
    for i in range(L):
        h = jnp.tanh(h @ ws[i])
    return h
want = jax.vmap(seq)(xmb)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                           rtol=1e-4)
print("MARKER pipeline-ok")

# --- 6. elastic checkpoint reshard -----------------------------------------
import tempfile
from repro.checkpoint.manager import save_checkpoint, load_checkpoint
tree = {"w": jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8),
                            NamedSharding(mesh, P("data", "model")))}
with tempfile.TemporaryDirectory() as d:
    save_checkpoint(d, 1, tree)
    mesh2 = _make_mesh((4, 2), ("data", "model"))
    sh2 = {"w": NamedSharding(mesh2, P("model", "data"))}
    out = load_checkpoint(d, 1, jax.tree.map(jnp.zeros_like, tree),
                          shardings=sh2)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert out["w"].sharding == sh2["w"]
print("MARKER elastic-ok")
print("MARKER all-ok")
'''


@pytest.fixture(scope="module")
def dist_output():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}"
    return proc.stdout


def test_sharding_rules(dist_output):
    assert "MARKER sharding-rules-ok" in dist_output


def test_dense_distributed_matches_single(dist_output):
    assert "MARKER dense-distributed-ok" in dist_output


def test_moe_paths_match_oracle(dist_output):
    assert "MARKER moe-parity-ok" in dist_output


def test_compressed_and_hierarchical_collectives(dist_output):
    assert "MARKER collectives-ok" in dist_output


def test_pipeline_parallel_forward(dist_output):
    assert "MARKER pipeline-ok" in dist_output


def test_elastic_checkpoint_reshard(dist_output):
    assert "MARKER elastic-ok" in dist_output


def test_all_distribution_checks(dist_output):
    assert "MARKER all-ok" in dist_output
