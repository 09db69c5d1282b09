"""``chip_smoke.py`` on the CPU: its phases at tiny widths, its refusal to
run without a TPU, and the compile-cache placement its entry points
share."""

import importlib.util
import os

import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("smollm-360m")


def test_train_then_checkpoint_restores_bit_identical(smoke, cfg, tmp_path):
    trainer, losses = smoke.phase_train(cfg, make_host_mesh(), steps=2,
                                        batch=2, seq_len=32, seed=0)
    assert len(losses) == 2 and trainer.step_idx == 2
    smoke.phase_checkpoint(trainer, str(tmp_path), seed=0)
    assert trainer.params is None          # released before the restore


def test_serve_stream_matches_reference_prefill(smoke, cfg):
    smoke.phase_serve(cfg, batch=2, prompt_len=16, gen=4, seed=0)


def test_accel_digest_transfer_matches_oracle(smoke):
    # off the TPU the kernel runs interpreted, so it is not compiled
    assert smoke.phase_accel_digest(n_items=3, item_bytes=8192,
                                    seed=0) is False


def test_four_chip_phase_compares_meshes(smoke, cfg):
    smoke.phase_four_chips(cfg, steps=2, batch=2, seq_len=32, seed=0)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_a_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
