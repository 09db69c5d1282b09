"""Every Pallas kernel compiles for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses what Mosaic would refuse on the chip (block shapes
off the (8, 128) tiling, unsupported reductions, layouts).  Interpret-mode
tests cannot see those refusals.  The topology is described inside a
fixture, never at import, so that every test worker collects the same
tests and only the worker running this file loads the TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention_bhd
from repro.kernels.digest import block_digest
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.quantize import dequantize_int8, quantize_int8
from repro.kernels.ssd_scan import ssd_scan_bhsd


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiles_to_mosaic(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_smollm_360m_widths(one_chip):
    B, Hq, Hkv, S, hd = 1, 15, 5, 2048, 64
    s = functools.partial(_spec, one_chip)
    _compiles_to_mosaic(
        functools.partial(flash_attention_bhsd, causal=True),
        s((B, Hq, S, hd), jnp.bfloat16), s((B, Hkv, S, hd), jnp.bfloat16),
        s((B, Hkv, S, hd), jnp.bfloat16))


@pytest.mark.parametrize("B", [1, 8])
def test_decode_attention_smollm_360m_cache(one_chip, B):
    Hq, Hkv, S, hd = 15, 5, 2048, 64
    s = functools.partial(_spec, one_chip)
    _compiles_to_mosaic(
        decode_attention_bhd, s((B, Hq, hd), jnp.bfloat16),
        s((B, Hkv, S, hd), jnp.bfloat16), s((B, Hkv, S, hd), jnp.bfloat16),
        s((B, S), jnp.int32), s((B,), jnp.int32))


def test_ssd_scan_mamba2_1_3b_widths(one_chip):
    B, H, S, P, N, G = 1, 64, 2048, 64, 128, 1
    s = functools.partial(_spec, one_chip)
    _compiles_to_mosaic(
        functools.partial(ssd_scan_bhsd, chunk=256),
        s((B, H, S, P), jnp.bfloat16), s((B, H, S), jnp.float32),
        s((H,), jnp.float32), s((B, G, S, N), jnp.bfloat16),
        s((B, G, S, N), jnp.bfloat16))


def test_block_digest_4mib_panels(one_chip):
    _compiles_to_mosaic(block_digest,
                        _spec(one_chip, (4096, 256), jnp.uint32))


def test_quantize_int8_1m_floats(one_chip):
    _compiles_to_mosaic(functools.partial(quantize_int8, block=256),
                        _spec(one_chip, (1 << 20,), jnp.float32))


def test_dequantize_int8_1m_floats(one_chip):
    _compiles_to_mosaic(
        functools.partial(dequantize_int8, shape=(1 << 20,)),
        _spec(one_chip, (4096, 256), jnp.int8),
        _spec(one_chip, (4096,), jnp.float32))
