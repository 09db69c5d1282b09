"""Every Pallas kernel, and the serving decode step, compiles for a TPU
v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses what Mosaic would refuse on the chip (block shapes
off the (8, 128) tiling, unsupported reductions, layouts).  Interpret-mode
tests cannot see those refusals.  The topology is described inside a
fixture, never at import, so that every test worker collects the same
tests and only the worker running this file loads the TPU library.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention_bhd
from repro.kernels.digest import block_digest
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.quantize import dequantize_int8, quantize_int8
from repro.kernels.ssd_scan import ssd_scan_bhsd
from repro.launch.serve import Server
from repro.models import ModelConfig, SSMConfig


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


def test_ssd_scan_with_state_granite_prefill(one_chip):
    """The kernel with its final state, at granite-4.0-h-micro's prefill:
    one piece of the batch (4 x 2048 tokens), 64 heads of 64, state 128."""
    B, H, S, P, N, G = 4, 64, 2048, 64, 128, 1
    s = functools.partial(_spec, one_chip)
    fn = functools.partial(ssd_scan_bhsd, chunk=256)
    args = (s((B, H, S, P), jnp.bfloat16), s((B, H, S), jnp.float32),
            s((H,), jnp.float32), s((B, G, S, N), jnp.bfloat16),
            s((B, G, S, N), jnp.bfloat16))
    _compiles_to_mosaic(fn, *args)
    y, state = jax.eval_shape(fn, *args)
    assert y.shape == (B, H, S, P)
    assert state.shape == (B, H, P, N) and state.dtype == jnp.float32


def test_hybrid_decode_step_updates_states_in_place(one_chip):
    """Granite-4.0-H's decode step at its widths and the cell's batch, on
    three layers: the donated Mamba states and K/V are aliased, and no
    temporary holds as much as one layer's SSM state."""
    cfg = ModelConfig(name="granite-3l", family="hybrid", n_layers=3,
                      d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
                      d_ff=8192, vocab=512,
                      ssm=SSMConfig(d_state=128, head_dim=64, chunk=256),
                      layer_types=("mamba", "attention", "mamba"), rope=False,
                      embedding_multiplier=12.0, residual_multiplier=0.22,
                      attention_multiplier=1 / 64, logits_scaling=8.0,
                      tie_embeddings=True)
    B, max_len = 32, 2304
    server = Server(cfg, max_len=max_len)
    place = functools.partial(jax.tree.map, lambda a: _spec(
        one_chip, a.shape, a.dtype))
    params = place(jax.eval_shape(server.api.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: server.api.init_cache(B, max_len, server.ctx)))
    m = server._decode.lower(params, cache, _spec(
        one_chip, (B, 1), jnp.int32)).compile().memory_analysis()
    state = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert m.alias_size_in_bytes >= state - 4
    one_layer = B * cfg.ssm_heads * 64 * 128 * 4
    assert m.temp_size_in_bytes < one_layer, m.temp_size_in_bytes


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


# Phi-3-mini's cache widths (32 KV heads of 96, batch 12, 768 positions)
# on two layers; the ring keeps 512 of the 768; and grouped heads of 128.
DECODE_CASES = [
    ModelConfig(name="full-hd96", family="dense", n_layers=2, d_model=3072,
                n_heads=32, n_kv_heads=32, d_ff=512, vocab=512),
    ModelConfig(name="ring-hd96", family="dense", n_layers=2, d_model=3072,
                n_heads=32, n_kv_heads=32, d_ff=512, vocab=512, window=512),
    ModelConfig(name="full-hd128-gqa", family="dense", n_layers=2,
                d_model=1024, n_heads=16, n_kv_heads=8, head_dim=128,
                d_ff=512, vocab=512),
]


def _unfused(text: str):
    """(computation, opcode, shape, in HBM) of every instruction that no
    fusion holds, fusions themselves included, from compiled HLO text; a
    result in another memory space (``S(n)``, VMEM) is on-chip, a read."""
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    comp, out = None, []
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = ("ENTRY" if head.group(1) else head.group(2))
            continue
        ins = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\](\S*) "
                       r"([\w\-]+)\(", line)
        if ins and comp not in fused:
            out.append((comp, ins.group(3), tuple(
                int(d) for d in ins.group(1).split(",") if d),
                "S(" not in ins.group(2)))
    return out


@pytest.mark.parametrize("cfg", DECODE_CASES, ids=lambda c: c.name)
def test_decode_step_writes_cache_in_place(one_chip, cfg):
    """The served decode step donates its K/V cache and writes the step's
    slot into it: no op, fused or not, materialises a layer of the cache
    in HBM (a slice into VMEM is the attention's read), the whole cache is
    written once after the layer scan, and the slot is contiguous in the
    cache's layout."""
    B, max_len = 12, 768
    server = Server(cfg, max_len=max_len)
    place = functools.partial(jax.tree.map, lambda a: _spec(
        one_chip, a.shape, a.dtype))
    params = place(jax.eval_shape(server.api.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: server.api.init_cache(B, max_len, server.ctx)))
    compiled = server._decode.lower(
        params, cache, _spec(one_chip, (B, 1), jnp.int32)).compile()

    whole = cache["k"].shape
    kv_bytes = 2 * cache["k"].size * cache["k"].dtype.itemsize
    assert compiled.memory_analysis().alias_size_in_bytes >= kv_bytes
    # with the position axis minor-most, one position's write would touch
    # every tile of the cache
    layout = compiled.input_formats[0][1]["k"].layout
    assert layout.major_to_minor[-1] != 2, layout
    layer = (whole[1:], (1,) + whole[1:])
    writes = ("copy", "dynamic-slice", "dynamic-update-slice", "fusion")
    for comp, op, shape, hbm in _unfused(compiled.as_text()):
        assert not (hbm and shape in layer), (comp, op, shape)
        # the one write of the step's K/V after the layer scan, in place
        if shape == whole and op in writes:
            assert comp == "ENTRY" and op != "copy", (comp, op)
