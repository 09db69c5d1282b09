"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode — kernel bodies execute on CPU; TPU is the target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_bhd
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.quantize import dequantize_int8, quantize_int8
from repro.kernels.ssd_scan import ssd_scan_bhsd


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: dict(atol=3e-5, rtol=3e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


@pytest.mark.parametrize("B,S,Hq,Hkv,hd", [
    (1, 128, 2, 2, 32),     # MHA
    (2, 256, 4, 2, 64),     # GQA 2:1
    (1, 256, 8, 1, 64),     # MQA
    (1, 512, 4, 4, 128),    # long, big head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(B, S, Hq, Hkv, hd, dtype, causal, window):
    k = jax.random.PRNGKey(B * S + Hq)
    q = _rand(k, (B, Hq, S, hd), dtype)
    kk = _rand(jax.random.fold_in(k, 1), (B, Hkv, S, hd), dtype)
    v = _rand(jax.random.fold_in(k, 2), (B, Hkv, S, hd), dtype)
    out = flash_attention_bhsd(q, kk, v, causal=causal, window=window,
                               bq=128, bk=128, interpret=True)
    expect = ref.attention_ref(q, kk, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **TOL[dtype])


@pytest.mark.parametrize("B,S,Hq,Hkv,hd,fill", [
    (2, 256, 4, 2, 32, 255),
    (1, 512, 8, 2, 64, 300),
    (3, 128, 4, 4, 64, 17),     # partially filled cache
    (4, 256, 4, 2, 32, (200, 131, 62, 9)),   # each sequence at its own position
])
@pytest.mark.parametrize("window", [0, 96])
def test_decode_attention_sweep(B, S, Hq, Hkv, hd, fill, window):
    k = jax.random.PRNGKey(S + int(np.max(fill)))
    q = _rand(k, (B, Hq, hd))
    kc = _rand(jax.random.fold_in(k, 1), (B, Hkv, S, hd))
    vc = _rand(jax.random.fold_in(k, 2), (B, Hkv, S, hd))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    q_pos = jnp.broadcast_to(jnp.asarray(fill, jnp.int32), (B,))
    k_pos = jnp.where(pos <= q_pos[:, None], pos, -1)
    out = decode_attention_bhd(q, kc, vc, k_pos, q_pos, window=window,
                               bk=128, interpret=True)
    expect = ref.decode_attention_ref(q, kc, vc, k_pos, q_pos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=3e-5, rtol=3e-5)


def test_decode_attention_ring_cache_order_irrelevant():
    """Ring caches present K/V in slot order, not time order — the kernel
    must only trust k_pos."""
    k = jax.random.PRNGKey(0)
    B, Hkv, S, hd = 1, 2, 128, 32
    kc = _rand(k, (B, Hkv, S, hd))
    vc = _rand(jax.random.fold_in(k, 1), (B, Hkv, S, hd))
    q = _rand(jax.random.fold_in(k, 2), (B, 4, hd))
    k_pos = jnp.arange(S, dtype=jnp.int32)[None]
    q_pos = jnp.full((B,), S - 1, jnp.int32)
    base = decode_attention_bhd(q, kc, vc, k_pos, q_pos, interpret=True)
    perm = np.random.default_rng(0).permutation(S)
    out = decode_attention_bhd(q, kc[:, :, perm], vc[:, :, perm],
                               k_pos[:, perm], q_pos, interpret=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(out),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("B,H,S,P,N,G,chunk", [
    (1, 2, 64, 16, 16, 1, 16),
    (2, 4, 128, 16, 32, 2, 32),
    (1, 8, 256, 32, 64, 1, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, H, S, P, N, G, chunk, dtype):
    k = jax.random.PRNGKey(S + H)
    x = _rand(k, (B, H, S, P), dtype)
    dt = jax.nn.softplus(_rand(jax.random.fold_in(k, 1), (B, H, S)))
    A = -jnp.exp(_rand(jax.random.fold_in(k, 2), (H,)) * 0.3)
    Bm = _rand(jax.random.fold_in(k, 3), (B, G, S, N), dtype)
    Cm = _rand(jax.random.fold_in(k, 4), (B, G, S, N), dtype)
    y, _ = ssd_scan_bhsd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    expect = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(expect, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("B,H,S,P,N,G,chunk", [
    (2, 4, 64, 16, 16, 1, 16),
    (1, 4, 128, 16, 32, 2, 32),
])
def test_ssd_scan_final_state_matches_chunked(B, H, S, P, N, G, chunk):
    """The kernel's second output, the state after the last chunk, is
    ``ssd_chunked``'s final state: what a prefill hands to decode."""
    from repro.models.ssm import ssd_chunked
    k = jax.random.PRNGKey(S * H + N)
    x = _rand(k, (B, H, S, P), jnp.bfloat16)
    dt = jax.nn.softplus(_rand(jax.random.fold_in(k, 1), (B, H, S)))
    A = -jnp.exp(_rand(jax.random.fold_in(k, 2), (H,)) * 0.3)
    Bm = _rand(jax.random.fold_in(k, 3), (B, G, S, N), jnp.bfloat16)
    Cm = _rand(jax.random.fold_in(k, 4), (B, G, S, N), jnp.bfloat16)
    y, state = ssd_scan_bhsd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    y_ref, state_ref = ssd_chunked(
        jnp.moveaxis(x, 1, 2), jnp.moveaxis(dt, 1, 2), A,
        jnp.moveaxis(Bm, 1, 2), jnp.moveaxis(Cm, 1, 2), chunk)
    assert state.shape == (B, H, P, N) and state.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(jnp.moveaxis(y_ref, 2, 1),
                                          np.float32), atol=5e-2, rtol=5e-2)


def test_ssd_state_continuity_across_chunks():
    """y at chunk c must depend on inputs of chunk c-1 (state carried)."""
    k = jax.random.PRNGKey(9)
    B, H, S, P, N, chunk = 1, 1, 64, 8, 8, 16
    x = _rand(k, (B, H, S, P))
    dt = jax.nn.softplus(_rand(jax.random.fold_in(k, 1), (B, H, S))) * 0 + 0.5
    A = -jnp.ones((H,)) * 0.01           # slow decay: long memory
    Bm = _rand(jax.random.fold_in(k, 3), (B, 1, S, N))
    Cm = _rand(jax.random.fold_in(k, 4), (B, 1, S, N))
    y1, _ = ssd_scan_bhsd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    x2 = x.at[:, :, 0].add(1.0)          # perturb first chunk only
    y2, _ = ssd_scan_bhsd(x2, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    # last chunk outputs must differ -> state flowed across chunks
    assert not np.allclose(np.asarray(y1[:, :, -chunk:]),
                           np.asarray(y2[:, :, -chunk:]), atol=1e-6)


@pytest.mark.parametrize("n", [256, 1000, 8192, 250_000])
@pytest.mark.parametrize("block", [128, 256])
def test_quantize_matches_ref_and_bounds(n, block):
    x = jax.random.normal(jax.random.PRNGKey(n), (n,)) * 3.0
    q, s = quantize_int8(x, block=block, interpret=True)
    rq, rs = ref.quantize_ref(x, block)
    np.testing.assert_array_equal(np.asarray(q)[: rq.shape[0]], np.asarray(rq))
    np.testing.assert_allclose(np.asarray(s)[: rs.shape[0]], np.asarray(rs),
                               atol=1e-6)
    back = dequantize_int8(q, s, (n,), interpret=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    # per-block bound: |err| <= scale/2 per element
    scales = np.repeat(np.asarray(s), block)[:n]
    assert np.all(err <= scales * 0.5 + 1e-7)
