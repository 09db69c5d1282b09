"""Model-semantics tests: masking invariants and prefill/decode consistency.

The strongest integration check is teacher-forced consistency: running the
full sequence through `forward` must produce the same last-token logits as
prefill(prompt) + decode_step(token-by-token).  That exercises every cache
(full, ring, SSM state, hybrid shared sites, enc-dec cross) against the
training path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, MoEConfig, SSMConfig, ShardCtx, build
from repro.models.attention import (attention, attention_decode,
                                    cache_positions_ring,
                                    cache_positions_full)
from repro.models.lm import forward_lm

pytestmark = pytest.mark.slow

CTX = ShardCtx()
BASE = dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
            d_ff=64, vocab=64, max_seq_len=128, remat="none")


def _mk(name, family="dense", **kw):
    return ModelConfig(name=name, family=family, **{**BASE, **kw})


# ---------------------------------------------------------------------------
# attention invariants
# ---------------------------------------------------------------------------


def test_causality_future_independence():
    """Changing a future token must not change past logits."""
    cfg = _mk("causal")
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, cfg.vocab)
    tok2 = tok.at[0, -1].set((tok[0, -1] + 1) % cfg.vocab)
    l1, _, _ = forward_lm(params, cfg, tok, CTX)
    l2, _, _ = forward_lm(params, cfg, tok2, CTX)
    np.testing.assert_allclose(np.asarray(l1[0, :-1], np.float32),
                               np.asarray(l2[0, :-1], np.float32),
                               atol=1e-4)
    assert not np.allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]))


def test_swa_equals_full_when_window_covers_seq():
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (1, 16, 4, 8))
    kv = jax.random.normal(jax.random.fold_in(k, 1), (1, 16, 2, 8))
    pos = jnp.arange(16)
    full = attention(q, kv, kv, q_pos=pos, k_pos=pos, causal=True, window=0)
    win = attention(q, kv, kv, q_pos=pos, k_pos=pos, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(full, np.float32),
                               np.asarray(win, np.float32), atol=1e-5)


def test_swa_actually_windows():
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (1, 16, 4, 8))
    kv = jax.random.normal(jax.random.fold_in(k, 1), (1, 16, 2, 8))
    pos = jnp.arange(16)
    full = attention(q, kv, kv, q_pos=pos, k_pos=pos, causal=True, window=0)
    win = attention(q, kv, kv, q_pos=pos, k_pos=pos, causal=True, window=4)
    assert not np.allclose(np.asarray(full[0, -1], np.float32),
                           np.asarray(win[0, -1], np.float32), atol=1e-5)


def test_chunked_attention_matches_unchunked():
    k = jax.random.PRNGKey(2)
    S = 512
    q = jax.random.normal(k, (2, S, 4, 16))
    kv = jax.random.normal(jax.random.fold_in(k, 1), (2, S, 2, 16))
    pos = jnp.arange(S)
    whole = attention(q, kv, kv, q_pos=pos, k_pos=pos, causal=True, q_chunk=0)
    chunked = attention(q, kv, kv, q_pos=pos, k_pos=pos, causal=True,
                        q_chunk=128)
    np.testing.assert_allclose(np.asarray(whole, np.float32),
                               np.asarray(chunked, np.float32), atol=2e-5)


def test_attention_decode_matches_attention():
    """Decode attention over a cache it only reads, with the step as one
    more column, equals attention over the cache with the step written."""
    B, S, Hq, Hkv, hd, pos = 2, 12, 6, 3, 16, 7
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.bfloat16)
    k_new = jax.random.normal(ks[3], (B, 1, Hkv, hd), jnp.bfloat16)
    v_new = jax.random.normal(ks[4], (B, 1, Hkv, hd), jnp.bfloat16)
    q_pos = jnp.asarray([pos])
    k_pos = cache_positions_full(S, jnp.asarray(pos))
    want = attention(q, k.at[:, pos].set(k_new[:, 0]),
                     v.at[:, pos].set(v_new[:, 0]), q_pos=q_pos, k_pos=k_pos,
                     window=5)
    rows = (B, S, Hkv * hd)
    got = attention_decode(q, k.reshape(rows), v.reshape(rows), k_new, v_new,
                           q_pos=q_pos, k_pos=jnp.where(k_pos < pos, k_pos, -1),
                           window=5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_ring_positions():
    # after writing pos=9 with window 4, slots hold positions 8,9,6,7
    got = np.asarray(cache_positions_ring(4, jnp.asarray(9)))
    np.testing.assert_array_equal(got, [8, 9, 6, 7])
    # early steps: invalid slots are -1
    got = np.asarray(cache_positions_ring(4, jnp.asarray(1)))
    np.testing.assert_array_equal(got, [0, 1, -1, -1])


def test_full_cache_positions():
    got = np.asarray(cache_positions_full(6, jnp.asarray(2)))
    np.testing.assert_array_equal(got, [0, 1, 2, -1, -1, -1])


# ---------------------------------------------------------------------------
# prefill + decode == teacher-forced forward
# ---------------------------------------------------------------------------

CONSISTENCY_CASES = [
    _mk("dense"),
    _mk("swa", window=8),
    _mk("local-global", window=8, global_every=2),
    _mk("window-beyond-cache", window=32),   # ring of max_len (28) slots
    _mk("moe", family="moe",
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=64,
                      capacity_factor=8.0)),   # high capacity: no drops
    _mk("ssm", family="ssm", n_heads=1, n_kv_heads=1,
        ssm=SSMConfig(d_state=16, head_dim=16, chunk=8)),
    _mk("hybrid", family="hybrid", n_layers=4, attn_every=2,
        ssm=SSMConfig(d_state=16, head_dim=16, chunk=8)),
]


@pytest.mark.parametrize("cfg", CONSISTENCY_CASES, ids=lambda c: c.name)
def test_prefill_decode_matches_forward(cfg):
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    S_prompt, S_total = 16, 24
    tok = jax.random.randint(jax.random.PRNGKey(3), (2, S_total), 0, cfg.vocab)

    # teacher-forced logits for the whole sequence
    full_logits, _, _ = jax.jit(
        lambda p, t: forward_lm(p, cfg, t, CTX))(params, tok)

    # prefill prompt, then feed gold tokens one at a time
    logits, cache = jax.jit(lambda p, b: api.prefill(
        p, b, CTX, max_len=S_total + 4))(params,
                                         {"tokens": tok[:, :S_prompt]})
    np.testing.assert_allclose(
        np.asarray(logits[:, -1], np.float32),
        np.asarray(full_logits[:, S_prompt - 1], np.float32),
        atol=3e-2, rtol=3e-2)

    step = jax.jit(lambda p, c, t: api.decode_step(p, c, t, CTX))
    for i in range(S_prompt, S_total):
        logits, cache = step(params, cache, tok[:, i:i + 1])
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, i], np.float32),
            atol=3e-2, rtol=3e-2,
            err_msg=f"{cfg.name}: decode step {i} diverged")


def test_encdec_prefill_decode_consistency():
    cfg = _mk("encdec", family="encdec", enc_layers=2)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    S_enc, S_dec = 12, 8
    frames = jax.random.normal(jax.random.PRNGKey(5), (2, S_enc, cfg.d_model),
                               jnp.bfloat16)
    tok = jax.random.randint(jax.random.PRNGKey(6), (2, S_dec), 0, cfg.vocab)
    from repro.models.encdec import forward_encdec
    full_logits = jax.jit(
        lambda p: forward_encdec(p, cfg, frames, tok, CTX))(params)

    logits, cache = jax.jit(lambda p: api.prefill(
        p, {"frames": frames, "tokens": tok}, CTX, max_len=S_dec + 4))(params)
    np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                               np.asarray(full_logits[:, 0], np.float32),
                               atol=3e-2, rtol=3e-2)
    step = jax.jit(lambda p, c, t: api.decode_step(p, c, t, CTX))
    for i in range(1, S_dec):
        logits, cache = step(params, cache, tok[:, i:i + 1])
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, i], np.float32),
            atol=3e-2, rtol=3e-2, err_msg=f"encdec step {i}")


def test_ring_cache_decode_matches_forward_beyond_window():
    """SWA ring cache must reproduce windowed teacher-forced logits even
    after the ring has wrapped."""
    cfg = _mk("swa-ring", window=6)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    S_total = 20
    tok = jax.random.randint(jax.random.PRNGKey(7), (1, S_total), 0, cfg.vocab)
    full_logits, _, _ = jax.jit(
        lambda p, t: forward_lm(p, cfg, t, CTX))(params, tok)
    logits, cache = jax.jit(lambda p, b: api.prefill(
        p, b, CTX, max_len=S_total + 4))(params, {"tokens": tok[:, :4]})
    step = jax.jit(lambda p, c, t: api.decode_step(p, c, t, CTX))
    for i in range(4, S_total):
        logits, cache = step(params, cache, tok[:, i:i + 1])
    np.testing.assert_allclose(
        np.asarray(logits[:, 0], np.float32),
        np.asarray(full_logits[:, -1], np.float32), atol=3e-2, rtol=3e-2)


def test_generate_twice_with_donated_cache():
    """The decode step donates its cache: a second request with the same
    prompt must serve the same tokens, so no donated buffer is read."""
    from repro.launch.serve import Server
    server = Server(_mk("donate"), max_len=24)
    server.load()
    prompt = {"tokens": np.asarray(jax.random.randint(
        jax.random.PRNGKey(8), (2, 12), 0, 64), np.int32)}
    first = server.generate(prompt, 8)
    second = server.generate(prompt, 8)
    assert first.shape == (2, 8)
    np.testing.assert_array_equal(first, second)
