"""The ssm and hybrid stacks against a plain float32 reference.

Prefill through the cache and then decode, token by token, must give the
logits of ``hybrid_reference.logits`` over the whole sequence: for a
Granite-4.0-H-shaped hybrid (attention at uneven positions, each layer its
own weights, an MLP after every mixer, no position embedding, the four
multipliers), a Zamba2-shaped one (one shared attention block) and a
plain Mamba-2 stack.  Also: a prefill through the SSD kernel (interpret
mode) gives what ``ssd_chunked`` gives, ``Server.generate`` serves a
hybrid, and the chunked SSD's gradients stay finite at Mamba-2's
published ranges of dt and A.
"""

import types
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_reference
from repro.kernels import ops as kops
from repro.models import ModelConfig, SSMConfig, ShardCtx, build
from repro.models import ssm as ssm_lib

CTX = ShardCtx()
SSM = SSMConfig(d_state=16, head_dim=16, chunk=8)
BASE = dict(n_layers=4, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
            d_ff=64, vocab=64, max_seq_len=128, remat="none", ssm=SSM)

CASES = [
    ModelConfig(name="granite-like", family="hybrid",
                **{**BASE, "n_layers": 5},
                layer_types=("mamba", "attention", "mamba", "mamba",
                             "attention"),
                rope=False, embedding_multiplier=12.0,
                residual_multiplier=0.22, attention_multiplier=0.25,
                logits_scaling=8.0, tie_embeddings=True),
    ModelConfig(name="zamba2-like", family="hybrid", attn_every=2, **BASE),
    ModelConfig(name="mamba2-like", family="ssm",
                **{**BASE, "n_heads": 1, "n_kv_heads": 1}),
]

#: widest logit error allowed, by the program's weights.  In bf16 the
#: program computes in bf16 throughout: over 24 positions it reads up to
#: 0.041 (CPU) on logits of unit scale, and this allows 2.4 times that; a
#: wrong multiplier, mask or state hand-over moves logits by 0.3 or more.
#: In float32, where only the K/V cache rounds to bf16, it reads up to
#: 2.6e-3 (zamba2-like, its keys rotated before they round), and in the
#: granite-like case a softmax scale or RoPE left at its default moves
#: logits by 0.0075 or more
ATOL = {"bf16": 0.1, "f32": 5e-3}


def _params(cfg, seed=0, weights="bf16"):
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(seed))
    if weights == "f32":
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    # norms away from their initial 1, so that a swapped scale shows
    key = jax.random.PRNGKey(seed + 1)
    return api, jax.tree_util.tree_map_with_path(
        lambda p, a: (a + 0.1 * jax.random.normal(
            jax.random.fold_in(key, zlib.crc32(str(p).encode())), a.shape, a.dtype))
        if str(p[-1]) in ("['ln']", "['ln1']", "['ln2']", "['final_norm']")
        else a, params)


def _served_logits(api, params, tok, prompt):
    """Logits at positions prompt-1 .. T-2: the prefill's last, then one
    per decode step through the cache."""
    logits, cache = jax.jit(lambda p, b: api.prefill(
        p, b, CTX, max_len=tok.shape[1] + 4))(params,
                                              {"tokens": tok[:, :prompt]})
    out = [logits[:, -1]]
    step = jax.jit(lambda p, c, t: api.decode_step(p, c, t, CTX))
    for i in range(prompt, tok.shape[1] - 1):
        logits, cache = step(params, cache, tok[:, i:i + 1])
        out.append(logits[:, 0])
    return np.stack([np.asarray(o, np.float32) for o in out], 1)


PROMPT, TOTAL = 16, 25


def _compare(cfg, weights):
    """(served logits, the reference's over the same positions)."""
    api, params = _params(cfg, weights=weights)
    tok = jax.random.randint(jax.random.PRNGKey(3), (2, TOTAL), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        got = _served_logits(api, params, tok, PROMPT)
    want = np.asarray(hybrid_reference.logits(params, cfg, tok[:, :-1]))
    return got, want[:, PROMPT - 1:], (params, tok)


@pytest.mark.parametrize("weights", ["bf16", "f32"])
@pytest.mark.parametrize("cfg", CASES, ids=lambda c: c.name)
def test_prefill_decode_matches_reference(cfg, weights):
    got, want, _ = _compare(cfg, weights)
    np.testing.assert_allclose(got, want, atol=ATOL[weights], rtol=0)


def test_granite_multipliers_are_applied():
    """The reference with a multiplier, or the position embedding, left at
    its default misses the program by more than the float32 tolerance:
    the comparison sees each."""
    import dataclasses
    cfg = CASES[0]
    got, _, (params, tok) = _compare(cfg, "f32")
    for field, value in [("embedding_multiplier", 1.0),
                         ("residual_multiplier", 1.0),
                         ("attention_multiplier", None),
                         ("logits_scaling", 1.0), ("rope", True)]:
        other = dataclasses.replace(cfg, **{field: value})
        want = np.asarray(hybrid_reference.logits(params, other,
                                                  tok[:, :-1]))
        assert np.max(np.abs(got - want[:, PROMPT - 1:])) > ATOL["f32"], field


def test_prefill_through_kernel_matches_chunked(monkeypatch):
    """The prefill's SSD through the Pallas kernel (interpret mode, as if
    on a TPU) gives the logits and states of ``ssd_chunked``."""
    cfg = CASES[0]
    api, params = _params(cfg)
    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg.vocab)
    prefill = lambda p, b: api.prefill(p, b, CTX, max_len=20)  # noqa: E731
    want_logits, want = jax.jit(prefill)(params, {"tokens": tok})
    monkeypatch.setattr(ssm_lib, "kops", types.SimpleNamespace(
        on_tpu=lambda: True, ssd_scan=kops.ssd_scan))
    got_logits, got = jax.jit(prefill)(params, {"tokens": tok})
    np.testing.assert_allclose(np.asarray(got["mamba"].ssm),
                               np.asarray(want["mamba"].ssm), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(got_logits, np.float32),
                               np.asarray(want_logits, np.float32),
                               atol=2e-2)


@pytest.mark.parametrize("cfg", CASES[:2], ids=lambda c: c.name)
def test_generate_serves_a_hybrid(cfg):
    """``Server.generate`` streams a hybrid's greedy tokens; a second
    request with the same prompt serves the same tokens, so no donated
    state is read again, and the first token is the reference's argmax."""
    from repro.launch.serve import Server
    server = Server(cfg, max_len=24)
    server.api, server.params = _params(cfg)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(8), (2, 12), 0,
                                           cfg.vocab), np.int32)
    got = []
    first = server.generate({"tokens": prompt}, 8, sink=got.append)
    second = server.generate({"tokens": prompt}, 8)
    assert first.shape == (2, 8)
    np.testing.assert_array_equal(first, second)
    np.testing.assert_array_equal(np.concatenate(got, 1), first[:, 1:])
    ref = np.asarray(hybrid_reference.logits(server.params, cfg, prompt))
    top2 = np.sort(ref[:, -1], -1)[:, -2:]
    sure = top2[:, 1] - top2[:, 0] > ATOL["bf16"]
    np.testing.assert_array_equal(first[sure, 0],
                                  np.argmax(ref[:, -1], -1)[sure])


def test_ssd_chunked_gradients_finite_at_mamba2_ranges():
    """At Mamba-2-1.3B's published ranges, dt up to 0.1 and A down to -16,
    a 256-long chunk's decay exponent reaches about 400 above the
    diagonal; the gradients must stay finite (and nonzero)."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    B, S, H, P, N = 1, 256, 4, 8, 16
    x = jax.random.normal(k[0], (B, S, H, P))
    dt = jnp.full((B, S, H), 0.1) * jnp.linspace(0.01, 1.0, H)
    A = -jnp.linspace(1.0, 16.0, H)
    Bm = jax.random.normal(k[1], (B, S, 1, N))
    Cm = jax.random.normal(k[2], (B, S, 1, N))

    def loss(x, dt, A, Bm, Cm):
        y, state = ssm_lib.ssd_chunked(x, dt, A, Bm, Cm, 256)
        return jnp.sum(y * y) + jnp.sum(state * state)

    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))
        assert np.any(np.asarray(g) != 0)


def test_mamba2_loss_gradients_finite_at_published_ranges():
    """The whole model's loss gradients at a Mamba-2 chunk of 256 with
    every head's dt near 0.1 and A from -1 to -16."""
    cfg = ModelConfig(name="mamba2-ranges", family="ssm",
                      **{**BASE, "n_layers": 2, "n_heads": 1, "n_kv_heads": 1,
                         "ssm": SSMConfig(d_state=16, head_dim=16,
                                          chunk=256)})
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    H = cfg.ssm_heads
    layers = dict(params["layers"])
    layers["A_log"] = jnp.broadcast_to(
        jnp.log(jnp.linspace(1.0, 16.0, H)), layers["A_log"].shape)
    layers["dt_bias"] = jnp.full(layers["dt_bias"].shape,
                                 float(np.log(np.expm1(0.1))))
    params = dict(params, layers=layers)
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 256), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    grads = jax.jit(jax.grad(lambda p: api.loss(p, batch, CTX)[0]))(params)
    for g in jax.tree.leaves(grads):
        assert np.all(np.isfinite(np.asarray(g, np.float32)))


def test_prefill_in_pieces_matches_whole(monkeypatch):
    """A prefill whose batch runs in pieces (``PREFILL_TOKENS``) fills the
    same cache and gives the same logits as one over the whole batch."""
    from repro.models import lm
    cfg = CASES[0]
    api, params = _params(cfg)
    tok = jax.random.randint(jax.random.PRNGKey(6), (4, 16), 0, cfg.vocab)
    prefill = lambda p, b: api.prefill(p, b, CTX, max_len=20)  # noqa: E731
    want_logits, want = jax.jit(prefill)(params, {"tokens": tok})
    monkeypatch.setattr(lm, "PREFILL_TOKENS", 16)      # one sequence a piece
    got_logits, got = jax.jit(prefill)(params, {"tokens": tok})
    np.testing.assert_array_equal(np.asarray(got_logits, np.float32),
                                  np.asarray(want_logits, np.float32))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
