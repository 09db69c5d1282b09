"""Weights of a Mamba-2 and attention hybrid (Granite-4.0-H) made from the
seed, leaf by leaf name, with no code of the program.

They are drawn as ``weights.py`` draws a dense decoder's: each leaf from
its own key, ``fold_in`` of the run's key with the leaf's name, and layer
``l`` of a stacked leaf from ``fold_in(leaf key, l)``, so that the program
gets the whole tree from one jitted call and the reference can draw one
layer at a time and get the same bits.  Two stacks: ``layers`` holds the
Mamba-2 layers, each with its MLP, in the order they run; ``attn_layers``
the attention layers, each with its MLP.

Mamba-2's own parameters follow its published initialisation: A = -exp(
A_log) with A uniform in [1, 16], dt_bias the inverse softplus of a dt
log-uniform in [1e-3, 1e-1]; the skip D and the gated norm's weight are 1
give or take a tenth.  The tied embedding, which is also the output head,
has std 1 / (8 sqrt(D)): at 1 / sqrt(D), the embedding multiplier of 12
puts each position's own token first by about ten standard deviations of
the other logits, so that greedy decoding repeats its input whatever the
precision, and no comparison of served tokens could tell a sound program
from a lower-precision one.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights as W

BF16, F32 = jnp.bfloat16, jnp.float32


def dims(hf: dict) -> dict:
    """The sizes a spec needs, from the published keys of the config."""
    if hf["family"] != "hybrid":
        raise ValueError(f"unknown family {hf['family']!r}")
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    types = hf["layer_types"]
    E = hf["mamba_expand"] * d
    G, N = hf["mamba_n_groups"], hf["mamba_d_state"]
    Hm = hf["mamba_n_heads"]
    return dict(
        L=len(types), Lm=types.count("mamba"), La=types.count("attention"),
        D=d, H=h, Hkv=hf["num_key_value_heads"], hd=hf.get("head_dim") or d // h,
        F=hf["intermediate_size"], V=hf["vocab_size"], E=E, Hm=Hm,
        P=hf["mamba_d_head"], N=N, G=G, W=hf["mamba_d_conv"],
        conv=E + 2 * G * N, in_proj=2 * E + 2 * G * N + Hm,
        chunk=hf["mamba_chunk_size"], tied=hf["tie_word_embeddings"])


def param_spec(hf: dict) -> dict[str, tuple]:
    """``{path: (shape without the layer axis, dtype, rule, layers)}``;
    ``layers`` is 0 for a leaf that is not stacked."""
    d = dims(hf)
    D, F, Lm, La = d["D"], d["F"], d["Lm"], d["La"]
    Q, KV = d["H"] * d["hd"], d["Hkv"] * d["hd"]
    spec: dict = {"embed": ((d["V"], D), BF16, "tied" if d["tied"] else "embed",
                            0),
                  "final_norm": ((D,), F32, "norm", 0)}
    if not d["tied"]:
        spec["lm_head"] = ((D, d["V"]), BF16, "matrix", 0)
    mlp = {"mlp/w_gate": ((D, F), BF16, "matrix"),
           "mlp/w_up": ((D, F), BF16, "matrix"),
           "mlp/w_down": ((F, D), BF16, "matrix"),
           "ln2": ((D,), F32, "norm")}
    mamba = {"ln": ((D,), F32, "norm"),
             "in_proj": ((D, d["in_proj"]), BF16, "matrix"),
             "conv_w": ((d["W"], d["conv"]), BF16, "matrix"),
             "conv_b": ((d["conv"],), F32, "norm"),
             "A_log": ((d["Hm"],), F32, "a_log"),
             "D": ((d["Hm"],), F32, "one"),
             "dt_bias": ((d["Hm"],), F32, "dt_bias"),
             "norm_w": ((d["E"],), F32, "one"),
             "out_proj": ((d["E"], D), BF16, "matrix"), **mlp}
    attn = {"attn/wq": ((D, Q), BF16, "matrix"),
            "attn/wk": ((D, KV), BF16, "matrix"),
            "attn/wv": ((D, KV), BF16, "matrix"),
            "attn/wo": ((Q, D), BF16, "matrix"),
            "ln1": ((D,), F32, "norm"), **mlp}
    spec.update({f"layers/{k}": v + (Lm,) for k, v in mamba.items()})
    spec.update({f"attn_layers/{k}": v + (La,) for k, v in attn.items()})
    return spec


def _draw(key, shape, rule) -> jax.Array:
    """float32 values of one leaf (one layer of a stacked leaf)."""
    u = jax.random.uniform(key, shape, F32, -1.0, 1.0)
    if rule == "a_log":                      # A uniform in [1, 16]
        return jnp.log(8.5 + 7.5 * u)
    if rule == "dt_bias":                    # softplus^-1 of log-uniform dt
        dt = jnp.exp(math.log(1e-2) + math.log(10.0) * u)
        return dt + jnp.log(-jnp.expm1(-dt))
    if rule == "one":
        return 1.0 + 0.1 * u
    if rule == "tied":                       # std 1 / (8 sqrt(D))
        return u * math.sqrt(3.0 / shape[-1]) / 8.0
    return W._draw(key, shape, rule)


def draw_layer(key: jax.Array, hf: dict, stack: str, layer) -> dict:
    """Layer ``layer`` of one stack (``layers`` or ``attn_layers``), as
    stored (bf16 or f32)."""
    out = {}
    for path, (shape, dtype, rule, n) in param_spec(hf).items():
        if n and path.startswith(stack + "/"):
            k = jax.random.fold_in(W.leaf_key(key, path), layer)
            out[path] = _draw(k, shape, rule).astype(dtype)
    return out


def draw_unstacked(key: jax.Array, hf: dict) -> dict:
    return {path: _draw(W.leaf_key(key, path), shape, rule).astype(dtype)
            for path, (shape, dtype, rule, n) in param_spec(hf).items()
            if not n}


def draw_all(key: jax.Array, hf: dict) -> dict:
    """Every leaf, stacked leaves with their leading layer axis."""
    d = dims(hf)
    out = draw_unstacked(key, hf)
    for stack, n in (("layers", d["Lm"]), ("attn_layers", d["La"])):
        out.update(jax.vmap(lambda l: draw_layer(key, hf, stack, l))(
            jnp.arange(n)))
    return out


def check_tree(abstract_tree, hf: dict) -> None:
    """Fail unless the program's parameter tree is exactly the spec."""
    spec = param_spec(hf)
    seen = set()
    for p, leaf in jax.tree_util.tree_flatten_with_path(abstract_tree)[0]:
        name = W.path_str(p)
        if name not in spec:
            raise ValueError(f"program leaf {name} is not in the spec")
        shape, dtype, _, n = spec[name]
        want = ((n,) + shape) if n else shape
        if tuple(leaf.shape) != want or leaf.dtype != dtype:
            raise ValueError(f"{name}: program {leaf.shape} {leaf.dtype}, "
                             f"spec {want} {dtype}")
        seen.add(name)
    if seen != set(spec):
        raise ValueError(f"spec leaves missing from the program: "
                         f"{sorted(set(spec) - seen)}")
