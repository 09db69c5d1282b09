"""Weights made from the seed, leaf by leaf name, with no code of the program.

The benchmark and its plain references draw the same values from the same
seed: each leaf's values come from its own key, ``fold_in`` of the run's
key with the leaf's name, and a stacked leaf's layer ``l`` from
``fold_in(leaf key, l)``.  So the program gets the whole tree from one
jitted call, and a reference can draw one layer at a time, after the
program's state is freed, and get the same bits.

``param_spec`` names every leaf of an architecture with its shape, the
type it is served in and how it is drawn.  The names are the program's
tree paths; the harness checks every leaf of the program against it.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32


def run_key(seed: int) -> jax.Array:
    """The key of a run; seeds beyond 31 bits fold their high part in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def dims(hf: dict) -> dict:
    """The sizes a spec needs, from the published keys of a decoder's config."""
    if hf["family"] != "dense":
        raise ValueError(f"unknown family {hf['family']!r}")
    d = hf["hidden_size"]
    h = hf["num_attention_heads"]
    return dict(family="dense", L=hf["num_hidden_layers"], D=d, H=h,
                Hkv=hf["num_key_value_heads"], hd=hf.get("head_dim", d // h),
                F=hf["intermediate_size"], V=hf["vocab_size"],
                tied=hf["tie_word_embeddings"])


def param_spec(hf: dict) -> dict[str, tuple]:
    """``{path: (shape without the layer axis, dtype, rule, stacked)}``."""
    d = dims(hf)
    D, V = d["D"], d["V"]
    # a tied table is also the output head: std 1/sqrt(D), logits of unit scale
    spec: dict = {"embed": ((V, D), BF16, "head" if d["tied"] else "embed",
                            False),
                  "final_norm": ((D,), F32, "norm", False)}
    Q, KV, F = d["H"] * d["hd"], d["Hkv"] * d["hd"], d["F"]
    spec.update({
        "layers/attn/wq": ((D, Q), BF16, "matrix", True),
        "layers/attn/wk": ((D, KV), BF16, "matrix", True),
        "layers/attn/wv": ((D, KV), BF16, "matrix", True),
        "layers/attn/wo": ((Q, D), BF16, "matrix", True),
        "layers/mlp/w_gate": ((D, F), BF16, "matrix", True),
        "layers/mlp/w_up": ((D, F), BF16, "matrix", True),
        "layers/mlp/w_down": ((F, D), BF16, "matrix", True),
        "layers/ln1": ((D,), F32, "norm", True),
        "layers/ln2": ((D,), F32, "norm", True),
    })
    if not d["tied"]:
        spec["lm_head"] = ((D, V), BF16, "matrix", False)
    return spec


def _draw(key, shape, rule) -> jax.Array:
    """float32 values of one leaf (one layer of a stacked leaf)."""
    u = jax.random.uniform(key, shape, F32, -1.0, 1.0)
    if rule == "matrix":
        return u * math.sqrt(3.0 / shape[-2])          # std 1/sqrt(fan-in)
    if rule == "embed":
        return u * math.sqrt(3.0)                       # std 1
    if rule == "head":
        return u * math.sqrt(3.0 / shape[-1])
    if rule == "norm":
        return 0.1 * u          # RMSNorm scale is 1 + this (program layout)
    raise ValueError(rule)


def leaf_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw_layer(key: jax.Array, hf: dict, layer) -> dict[str, jax.Array]:
    """One layer's stacked leaves, as stored (bf16 or f32)."""
    out = {}
    for path, (shape, dtype, rule, stacked) in param_spec(hf).items():
        if stacked:
            k = jax.random.fold_in(leaf_key(key, path), layer)
            out[path] = _draw(k, shape, rule).astype(dtype)
    return out


def draw_unstacked(key: jax.Array, hf: dict) -> dict[str, jax.Array]:
    return {path: _draw(leaf_key(key, path), shape, rule).astype(dtype)
            for path, (shape, dtype, rule, stacked) in param_spec(hf).items()
            if not stacked}


def draw_all(key: jax.Array, hf: dict) -> dict[str, jax.Array]:
    """Every leaf, stacked leaves with their leading layer axis."""
    n = dims(hf)["L"]
    out = draw_unstacked(key, hf)
    stacked = jax.vmap(lambda l: draw_layer(key, hf, l))(jnp.arange(n))
    out.update(stacked)
    return out


def path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "name",
                                                   getattr(p, "idx", p)))))
    return "/".join(parts)


def check_tree(abstract_tree, hf: dict) -> None:
    """Fail unless the program's parameter tree is exactly the spec."""
    spec = param_spec(hf)
    n = dims(hf)["L"]
    seen = set()
    for p, leaf in jax.tree_util.tree_flatten_with_path(abstract_tree)[0]:
        name = path_str(p)
        if name not in spec:
            raise ValueError(f"program leaf {name} is not in the spec")
        shape, dtype, _, stacked = spec[name]
        want = ((n,) + shape) if stacked else shape
        if tuple(leaf.shape) != want or leaf.dtype != dtype:
            raise ValueError(f"{name}: program {leaf.shape} {leaf.dtype}, "
                             f"spec {want} {dtype}")
        seen.add(name)
    if seen != set(spec):
        raise ValueError(f"spec leaves missing from the program: "
                         f"{sorted(set(spec) - seen)}")


def as_program_tree(flat: dict[str, jax.Array], abstract_tree):
    """Arrange ``{path: array}`` in the program's tree structure."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    return jax.tree_util.tree_unflatten(
        treedef, [flat[path_str(p)] for p, _ in leaves])
