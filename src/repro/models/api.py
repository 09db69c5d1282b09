"""Unified model API — one handle per architecture for the launcher, the
dry-run, the trainer, the server, tests, and benchmarks.

``ModelApi`` exposes exactly the entry points the rest of the framework
needs, dispatched per family:

    init(key)                          -> params
    loss(params, batch, ctx)           -> (scalar, aux dict)
    prefill(params, batch, ctx, max)   -> (last logits, cache)
    decode_step(params, cache, tok, ctx) -> (logits, cache')
    init_cache(batch, max_len, ctx)    -> cache pytree
    train_input_specs / decode_input_specs -> ShapeDtypeStruct pytrees
    model_flops(shape)                 -> useful-FLOPs convention (6*N*D / 2*N*D)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import encdec as encdec_lib
from . import lm as lm_lib
from .blocks import ShardCtx
from .config import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


# the four assigned shape cells (identical across all ten archs)
SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ModelApi:
    cfg: ModelConfig

    # -- lifecycle -----------------------------------------------------------

    def init(self, key: jax.Array) -> dict:
        if self.cfg.family == "encdec":
            return encdec_lib.init_encdec(self.cfg, key)
        return lm_lib.init_lm(self.cfg, key)

    # -- training ------------------------------------------------------------

    def loss(self, params: dict, batch: dict, ctx: ShardCtx
             ) -> tuple[jax.Array, dict]:
        if self.cfg.family == "encdec":
            return encdec_lib.encdec_loss(params, self.cfg, batch, ctx)
        return lm_lib.lm_loss(params, self.cfg, batch, ctx)

    # -- serving -------------------------------------------------------------

    def prefill(self, params: dict, batch: dict, ctx: ShardCtx,
                max_len: int) -> tuple[jax.Array, dict]:
        if self.cfg.family == "encdec":
            enc_out = encdec_lib.encode(params, self.cfg, batch["frames"], ctx)
            ck, cv = encdec_lib.cross_kv(params, self.cfg, enc_out, ctx)
            cache = encdec_lib.init_encdec_cache(
                self.cfg, enc_out.shape[0], max_len, enc_out.shape[1], ctx)
            cache["cross_k"], cache["cross_v"] = ck, cv
            logits, cache = encdec_lib.encdec_decode_step(
                params, self.cfg, cache, batch["tokens"][:, :1], ctx)
            return logits, cache
        return lm_lib.prefill_lm(params, self.cfg, batch["tokens"], ctx,
                                 max_len,
                                 extra_embeds=batch.get("extra_embeds"))

    def init_cache(self, batch: int, max_len: int, ctx: ShardCtx,
                   enc_len: Optional[int] = None) -> dict:
        if self.cfg.family == "encdec":
            return encdec_lib.init_encdec_cache(
                self.cfg, batch, max_len, enc_len or max_len, ctx)
        return lm_lib.init_lm_cache(self.cfg, batch, max_len, ctx)

    def decode_step(self, params: dict, cache: dict, tokens: jax.Array,
                    ctx: ShardCtx) -> tuple[jax.Array, dict]:
        if self.cfg.family == "encdec":
            return encdec_lib.encdec_decode_step(params, self.cfg, cache,
                                                 tokens, ctx)
        return lm_lib.lm_decode_step(params, self.cfg, cache, tokens, ctx)

    # -- abstract input specs (dry-run: no allocation) -------------------------

    def train_input_specs(self, shape: ShapeSpec) -> dict:
        B, S = shape.global_batch, shape.seq_len
        cfg = self.cfg
        i32 = jnp.int32
        if cfg.family == "encdec":
            return {
                "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
        if cfg.frontend:
            s_text = S - cfg.frontend_len
            return {
                "tokens": jax.ShapeDtypeStruct((B, s_text), i32),
                "labels": jax.ShapeDtypeStruct((B, s_text), i32),
                "extra_embeds": jax.ShapeDtypeStruct(
                    (B, cfg.frontend_len, cfg.d_model), jnp.bfloat16),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }

    def decode_input_specs(self, shape: ShapeSpec, ctx: ShardCtx
                           ) -> tuple[dict, jax.ShapeDtypeStruct]:
        """(cache specs, token specs) for serve_step lowering."""
        B, S = shape.global_batch, shape.seq_len
        enc_len = min(S, 8192) if self.cfg.family == "encdec" else None
        cache = jax.eval_shape(
            lambda: self.init_cache(B, S, ctx, enc_len=enc_len))
        tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        return cache, tokens

    # -- accounting ------------------------------------------------------------

    def model_flops(self, shape: ShapeSpec) -> float:
        """Useful-FLOPs convention: 6*N*D train, 2*N_active*D inference
        (decode: D = one token per sequence)."""
        n = self.cfg.active_param_count()
        if shape.kind == "train":
            return 6.0 * n * shape.tokens
        if shape.kind == "prefill":
            return 2.0 * n * shape.tokens
        return 2.0 * n * shape.global_batch  # decode: one token / sequence

    def flash_ideal_io_bytes(self, shape: ShapeSpec) -> float:
        """Global ideal HBM IO of the kernel-fusable regions (attention /
        SSD cores): what the Pallas kernels move instead of the unfused
        oracle graphs.  Convention: fwd reads q,k,v + writes o; backward
        re-reads q,k,v,o and writes dq,dk,dv (~3x fwd IO); decode reads
        the cache once per step.
        """
        cfg = self.cfg
        B = shape.global_batch
        S = shape.seq_len
        bpe = 2.0  # bf16
        passes = 3.0 if shape.kind == "train" else 1.0

        def attn_call_bytes(s_q: float, s_kv: float) -> float:
            q_o = 2.0 * B * s_q * cfg.q_dim * bpe
            kv = 2.0 * B * s_kv * cfg.kv_dim * bpe
            return q_o + kv

        n_attn, n_ssd = 0, 0
        if cfg.family in ("dense", "vlm", "moe"):
            n_attn = cfg.n_layers
        elif cfg.family == "ssm":
            n_ssd = cfg.n_layers
        elif cfg.family == "hybrid":
            n_ssd = cfg.n_mamba
            n_attn = cfg.attn_sites
        elif cfg.family == "encdec":
            n_attn = cfg.enc_layers + 2 * cfg.n_layers  # self + cross

        if shape.kind == "decode":
            s_kv = min(cfg.window, S) if (cfg.window and cfg.global_every == 0) else S
            attn = n_attn * attn_call_bytes(1, s_kv)
            s_ssm = cfg.ssm
            ssd = n_ssd * (2.0 * B * cfg.d_inner * bpe
                           + 2.0 * B * self.cfg.ssm_heads
                           * (s_ssm.head_dim * s_ssm.d_state) * 4.0) if n_ssd else 0.0
            return attn + ssd
        attn = passes * n_attn * attn_call_bytes(S, S)
        ssd = 0.0
        if n_ssd:
            s_ssm = cfg.ssm
            per_layer = (2.0 * B * S * cfg.d_inner * bpe          # x in, y out
                         + 2.0 * B * S * 2 * s_ssm.n_groups * s_ssm.d_state * bpe)
            ssd = passes * n_ssd * per_layer
        return attn + ssd

    def applicable(self, shape: ShapeSpec) -> tuple[bool, str]:
        """Assignment rules: long_500k only for sub-quadratic attention."""
        cfg = self.cfg
        if shape.name == "long_500k":
            sub_quadratic = (cfg.family in ("ssm", "hybrid")
                             or (cfg.window > 0))
            if not sub_quadratic:
                return False, ("pure full-attention arch — long_500k skipped "
                               "(see DESIGN.md section 5)")
        return True, ""


def build(cfg: ModelConfig) -> ModelApi:
    cfg.validate()
    return ModelApi(cfg)
