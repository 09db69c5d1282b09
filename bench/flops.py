"""Operations and bytes that the work needs, counted from shapes.

Counts are of the algorithm, not of what the program happens to run: a
causal prefill attends to the positions so far, a decode step reads the
keys and values written so far and not the padded cache, and recomputed
operations are not counted.  A ``Work`` is (FLOPs, bytes); its least time
on a chip is the larger of FLOPs over the peak rate and bytes over the
memory bandwidth.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from weights import dims, param_spec

BF16_BYTES = 2


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    def least_s(self, peaks) -> float:
        return max(self.flops / peaks.flops_bf16,
                   self.bytes / peaks.hbm_bytes_per_s)


def n_params(hf: dict) -> int:
    L = dims(hf)["L"]
    total = 0
    for shape, _, _, stacked in param_spec(hf).values():
        n = 1
        for s in shape:
            n *= s
        total += n * (L if stacked else 1)
    return total


def _matmul_params(hf: dict) -> tuple[int, int]:
    """(parameters in the layers' matrix products, in the output head)."""
    d = dims(hf)
    per_layer = sum(s[0] * s[1] for s, _, rule, stacked in param_spec(hf).values()
                    if stacked and rule == "matrix")
    return per_layer * d["L"], d["D"] * d["V"]


def weight_bytes(hf: dict) -> int:
    """Bytes of every weight a decode step reads (the embedding table is
    gathered, not read, unless it is also the output head)."""
    d = dims(hf)
    total = 0
    for path, (shape, dtype, _, stacked) in param_spec(hf).items():
        n = 1
        for s in shape:
            n *= s
        total += n * (d["L"] if stacked else 1) * np.dtype(dtype).itemsize
    if not d["tied"]:
        total -= d["V"] * d["D"] * BF16_BYTES
    return total


def kv_bytes_per_token(hf: dict) -> int:
    d = dims(hf)
    return 2 * d["L"] * d["Hkv"] * d["hd"] * BF16_BYTES


# -- dense decoder serving --------------------------------------------------


def attention_prefill(hf: dict, batch: int, seq: int) -> Work:
    """Causal self-attention over a prompt, every layer."""
    d = dims(hf)
    pairs = seq * (seq + 1) / 2
    flops = 4.0 * batch * d["H"] * d["hd"] * pairs * d["L"]
    qkvo = batch * seq * (2 * d["H"] + 2 * d["Hkv"]) * d["hd"] * BF16_BYTES
    return Work(flops, qkvo * d["L"])


def attention_decode(hf: dict, batch: int, keys: int) -> Work:
    """One query per sequence against ``keys`` cached positions, every layer."""
    d = dims(hf)
    flops = 4.0 * batch * d["H"] * d["hd"] * keys * d["L"]
    kv = batch * keys * 2 * d["Hkv"] * d["hd"] * BF16_BYTES
    qo = batch * 2 * d["H"] * d["hd"] * BF16_BYTES
    return Work(flops, (kv + qo) * d["L"])


def prefill_step(hf: dict, batch: int, seq: int) -> Work:
    """A prefill: every layer over the prompt, the head at the last position."""
    layers, head = _matmul_params(hf)
    flops = 2.0 * layers * batch * seq + 2.0 * head * batch
    nbytes = (weight_bytes(hf) + batch * seq * kv_bytes_per_token(hf))
    return Work(flops, nbytes) + attention_prefill(hf, batch, seq)


def decode_step(hf: dict, batch: int, keys: int) -> Work:
    """One decode step whose query attends to ``keys`` positions."""
    layers, head = _matmul_params(hf)
    flops = 2.0 * (layers + head) * batch
    nbytes = weight_bytes(hf) + batch * kv_bytes_per_token(hf)   # K/V written
    return Work(flops, nbytes) + attention_decode(hf, batch, keys)
