"""Operations and bytes of a hybrid's Mamba-2 work, counted from shapes.

As in ``flops.py``, counts are of the algorithm: the chunked SSD's
intra-chunk products over the causal pairs of each chunk only, and each
input read and each output written once.
"""

from __future__ import annotations

from flops import BF16_BYTES, Work
from weights_hybrid import dims

F32_BYTES = 4


def ssd_prefill(hf: dict, batch: int, seq: int) -> Work:
    """The chunked SSD of every Mamba-2 layer over a prompt: per chunk, C.B
    per group and, per head, the intra-chunk product, the chunk state's
    contribution and its update; x, dt, B and C read once, y and the final
    state written once."""
    d = dims(hf)
    Q, H, P, N, G = d["chunk"], d["Hm"], d["P"], d["N"], d["G"]
    chunks = -(-seq // Q)
    pairs = Q * (Q + 1) / 2
    flops = batch * chunks * (2 * pairs * N * G
                              + H * (2 * pairs * P + 4 * Q * N * P))
    tokens = batch * seq
    nbytes = (2 * tokens * H * P * BF16_BYTES          # x in, y out
              + tokens * H * F32_BYTES                 # dt
              + 2 * tokens * G * N * BF16_BYTES        # B, C
              + batch * H * P * N * F32_BYTES)         # final state
    return Work(flops, nbytes) * d["Lm"]


def ssm_step_bytes(hf: dict, batch: int) -> float:
    """Bytes of one decode step's conv and SSM states, every Mamba-2 layer,
    each read and written once (both kept in f32)."""
    d = dims(hf)
    state = d["Hm"] * d["P"] * d["N"] + (d["W"] - 1) * d["conv"]
    return 2.0 * batch * state * F32_BYTES * d["Lm"]
