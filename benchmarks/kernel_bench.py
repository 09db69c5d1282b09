"""Kernel microbench: interpret-mode wall time is NOT TPU performance —
what matters here is (a) oracle parity and (b) the analytic VMEM/roofline
characteristics emitted as `derived` (block sizes, ideal IO)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_scan_bhsd

from .common import emit, time_it


def run() -> None:
    k = jax.random.PRNGKey(0)
    B, H, S, hd = 1, 4, 512, 64
    q = jax.random.normal(k, (B, H, S, hd))
    kv = jax.random.normal(jax.random.fold_in(k, 1), (B, 2, S, hd))
    t, out = time_it(lambda: jax.block_until_ready(
        flash_attention_bhsd(q, kv, kv, causal=True, bq=128, bk=128,
                             interpret=True)))
    r = ref.attention_ref(q, kv, kv, causal=True)
    err = float(np.abs(np.asarray(out) - np.asarray(r)).max())
    flops = 4 * B * H * S * S * hd
    ideal_us = flops / 197e12 * 1e6
    emit("kernel/flash_attention_interp", t * 1e6,
         f"maxerr={err:.1e} tpu_ideal={ideal_us:.1f}us "
         f"vmem_per_step={(3*128*hd*2 + 2*128*128*4)/1024:.0f}KiB")

    Bs, Hs, Ss, P, N = 1, 4, 256, 16, 32
    x = jax.random.normal(k, (Bs, Hs, Ss, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 2),
                                           (Bs, Hs, Ss)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 3), (Hs,)) * 0.2)
    Bm = jax.random.normal(jax.random.fold_in(k, 4), (Bs, 1, Ss, N))
    Cm = jax.random.normal(jax.random.fold_in(k, 5), (Bs, 1, Ss, N))
    t, y = time_it(lambda: jax.block_until_ready(
        ssd_scan_bhsd(x, dt, A, Bm, Cm, chunk=64, interpret=True)[0]))
    r = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=64)
    err = float(np.abs(np.asarray(y) - np.asarray(r)).max())
    emit("kernel/ssd_scan_interp", t * 1e6,
         f"maxerr={err:.1e} state_vmem={(P*N*4)/1024:.0f}KiB "
         f"chunk_flops={2*64*64*(N+P)}")

    # lattice digest: the accelerator-placed integrity kernel must be
    # BIT-EXACT against the jnp oracle (uint32 wraparound arithmetic is
    # deterministic on both paths — any mismatch is a kernel bug, not
    # float noise), since the oracle is the digest off the TPU
    from repro.core.integrity import DIGEST_BLOCK
    from repro.kernels.digest import block_digest, digest_ref
    tile = 8
    nb = 64 * tile
    panels = jnp.asarray(
        np.random.default_rng(0).integers(0, 1 << 32, (nb, DIGEST_BLOCK),
                                          dtype=np.uint32))
    t, d = time_it(lambda: jax.block_until_ready(
        block_digest(panels, tile=tile, interpret=True)))
    d_ref = np.asarray(digest_ref(panels))
    exact = bool((np.asarray(d) == d_ref).all())
    emit("kernel/digest_interp", t * 1e6,
         f"exact_parity={exact} blocks={nb} "
         f"bytes={nb * DIGEST_BLOCK * 4 // 1024}KiB")
    if not exact:
        raise SystemExit("kernel/digest_interp: pallas digest diverged "
                         "from the jnp oracle (must be bit-exact)")

    # wire compression roundtrip: the blockwise-int8 stage transform
    # must reconstruct within int8 quantization error
    from repro.core.integrity import compress_transform, decompress_transform
    xs = jax.random.normal(jax.random.fold_in(k, 6), (64, 256)) * 3.0
    comp, decomp = compress_transform(), decompress_transform()
    t, back = time_it(lambda: jax.block_until_ready(decomp(comp(xs))))
    scale = float(jnp.abs(xs).max())
    rerr = float(jnp.abs(back - xs).max()) / max(scale, 1e-9)
    emit("kernel/compress_roundtrip_interp", t * 1e6,
         f"rel_err={rerr:.1e} ratio=4x block=256")
    if rerr > 2.0 / 127.0:
        raise SystemExit(
            f"kernel/compress_roundtrip_interp: reconstruction error "
            f"{rerr:.2e} exceeds int8 quantization bound")
