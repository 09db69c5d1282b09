"""Stream integrity and wire compression — the §3.4 compute budget,
placeable on host or accelerator.

The paper's §3.4 point is that integrity/encryption are *budgeted
compute inside the data path*, and "Demystifying the Performance of Data
Transfers" shows what happens when that budget lands on the wrong
resource: a host-side hash pins an otherwise line-rate hop at the CPU's
hash throughput.  This module is the placement seam:

* :class:`StreamDigest` with ``placement="host"`` is the historical
  order-independent stream checksum — XOR of per-item SHA-256 digests,
  bit-identical (format and value) with every prior release.
* ``placement="accel"`` computes per-item fingerprints with the batched
  lattice-digest kernel (:mod:`repro.kernels.digest`): item bytes are
  viewed as uint32 words, reduced blockwise on the accelerator, and
  folded into a 64-bit fingerprint whose XOR over the stream is the
  checksum.  On a TPU backend the compiled Pallas kernel computes it;
  elsewhere the jit-compiled jnp oracle does, which gives the same bits
  (the kernel is gated bit-exact against it).

Both placements are order-independent (concurrent staging workers
deliver out of order) and batch-aware: :meth:`StreamDigest.add_many`
folds a whole slab under one lock acquisition, and the object itself is
a batch-capable stage transform (``__call__`` per item, ``.many`` per
slab) — the hook :meth:`repro.core.staging.Stage._step_batch` looks for.

The two placements produce *different* checksum formats on purpose (64
hex chars vs ``u32:`` + 16): a host digest and an accel digest are not
comparable, so equivalence gates always compare like with like.

Wire compression rides the same seam: :func:`compress_transform` /
:func:`decompress_transform` wrap the blockwise-int8 Pallas kernel
(:mod:`repro.kernels.quantize`; jnp oracle
:mod:`repro.optim.compression`) as batch-capable stage transforms for
float-array item streams (gradient/checkpoint shards) — 4x fewer bytes
on the wire for one budgeted accelerator pass.

jax imports are lazy: a host-placement digest (the default everywhere)
never touches jax, so the core data plane stays importable and fast on
machines without the accelerator stack.
"""

from __future__ import annotations

import hashlib
import threading
from typing import Any, Callable, Iterable, Optional, Sequence

#: uint32 words per digest block (matches the quantize kernel's panel
#: width: 1 KiB of payload per block row)
DIGEST_BLOCK = 256


def as_bytes(item: Any) -> bytes:
    """Stable byte view of an item for integrity hashing."""
    if isinstance(item, (bytes, bytearray)):
        return bytes(item)
    if isinstance(item, memoryview):
        return item.tobytes()
    tobytes = getattr(item, "tobytes", None)
    if tobytes is not None:
        return tobytes()
    if isinstance(item, (tuple, list)):
        return b"".join(as_bytes(e) for e in item)
    if isinstance(item, dict):
        return b"".join(as_bytes(item[k]) for k in sorted(item))
    return repr(item).encode()


def _item_words(data: bytes):
    """Item bytes -> zero-padded uint32 words (little-endian), plus the
    real block count the digest fold keeps."""
    import numpy as np
    n = len(data)
    blocks = max(1, -(-n // (4 * DIGEST_BLOCK)))
    padded = data + b"\0" * (blocks * 4 * DIGEST_BLOCK - n)
    return np.frombuffer(padded, dtype="<u4").reshape(-1, DIGEST_BLOCK), \
        blocks


class StreamDigest:
    """Order-independent integrity over an item stream.

    ``placement="host"``: XOR of per-item SHA-256 digests (commutative +
    associative), shared by the staged, parallel-branch, and direct
    paths so their checksums stay comparable.  ``placement="accel"``:
    XOR of per-item 64-bit lattice fingerprints computed by the batched
    digest kernel: the compiled Pallas kernel on a TPU backend
    (:func:`repro.kernels.ops.on_tpu`), the jit-compiled jnp oracle,
    which gives the same bits, elsewhere.

    Thread-safe; a disabled instance is a no-op.  Usable directly as a
    stage transform: calling it (or :meth:`add`) folds one item and
    returns it; :meth:`many` folds a slab under one lock acquisition and
    returns it — the batch hook the slab worker loop discovers."""

    def __init__(self, enabled: bool, placement: str = "host"):
        if placement not in ("host", "accel"):
            raise ValueError(
                f"placement must be 'host' or 'accel', got {placement!r}")
        self.placement = placement
        self._enabled = bool(enabled)
        self._acc = 0 if enabled else None
        self._lock = threading.Lock()
        self._kernel: Optional[Callable[[Any], Any]] = None

    # -- accel fingerprinting -------------------------------------------------

    def _block_digests(self, panels):
        if self._kernel is None:
            # lazy: the host placement never pays the jax import
            from ..kernels import ops
            from ..kernels.digest import digest_ref
            self._kernel = ops.block_digest if ops.on_tpu() else digest_ref
        return self._kernel(panels)

    def _fingerprint(self, item: Any) -> int:
        import numpy as np
        data = as_bytes(item)
        panels, blocks = _item_words(data)
        d = np.asarray(self._block_digests(panels)[:blocks],
                       dtype=np.uint64)
        mix = (len(data) * 0x9E3779B1) & 0xFFFFFFFF
        hi = int(np.bitwise_xor.reduce(d)) ^ mix
        lo = (int(np.sum(d)) + mix) & 0xFFFFFFFF
        return (hi << 32) | lo

    def _fold_host(self, items: Sequence[Any]) -> int:
        acc = 0
        for it in items:
            acc ^= int.from_bytes(hashlib.sha256(as_bytes(it)).digest(),
                                  "little")
        return acc

    def _fold(self, items: Sequence[Any]) -> int:
        if self.placement == "host":
            return self._fold_host(items)
        acc = 0
        for it in items:
            acc ^= self._fingerprint(it)
        return acc

    # -- stream API -----------------------------------------------------------

    def add(self, item: Any) -> Any:
        if self._acc is not None:
            fold = self._fold((item,))
            with self._lock:
                self._acc ^= fold
        return item

    def add_many(self, items: Sequence[Any]) -> Sequence[Any]:
        """Fold a whole slab: the hashes compute outside the lock and
        the accumulator takes ONE acquisition — the batch-admitted
        counterpart of per-item ``add``, bit-identical in result
        (XOR is order-independent and associative)."""
        if self._acc is not None and items:
            fold = self._fold(items)
            with self._lock:
                self._acc ^= fold
        return items

    # stage-transform protocol: per-item call + the `.many` batch hook
    __call__ = add
    many = add_many

    def absorb_digest(self, item_sha256_hex: str) -> None:
        """Fold a previously recorded per-item SHA-256 into the stream
        accumulator *without the item* — the resume path's stand-in for
        re-hashing a ledger-verified item that is being skipped, so a
        resumed transfer's stream checksum stays bit-identical to an
        unbroken run's.  Host placement only: the resumable ledger
        records host SHA-256 identities (the accel lattice fingerprint
        is a different format by design)."""
        if self._acc is None:
            return
        if self.placement != "host":
            raise ValueError(
                "resume digests fold into the host placement only; "
                "plan the resumed transfer with checksum_placement='host'")
        fold = int.from_bytes(bytes.fromhex(item_sha256_hex), "little")
        with self._lock:
            self._acc ^= fold

    def hexdigest(self) -> Optional[str]:
        if self._acc is None:
            return None
        if self.placement == "host":
            # bit-identical to the historical byte-array accumulator
            return self._acc.to_bytes(32, "little").hex()
        return f"u32:{self._acc:016x}"


# -- wire compression (float-array item streams) -----------------------------


class _BatchTransform:
    """A per-item callable carrying a ``.many`` slab hook."""

    def __init__(self, one: Callable[[Any], Any],
                 many: Callable[[Sequence[Any]], Iterable[Any]]):
        self._one = one
        self.many = many

    def __call__(self, item: Any) -> Any:
        return self._one(item)


def compress_transform(block: int = 256) -> _BatchTransform:
    """Stage transform: float array item -> ``(q int8, scales, shape)``
    via the blockwise-int8 Pallas kernel — the budgeted accelerator pass
    that puts 4x fewer bytes on the wire (oracle:
    :func:`repro.optim.compression.quantize_int8_blockwise`, parity
    gated in ``tests/test_kernels.py``)."""
    from ..kernels.ops import quantize

    def one(x):
        q, s = quantize(x, block=block)
        return q, s, tuple(x.shape)

    return _BatchTransform(one, lambda items: [one(x) for x in items])


def decompress_transform(block: int = 256) -> _BatchTransform:
    """Inverse stage transform: ``(q, scales, shape)`` -> float array."""
    from ..kernels.ops import dequantize

    def one(t):
        q, s, shape = t
        return dequantize(q, s, shape)

    return _BatchTransform(one, lambda items: [one(t) for t in items])
