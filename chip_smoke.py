"""Chip smoke run: the system's main paths on a TPU at full smollm-360m width.

    python chip_smoke.py               # one chip: train, checkpoint, serve,
                                       # accel digest
    python chip_smoke.py --four-chips  # every chip of the host: the same
                                       # training on the host mesh and on
                                       # one chip, losses compared

Everything runs in this one process, through the entry points a user
calls: ``Trainer`` fed by ``InputPipeline``, ``CheckpointManager``,
``Server.generate`` streaming through the mover, and a checksummed mover
transfer with the digest placed on the accelerator.  Weights and data
are random from ``--seed``; nothing is downloaded.  Each phase prints its
numbers on earlier lines; the last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU, or when a phase fails, the run exits nonzero and prints
no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-360m"
TRAIN_STEPS = 5
TRAIN_BATCH = 8
TRAIN_SEQ = 2048
SERVE_BATCH = 4
SERVE_PROMPT = 256
SERVE_GEN = 32
DIGEST_ITEMS = 32
DIGEST_ITEM_BYTES = 4 << 20
#: a streamed greedy token may trail the reference prefill's top logit by
#: at most this much: decode and prefill round differently in bf16, so a
#: near-tie may flip, but a token the reference ranks clearly lower is a
#: wrong one.  The control, each token read one decode step off, must
#: exceed it.
SERVE_LOGIT_TOL = 0.25
#: 4-chip vs 1-chip per-step loss agreement (relative): the sharded step
#: reduces in another order, in bf16.  The control, a one-chip run whose
#: update is dropped (lr 0), must exceed it.
LOSS_RTOL = 2e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def phase_train(cfg, mesh, *, steps: int, batch: int, seq_len: int,
                seed: int, frozen: bool = False):
    """A few optimizer steps through ``Trainer.run`` on ``mesh``; returns
    the trainer (holding the trained state) and the per-step losses.
    ``frozen`` trains at learning rate 0, so the state never changes."""
    import numpy as np

    from repro.data.pipeline import PipelineConfig, SyntheticTokenSource
    from repro.launch.train import Trainer

    trainer = Trainer(cfg, mesh, total_steps=steps,
                      **({"lr": 0.0} if frozen else {}))
    t0 = time.monotonic()
    trainer.init_state(seed)
    log(f"[train] init {time.monotonic() - t0:.2f}s mesh={dict(mesh.shape)}")
    pc = PipelineConfig(global_batch=batch, seq_len=seq_len, seed=seed)
    records = trainer.run(SyntheticTokenSource(cfg, pc, n_batches=steps),
                          steps)
    if len(records) != steps:
        raise RuntimeError(f"train ran {len(records)} of {steps} steps")
    stalled = 0.0       # the pipeline reports its stall summed over the run
    for r in records:
        log(f"[train] step {r['step']} loss {r['loss']:.6f} "
            f"time {r['wall_s']:.4f}s "
            f"input_stall {r['input_stall_s'] - stalled:.4f}s")
        stalled = r["input_stall_s"]
    log(f"[train] input stall over the run {stalled:.4f}s")
    losses = [r["loss"] for r in records]
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite loss: {losses}")
    steady = sorted(r["wall_s"] for r in records[1:])
    if steady:
        log(f"[train] steady step time (median of steps 2..{steps}) "
            f"{steady[len(steady) // 2]:.4f}s; tokens/step {batch * seq_len}")
    return trainer, losses


def _same_bits(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and np.array_equal(a.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8)))


def phase_checkpoint(trainer, root: str, *, seed: int) -> None:
    """Save the trainer's params and optimizer state with a
    ``CheckpointManager``, verify the SHA-256 manifest, restore into a
    fresh ``Trainer`` and compare every leaf bit for bit.  Releases the
    given trainer's device state first, so that both fit on one chip."""
    import jax

    from repro.checkpoint.manager import CheckpointManager, verify_checkpoint
    from repro.launch.train import Trainer

    step = trainer.step_idx
    state = {"params": trainer.params, "opt": trainer.opt_state}
    want = jax.device_get(state)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(want))
    mgr = CheckpointManager(root)
    t0 = time.monotonic()
    mgr.maybe_save(step, state, force=True)
    mgr.wait()
    save_s = time.monotonic() - t0
    log(f"[ckpt] saved step {step}: {len(jax.tree.leaves(want))} leaves, "
        f"{nbytes} bytes in {save_s:.2f}s")
    if not verify_checkpoint(root, step):
        raise RuntimeError("checkpoint manifest SHA-256 does not verify")
    log("[ckpt] manifest sha256 verified")

    cfg, mesh = trainer.cfg, trainer.mesh
    trainer.params = trainer.opt_state = None
    del state
    fresh = Trainer(cfg, mesh, ckpt_dir=root)
    fresh.init_state(seed + 1)          # other values, so restore must win
    t0 = time.monotonic()
    if not fresh.try_restore():
        raise RuntimeError("try_restore found no checkpoint")
    jax.block_until_ready((fresh.params, fresh.opt_state))
    restore_s = time.monotonic() - t0
    if fresh.step_idx != step:
        raise RuntimeError(f"restored step {fresh.step_idx} != saved {step}")
    got = {"params": fresh.params, "opt": fresh.opt_state}
    pairs = zip(jax.tree.leaves(want), jax.tree.leaves(got))
    bad = sum(not _same_bits(w, g) for w, g in pairs)
    if bad:
        raise RuntimeError(f"{bad} restored leaves differ from the saved ones")
    log(f"[ckpt] restored step {step} in {restore_s:.2f}s: every leaf "
        f"bit-identical")


def phase_serve(cfg, *, batch: int, prompt_len: int, gen: int,
                seed: int) -> None:
    """Greedy generation through ``Server.generate``, the decode tokens
    streamed through the mover to a sink; one prefill over prompt plus
    generated tokens must rank each streamed token within
    ``SERVE_LOGIT_TOL`` of its top logit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import Server
    from repro.models.lm import forward_lm

    server = Server(cfg, max_len=prompt_len + gen)
    t0 = time.monotonic()
    server.load(seed)
    jax.block_until_ready(server.params)
    log(f"[serve] load {time.monotonic() - t0:.2f}s")
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab, (batch, prompt_len), dtype=np.int32)

    t0 = time.monotonic()
    server.generate({"tokens": prompt}, gen)            # compiles
    log(f"[serve] first generate (compile included) "
        f"{time.monotonic() - t0:.2f}s")
    streamed: list = []
    t0 = time.monotonic()
    tokens = server.generate({"tokens": prompt}, gen, sink=streamed.append)
    dt = time.monotonic() - t0
    if tokens.shape != (batch, gen):
        raise RuntimeError(f"generated {tokens.shape}, want {(batch, gen)}")
    if len(streamed) != gen - 1 or not np.array_equal(
            np.concatenate(streamed, axis=1), tokens[:, 1:]):
        raise RuntimeError("the sink did not receive the decoded tokens")
    log(f"[serve] batch {batch} prompt {prompt_len} gen {gen}: {dt:.3f}s "
        f"{batch * gen / dt:.1f} tok/s; sink got {len(streamed)} streamed "
        f"steps; stream {server.last_report.throughput_bytes_per_s:.0f} B/s")

    full = np.concatenate([prompt, tokens[:, :-1]], axis=1)
    ref = jax.jit(lambda p, t: forward_lm(p, cfg, t, server.ctx)[0])
    logits = ref(server.params, jnp.asarray(full))[:, prompt_len - 1:]
    logits = np.asarray(logits, np.float32)            # (B, gen, V)
    picked = np.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    margin = logits.max(axis=-1) - picked
    # control: each token read against the step before its own, as a
    # decode that takes its token from the wrong step would deliver it
    shifted = np.take_along_axis(logits[:, :-1], tokens[:, 1:, None],
                                 axis=-1)[..., 0]
    control = logits[:, :-1].max(axis=-1) - shifted
    agree = float(np.mean(logits.argmax(axis=-1) == tokens))
    log(f"[serve] reference prefill: argmax agreement {agree:.4f}, max "
        f"logit margin {float(margin.max()):.4f} (tol {SERVE_LOGIT_TOL}), "
        f"finite {bool(np.isfinite(logits).all())}; control one step off: "
        f"max margin {float(control.max()):.4f}, median "
        f"{float(np.median(control)):.4f}")
    if not np.isfinite(logits).all() or float(margin.max()) > SERVE_LOGIT_TOL:
        raise RuntimeError("streamed tokens disagree with the reference")
    if float(control.max()) <= SERVE_LOGIT_TOL:
        raise RuntimeError("the logit gate cannot tell a token from the "
                           "wrong step")


def phase_accel_digest(*, n_items: int, item_bytes: int, seed: int) -> bool:
    """A checksummed mover transfer with the digest placed on the
    accelerator; its stream checksum must equal one ``StreamDigest``
    folding the same items, and the kernel's per-block digests the jnp
    oracle's, bit for bit.  Returns whether the kernel ran compiled (a
    Mosaic custom call in the program)."""
    import jax
    import numpy as np

    from repro.core.basin import checkpoint_basin
    from repro.core.integrity import DIGEST_BLOCK, StreamDigest
    from repro.core.mover import MoverConfig, UnifiedDataMover
    from repro.core.planner import plan_transfer
    from repro.kernels import ops
    from repro.kernels.digest import digest_ref

    rng = np.random.default_rng(seed)
    items = [rng.integers(0, 256, item_bytes, dtype=np.uint8)
             for _ in range(n_items)]
    plan = plan_transfer(checkpoint_basin(), item_bytes,
                         stages=("serialize",), checksum=True,
                         checksum_placement="accel")
    mover = UnifiedDataMover(MoverConfig(checksum=True), plan=plan)
    got: list = []
    t0 = time.monotonic()
    report = mover.bulk_transfer(iter(items), got.append,
                                 transforms=[("serialize", None)])
    dt = time.monotonic() - t0
    direct = StreamDigest(True, placement="accel")
    direct.many(items)
    if report.items != n_items or len(got) != n_items:
        raise RuntimeError(f"moved {report.items} of {n_items} items")
    if report.checksum != direct.hexdigest():
        raise RuntimeError(f"mover checksum {report.checksum} != "
                           f"{direct.hexdigest()} folded directly")
    panels = np.concatenate(items).view("<u4").reshape(-1, DIGEST_BLOCK)
    exact = np.array_equal(np.asarray(ops.block_digest(panels)),
                           np.asarray(digest_ref(panels)))
    compiled = "tpu_custom_call" in jax.jit(ops.block_digest).lower(
        panels).as_text()
    log(f"[digest] {n_items} x {item_bytes} B through the mover in {dt:.3f}s "
        f"({n_items * item_bytes / dt:.0f} B/s); checksum {report.checksum} "
        f"== direct fold; {panels.shape[0]} block digests bit-exact vs "
        f"digest_ref {exact}; "
        f"compiled kernel {compiled}")
    if not exact:
        raise RuntimeError("pallas block digests differ from digest_ref")
    return compiled


def phase_four_chips(cfg, *, steps: int, batch: int, seq_len: int,
                     seed: int) -> None:
    """The same training on the host mesh over every device and on a
    one-device mesh; per-step losses must agree within ``LOSS_RTOL``, and
    a frozen one-device run (its update dropped) must differ by more.
    Every device's peak memory is printed."""
    import jax

    from repro.launch.mesh import make_host_mesh

    trainer, many = phase_train(cfg, make_host_mesh(), steps=steps,
                                batch=batch, seq_len=seq_len, seed=seed)
    del trainer
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    for d, peak in zip(jax.devices(), peaks):
        log(f"[4chip] device {d.id} peak_bytes_in_use {peak}")
    solo_mesh = make_host_mesh((1, 1), ("data", "model"))
    trainer, one = phase_train(cfg, solo_mesh, steps=steps, batch=batch,
                               seq_len=seq_len, seed=seed)
    del trainer
    solo = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"[4chip] device {jax.devices()[0].id} peak_bytes_in_use {solo} "
        f"after the one-device run")
    trainer, frozen = phase_train(cfg, solo_mesh, steps=steps, batch=batch,
                                  seq_len=seq_len, seed=seed, frozen=True)
    del trainer

    def worst_rel(xs):
        return max(abs(a - b) / abs(b) for a, b in zip(xs, one))

    worst, control = worst_rel(many), worst_rel(frozen)
    log(f"[4chip] {len(jax.devices())}-device losses {many}")
    log(f"[4chip] 1-device losses {one}")
    log(f"[4chip] 1-device frozen (lr 0) losses {frozen}")
    log(f"[4chip] worst relative loss difference {worst:.3e} "
        f"(tol {LOSS_RTOL}); control, update dropped: {control:.3e}")
    if worst > LOSS_RTOL:
        raise RuntimeError("sharded and single-device losses disagree")
    if control <= LOSS_RTOL:
        raise RuntimeError("the loss gate cannot tell a dropped update "
                           "from a sound one")
    known = [p for p in peaks if p]
    if known and min(known) < 0.5 * max(known):
        raise RuntimeError(f"peak memory not spread over the devices: {peaks}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the host-mesh vs one-chip training check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    log(f"[device] {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; compile cache {enable_compile_cache()}")
    cfg = get_config(ARCH)
    sizes = dict(steps=TRAIN_STEPS, batch=TRAIN_BATCH, seq_len=TRAIN_SEQ,
                 seed=args.seed)
    try:
        if args.four_chips:
            phase_four_chips(cfg, **sizes)
        else:
            trainer, _ = phase_train(cfg, make_host_mesh(), **sizes)
            with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
                phase_checkpoint(trainer, d, seed=args.seed)
            del trainer
            phase_serve(cfg, batch=SERVE_BATCH, prompt_len=SERVE_PROMPT,
                        gen=SERVE_GEN, seed=args.seed)
            if not phase_accel_digest(n_items=DIGEST_ITEMS,
                                      item_bytes=DIGEST_ITEM_BYTES,
                                      seed=args.seed):
                raise RuntimeError("the accel digest did not run the "
                                   "compiled Pallas kernel")
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
