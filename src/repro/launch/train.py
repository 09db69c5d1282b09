"""Training driver: the full co-designed data path, end to end.

    dataset -> burst-buffered input pipeline -> pjit train_step
            -> async checksummed checkpoints -> restart recovery

Fault tolerance (DESIGN.md §7):
* periodic async checkpoints (manifest-atomic, SHA-256 per shard),
* automatic restart discovery (newest complete manifest),
* step-failure recovery: a failing step restores the last checkpoint and
  resumes (``--inject-failure`` exercises this in tests/examples),
* elastic restore: checkpoints re-shard onto whatever mesh the restarted
  job has.

Usage (the mesh spans every local device: one TPU chip, a 2x2 host,
or the CPU; ``chip_smoke.py`` drives this path at full width on a TPU):
  python -m repro.launch.train --arch repro-100m --steps 50 \
      --global-batch 8 --seq-len 256 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.core.basin import tpu_input_basin
from repro.core.codesign import CodesignPlan
from repro.core.telemetry import get_registry
from repro.data.pipeline import InputPipeline, PipelineConfig, SyntheticTokenSource
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.api import build
from repro.optim.adamw import adamw_init
from repro.parallel.sharding import batch_axes_of


class Trainer:
    """Owns the step function, state, pipeline, and recovery logic."""

    def __init__(self, cfg, mesh, *, plan: Optional[CodesignPlan] = None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 lr: float = 3e-4, total_steps: int = 1000):
        self.cfg = cfg
        self.api = build(cfg)
        self.mesh = mesh
        self.plan = plan or CodesignPlan(sharding="fsdp_tp", microbatches=1,
                                         remat=cfg.remat,
                                         seq_parallel=False)
        # warmup must fit inside the run: the default 100-step warmup never
        # reaches peak lr on short runs (smoke tests, examples)
        warmup = max(1, min(100, total_steps // 5))
        (self.train_step, self.p_shard, self.s_shard,
         self.ctx) = steps_lib.make_train_step(
            self.api, mesh, self.plan, lr_peak=lr, warmup=warmup,
            total_steps=total_steps)
        self.ckpt = (CheckpointManager(ckpt_dir, every_steps=ckpt_every)
                     if ckpt_dir else None)
        self.params = None
        self.opt_state = None
        self.step_idx = 0
        self.metrics_log: list[dict] = []

    # -- state ---------------------------------------------------------------

    def init_state(self, seed: int = 0) -> None:
        key = jax.random.PRNGKey(seed)
        params = jax.jit(self.api.init, out_shardings=self.p_shard)(key)
        opt = jax.jit(adamw_init, out_shardings=self.s_shard)(params)
        self.params, self.opt_state = params, opt

    def try_restore(self) -> bool:
        """Resume from the newest complete checkpoint, re-sharded onto the
        current mesh (elastic)."""
        if self.ckpt is None:
            return False
        like = {"params": self.params, "opt": self.opt_state}
        shardings = {"params": self.p_shard, "opt": self.s_shard}
        step, state = self.ckpt.restore_latest(like, shardings=shardings)
        if step is None:
            return False
        self.params, self.opt_state = state["params"], state["opt"]
        self.step_idx = step
        return True

    # -- loop ----------------------------------------------------------------

    def run(self, source, n_steps: int, *, inject_failure_at: int = -1,
            replan_every: int = 0, telemetry_json: Optional[str] = None,
            telemetry_every: int = 10,
            telemetry_jsonl: Optional[str] = None) -> list[dict]:
        """Train ``n_steps``.  ``replan_every > 0`` folds observed input
        stall ratios and service-time samples back into the transfer plan
        *online*, every that many batches, at a buffer boundary inside the
        running stream (one batch = one item, so the step cadence and the
        item cadence coincide) — no staged batch is dropped and the
        revision takes effect mid-run, not at the next epoch.  Logged
        fidelity gaps always measure against the plan the stream started
        with.  ``telemetry_json`` dumps the cross-layer
        :class:`~repro.core.telemetry.TelemetryRegistry` to that path every
        ``telemetry_every`` steps (atomic rename — safe to poll);
        ``telemetry_jsonl`` additionally *appends* one snapshot line per
        flush to that path — a time series the trend example
        (``examples/telemetry_timeseries.py``) reads back."""
        pc = getattr(source, "pc", None)
        pipeline = InputPipeline(
            source, basin=tpu_input_basin(), pc=pc, mesh=self.mesh,
            batch_axes=batch_axes_of(self.mesh),
            # None defers to pc.replan_every_items; an unset flag must not
            # silently disable a cadence the PipelineConfig asked for
            replan_every_items=replan_every if replan_every else None)
        it = iter(pipeline)
        done = 0
        while done < n_steps:
            batch = next(it, None)
            if batch is None:
                break
            try:
                if self.step_idx == inject_failure_at:
                    inject_failure_at = -1          # fail exactly once
                    raise RuntimeError("injected node failure")
                t0 = time.monotonic()
                # the step time covers the whole update, not the enqueue
                self.params, self.opt_state, metrics = jax.block_until_ready(
                    self.train_step(self.params, self.opt_state, batch))
                loss = float(metrics["loss"])
                dt = time.monotonic() - t0
            except RuntimeError as e:
                if "injected" not in str(e):
                    raise
                # node-failure path: restore + resume (paper: the data path
                # must survive erratic components)
                restored = self.try_restore()
                if not restored:
                    self.init_state()
                continue
            self.step_idx += 1
            done += 1
            rec = {"step": self.step_idx, "loss": loss, "wall_s": dt,
                   "input_stall_s": pipeline.consumer_stall_s(),
                   "input_fidelity_gap": pipeline.fidelity_gap()}
            self.metrics_log.append(rec)
            if done % max(1, telemetry_every) == 0:
                if telemetry_json:
                    get_registry().dump_json(telemetry_json)
                if telemetry_jsonl:
                    get_registry().append_jsonl(telemetry_jsonl)
            if self.ckpt is not None:
                self.ckpt.maybe_save(self.step_idx, {
                    "params": self.params, "opt": self.opt_state})
        pipeline.record_telemetry()
        if telemetry_json:
            get_registry().dump_json(telemetry_json)
        if telemetry_jsonl:
            get_registry().append_jsonl(telemetry_jsonl)
        if self.ckpt is not None:
            self.ckpt.wait()
            self.ckpt.maybe_save(self.step_idx, {
                "params": self.params, "opt": self.opt_state}, force=True)
            self.ckpt.wait()
        return self.metrics_log


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-failure-at", type=int, default=-1)
    ap.add_argument("--replan-every", type=int, default=0,
                    help="revise the transfer plan online from observed "
                         "stalls and service-time samples every N batches, "
                         "at a buffer boundary inside the running stream "
                         "(0 = off)")
    ap.add_argument("--telemetry-json", default=None, metavar="PATH",
                    help="periodically dump the cross-layer telemetry "
                         "registry to PATH as JSON (atomic rename; for "
                         "dashboards)")
    ap.add_argument("--telemetry-every", type=int, default=10,
                    help="step cadence of --telemetry-json/-jsonl dumps")
    ap.add_argument("--telemetry-jsonl", default=None, metavar="PATH",
                    help="append one telemetry snapshot per flush to PATH "
                         "as a JSONL time series (see "
                         "examples/telemetry_timeseries.py)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh()
    trainer = Trainer(cfg, mesh, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, lr=args.lr,
                      total_steps=args.steps)
    trainer.init_state(args.seed)
    if trainer.try_restore():
        print(f"[train] resumed from step {trainer.step_idx}")

    pc = PipelineConfig(global_batch=args.global_batch, seq_len=args.seq_len,
                        seed=args.seed)
    source = SyntheticTokenSource(cfg, pc, n_batches=args.steps + 8)
    log = trainer.run(source, args.steps,
                      inject_failure_at=args.inject_failure_at,
                      replan_every=args.replan_every,
                      telemetry_json=args.telemetry_json,
                      telemetry_every=args.telemetry_every,
                      telemetry_jsonl=args.telemetry_jsonl)
    for rec in log[-5:]:
        gap = rec.get("input_fidelity_gap")
        gap_s = f" gap {gap:+.3f}" if gap is not None else ""
        print(f"[train] step {rec['step']:5d} loss {rec['loss']:.4f} "
              f"wall {rec['wall_s']*1e3:.1f} ms "
              f"stall {rec['input_stall_s']:.3f}s{gap_s}")
    losses = [r["loss"] for r in log]
    if len(losses) >= 10:
        print(f"[train] loss {losses[0]:.4f} -> {losses[-1]:.4f} "
              f"({'improved' if losses[-1] < losses[0] else 'NOT improved'})")
    print("[train] transfer telemetry (all layers):")
    for line in get_registry().format_summary().splitlines():
        print(f"[train]   {line}")


if __name__ == "__main__":
    main()
