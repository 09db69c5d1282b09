"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/control.py --workload <cell> --seeds 11,12,... \
        --control-seeds 3 --seconds 0.001

For each seed, in one process, the cell runs at its own size with a short
window (one request at the cell's batch and lengths), and prints the
numbers its reference compares: the lower reading is the largest over the
seeds.  For the first ``--control-seeds`` seeds the control runs too: the
reference in the precision below the one the configuration states (fp8
for bf16), put in the program's place.  Its readings go through the
driver's own comparison with the cell's limits, so each line says whether
the control came out ``correct``; the smallest of its readings is the
upper reading.  The benchmark's own runs never run any of this.  Needs
the chips the cell asks for.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.001)
    args = ap.parse_args(argv)
    f = bench_run.cell_files(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < f["cell"]["chips"]:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    bench_run.enable_cache(jax)
    driver = importlib.import_module(f["traffic"]["kind"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        with tempfile.TemporaryDirectory(prefix="bench_") as scratch:
            out = driver.run(hf=f["hf"], traffic=f["traffic"],
                             limits=f["limits"], seed=seed,
                             seconds=args.seconds, trace=False,
                             chips=f["cell"]["chips"], t_start=t0,
                             scratch=scratch, device_info=lambda: {},
                             faults={})
        line = {"seed": seed, "program": out["readings"],
                "program_correct": all(c["ok"] for c in out["checks"].values())}
        if i < args.control_seeds:
            readings = dict(out["readings"], **driver.control(
                f["hf"], f["traffic"], seed, out["samples"]))
            checks = driver.judge(readings, f["limits"])
            line["control"] = readings
            line["control_correct"] = all(c["ok"] for c in checks.values())
        line["seconds"] = time.monotonic() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
