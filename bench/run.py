"""Run one benchmark cell on the accelerator and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix come from BENCHMARK.json
at the root of the checkout, and each is a file found by its name:
``bench/configs/<config>.json`` (sizes, as run), ``bench/traffic/<mix>.json``
(the mix's parameters; its ``kind`` names the driver module, such as
``serve.py``), ``bench/limits/<cell>.json`` (the limit of each number
compared with the reference) and ``bench/metrics/<metric>.py`` (the reader
of one per-layer metric).  Weights, inputs and data come from ``--seed``.

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and from the program's counters.  The last line of standard output
is one JSON object; the numbers compared with the reference, each beside
its limit, close standard error and the result line.  Without an
accelerator, or with fewer chips than the cell asks for, the run exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # set-up is timed from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

#: JAX's persistent compilation cache: a fixed directory in the checkout,
#: so that every run after a cell's first finds its programs there
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(name: str) -> dict:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "bench": bench,
        "cell": cell,
        "hf": load_json(os.path.join(ROOT, config["file"])),
        "traffic": load_json(os.path.join(BENCH, "traffic",
                                          cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(BENCH, "limits", name + ".json")),
    }


def reported(metrics: list[dict], cell: dict, e2e: set[str]) -> list[dict]:
    """The metrics of ``metrics`` that this cell reports."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif "moves" not in m or m["moves"] in e2e:
            out.append(m)
    return out


def read_metric(name: str, ctx: dict):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def enable_cache(jax) -> None:
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # no eviction, whatever the environment sets: an evicted program
    # compiles again inside the next run's set-up
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, *, require_chip: bool = True, files: dict | None = None,
         faults=None) -> dict | None:
    """One run; returns the result, or None when there is no chip.

    ``require_chip``, ``files`` and ``faults`` are for the benchmark's own
    tests: they run a cell at a small size on the CPU, with the timed path
    broken in a known way."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    f = files or cell_files(args.workload)
    cell = f["cell"]

    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return None
    enable_cache(jax)
    from peaks import peaks_for
    peaks = peaks_for(devs[0].device_kind) if require_chip else None

    driver = importlib.import_module(f["traffic"]["kind"])
    with tempfile.TemporaryDirectory(prefix="bench_") as scratch:
        run = driver.run(
            hf=f["hf"], traffic=f["traffic"], limits=f["limits"],
            seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            chips=cell["chips"], t_start=T_START, scratch=scratch,
            device_info=lambda: device_info(jax, cell["chips"]),
            faults=faults or {})
        gc.collect()

    bench = f["bench"]
    e2e = {m["name"] for m in reported(bench["end_to_end"], cell, set())}
    metrics = {}
    if args.trace:
        ctx = dict(run["context"], hf=f["hf"], traffic=f["traffic"],
                   peaks=peaks, chips=cell["chips"])
        for m in reported(bench["per_layer"], cell, e2e):
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in reported(bench["end_to_end"], cell, set()):
            metrics[m["name"]] = {"value": run["metrics"][m["name"]],
                                  "unit": m["unit"]}
    checks = run["checks"]
    correct = all(c["ok"] for c in checks.values())
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics,
              "device": run["device"]}
    if args.trace and run.get("breakdown"):
        result["breakdown"] = run["breakdown"]
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for line in run.get("notes", []):
        print(f"bench: {line}", file=sys.stderr)
    for k, c in checks.items():
        print(f"bench: check {k} = {c['value']!r} (limit {c['limit']!r}, "
              f"{c['rule']}) {'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    sys.exit(0 if main() is not None else 2)
