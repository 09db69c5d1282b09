"""The benchmark's own tests, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/test_bench.py -q

* a run with no accelerator exits nonzero and prints no result;
* a sound run is ``correct``, and one with its timed path broken in each
  way the cell can be broken is not;
* the lower-precision control, judged by the driver's own comparison,
  is not ``correct`` either;
* the self-check of the trace reduction and of the counts.

The limits here are for the small sizes, not the cells' own.  Readings at
these sizes (CPU): the sound served gap of one request reads up to 0.034
over 100 requests on each of 5 seeds, against 3.3 with a token altered;
the fp8 control reads 0.050 to 0.31 over three requests of those seeds,
and the control test reads three.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import run as bench_run  # noqa: E402
import selfcheck  # noqa: E402
import tiny  # noqa: E402

SERVE = ("phi3-mini-3.8b", "serve_decode")
LIMITS = {SERVE: {"served_logit_gap": 0.04}}


def _files(cell):
    return tiny.files(*cell, LIMITS[cell])


def _run(cell, fault: str | None = None, seed: int = 2**31 + 77):
    return bench_run.main(
        ["--workload", ".".join(cell), "--seed", str(seed), "--seconds", "1",
         "--trace", "0"],
        require_chip=False, files=_files(cell),
        faults={fault: True} if fault else {})


def test_no_accelerator_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", ".".join(SERVE), "--seed", "1",
                        "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell", [SERVE])
def test_sound_run_is_correct(cell):
    assert _run(cell)["correct"] is True


@pytest.mark.parametrize("cell,fault", [
    (SERVE, "alter_token"),     # a token altered where it is produced
])
def test_broken_timed_path_is_not_correct(cell, fault):
    assert _run(cell, fault)["correct"] is False


@pytest.mark.parametrize("cell", [SERVE])
def test_lower_precision_control_fails_a_limit(cell):
    import importlib
    f = _files(cell)
    f["traffic"]["sample_requests"] = 3
    driver = importlib.import_module(f["traffic"]["kind"])
    with tempfile.TemporaryDirectory() as scratch:
        out = driver.run(hf=f["hf"], traffic=f["traffic"], limits=f["limits"],
                         seed=5, seconds=0.5, trace=False, chips=1,
                         t_start=time.monotonic(), scratch=scratch,
                         device_info=lambda: {}, faults={})
    assert all(c["ok"] for c in out["checks"].values()), out["checks"]
    readings = dict(out["readings"],
                    **driver.control(f["hf"], f["traffic"], 5, out["samples"]))
    checks = driver.judge(readings, f["limits"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_selfcheck():
    selfcheck.check_trace()
    selfcheck.check_counts()
    selfcheck.check_draws()
