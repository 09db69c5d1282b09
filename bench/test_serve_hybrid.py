"""Tests of the hybrid serving module, on the CPU at a small size:

    JAX_PLATFORMS=cpu python -m pytest bench/test_serve_hybrid.py -q

* a sound run is ``correct``, and one with a token altered where it is
  produced is not;
* the lower-precision control, judged by the comparison that decides
  ``correct``, is not ``correct`` either;
* the program's parameter tree is exactly the drawer's spec at the cell's
  published sizes.

The limit here is for the small size, not the cell's own.  Readings at
this size (CPU), three requests on each of 5 seeds: the sound served gap
reads 0 to 0.00047, the fp8 control 0.0043 to 0.0064; the limit lies 4.2
times above the one and 2.1 times under the other.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import pytest  # noqa: E402

import run as bench_run  # noqa: E402
import serve_hybrid  # noqa: E402
import weights_hybrid  # noqa: E402

CELL = "granite-4.0-h-micro.serve_concurrent"
LIMITS = {"served_logit_gap": 0.002}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _files() -> dict:
    """The cell's files with every size cut for a CPU (widths included:
    these are tests, not measurements); attention at uneven positions."""
    hf = copy.deepcopy(_load("configs", "granite-4.0-h-micro.json"))
    types = ["mamba", "attention", "mamba", "mamba", "attention"]
    hf.update(hidden_size=64, intermediate_size=128, num_hidden_layers=5,
              num_attention_heads=4, num_key_value_heads=2, vocab_size=4096,
              layer_types=types, mamba_n_heads=8, mamba_d_head=16,
              mamba_d_state=16, mamba_chunk_size=8, attention_multiplier=0.0625)
    hf["model"].update(n_layers=5, d_model=64, n_heads=4, n_kv_heads=2,
                       head_dim=16, d_ff=128, vocab=4096, layer_types=types,
                       attention_multiplier=0.0625,
                       ssm=dict(hf["model"]["ssm"], d_state=16, head_dim=16,
                                chunk=8))
    traffic = dict(_load("traffic", "serve_concurrent.json"), batch=4,
                   prompt_len=16, gen_tokens=8)
    return {"bench": _load("..", "BENCHMARK.json"),
            "cell": {"name": CELL, "chips": 1}, "hf": hf, "traffic": traffic,
            "limits": dict(LIMITS)}


def _run(fault: str | None = None, seed: int = 2**31 + 77):
    return bench_run.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
         "--trace", "0"], require_chip=False, files=_files(),
        faults={fault: True} if fault else {})


def test_sound_run_is_correct():
    assert _run()["correct"] is True


def test_altered_token_is_not_correct():
    assert _run("alter_token")["correct"] is False


def test_lower_precision_control_fails_a_limit():
    f = _files()
    f["traffic"]["sample_requests"] = 3
    with tempfile.TemporaryDirectory() as scratch:
        out = serve_hybrid.run(
            hf=f["hf"], traffic=f["traffic"], limits=f["limits"], seed=5,
            seconds=0.5, trace=False, chips=1, t_start=time.monotonic(),
            scratch=scratch, device_info=lambda: {}, faults={})
    assert all(c["ok"] for c in out["checks"].values()), out["checks"]
    readings = dict(out["readings"], **serve_hybrid.control(
        f["hf"], f["traffic"], 5, out["samples"]))
    checks = serve_hybrid.judge(readings, f["limits"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_spec_is_the_program_tree_at_published_sizes():
    import jax
    from common import model_config
    from repro.models import build
    hf = _load("configs", "granite-4.0-h-micro.json")
    abstract = jax.eval_shape(build(model_config(hf)).init,
                              jax.random.PRNGKey(0))
    weights_hybrid.check_tree(abstract, hf)
    n = sum(leaf.size for leaf in jax.tree.leaves(abstract))
    assert 3.15e9 < n < 3.25e9, n


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**33 + 1])
def test_draws_are_the_same_layer_by_layer(seed):
    """The program's stacked draw and the reference's layer draw agree."""
    import jax
    import numpy as np
    import weights as W
    hf = _files()["hf"]
    key = W.run_key(seed)
    whole = weights_hybrid.draw_all(key, hf)
    for stack, n in (("layers", 3), ("attn_layers", 2)):
        for layer in range(n):
            one = weights_hybrid.draw_layer(key, hf, stack, layer)
            for path, v in one.items():
                np.testing.assert_array_equal(
                    np.asarray(jax.device_get(whole[path][layer]), np.float32),
                    np.asarray(v, np.float32))


@pytest.mark.parametrize("metric", ["ssd_roofline.serve",
                                    "ssm_step_roofline.serve"])
def test_kernel_readers_read_nothing_without_their_scope(metric):
    """On a program without the scope (the parent's), on a dense
    configuration or with no trace, the new readers give None and do not
    raise; with the scope they give a share of the roofline."""
    import devtrace as tr
    from peaks import PEAKS
    hf = _files()["hf"]
    work = {"prefill": [(4, 16)], "decode": [(4, 17)]}

    def ctx(meta, hf=hf):
        op = tr.Op(0, 10**6, "fusion.1", meta=meta)
        t = tr.Trace([tr.Device("/device:TPU:0", [op], [])], [], (0, 10**9))
        return {"trace": t, "work": work, "hf": hf,
                "peaks": PEAKS["TPU v5 lite"]}

    scope = "flashable_ssd" if metric.startswith("ssd") else "ssm_step"
    assert bench_run.read_metric(metric, ctx("jit/other")) is None
    assert bench_run.read_metric(metric, {"hf": hf}) is None
    dense = _load("configs", "phi3-mini-3.8b.json")
    assert bench_run.read_metric(metric, ctx(scope, dense)) is None
    assert bench_run.read_metric(metric, ctx(f"jit/{scope}/x")) > 0
