"""Self-check of the benchmark's yardstick, on the CPU:

    JAX_PLATFORMS=cpu python bench/selfcheck.py

* the trace reduction on a small trace in the TPU layout
  (``selfcheck_trace.pbtxt``): busy union, time under a named scope,
  collective time with no compute beside it, program runs, idle gaps;
* the FLOP and byte counts against hand counts: Phi-3-mini 3,821,079,552
  parameters and 393,216 K/V bytes per token;
* that a reference drawing one layer's weights gets the bits the program
  was given.

Exits nonzero on the first mismatch.
"""

from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH]

import numpy as np  # noqa: E402

import flops  # noqa: E402
import devtrace as tr  # noqa: E402
import weights as W  # noqa: E402

US = 1000     # ns

HLO = """HloModule jit_step

%fused_computation.1 (param_0: bf16[8,8]) -> bf16[8,8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  ROOT %dot.1 = bf16[8,8]{1,0} dot(%param_0, %param_0), metadata={op_name="jit(step)/flashable_attention/dot_general"}
}

ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  %fusion.1 = bf16[8,8]{1,0} fusion(%p), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/mul"}
  %fusion.2 = bf16[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/add"}
  ROOT %all-reduce.3 = bf16[8,8]{1,0} all-reduce(%fusion.2), metadata={op_name="jit(step)/psum"}
}
"""


def expect(what: str, got, want) -> None:
    ok = (abs(got - want) <= 1e-9 * max(1.0, abs(want))
          if isinstance(want, float) else got == want)
    print(f"selfcheck: {what}: {got!r} (want {want!r}) {'ok' if ok else 'WRONG'}")
    if not ok:
        sys.exit(1)


def check_trace() -> None:
    from jax.profiler import ProfileData
    with open(os.path.join(BENCH, "selfcheck_trace.pbtxt")) as f:
        text = "".join(l for l in f if not l.lstrip().startswith("#"))
    t = tr.read(ProfileData.from_text_proto(text), "window")
    tr.label(t, [HLO])
    d0 = t.devices[0]
    expect("devices", [d.name for d in t.devices],
           ["/device:TPU:0", "/device:TPU:1"])
    expect("window_s", t.window_s, 20 * US / 1e9)
    expect("device 0 busy", tr.length(tr.busy(d0, t.window)), 9 * US)
    expect("busy_s (mean of 9 and 20 us)", tr.busy_s(t), 14.5 * US / 1e9)
    expect("idle share", tr.idle_share(t), 100.0 * (1 - 14.5 / 20))
    expect("ops get their module", [o.module for o in d0.ops],
           ["jit_step(111)"] * 4 + ["jit_other(222)"])
    expect("attention scope time", tr.scope_s(d0, "flashable_attention",
                                              t.window), 2 * US / 1e9)
    expect("collective exposed", tr.collective_exposed_s(d0, t.window),
           3 * US / 1e9)
    expect("program runs", dict(tr.module_runs(d0)),
           {"jit_step(111)": [10 * US / 1e9], "jit_other(222)": [4 * US / 1e9]})
    expect("idle gaps", tr.idle_gaps(t),
           [["sink", 6 * US / 1e9], ["generate", 4 * US / 1e9],
            ["no host span", 1 * US / 1e9]])


def check_counts() -> None:
    with open(os.path.join(BENCH, "configs", "phi3-mini-3.8b.json")) as f:
        phi3 = json.load(f)
    expect("phi3 parameters", flops.n_params(phi3), 3_821_079_552)
    expect("phi3 K/V bytes per token", flops.kv_bytes_per_token(phi3), 393_216)
    # one decode step, batch 1, one key: 2 x (32 x 113,246,208 + 98,500,608)
    # FLOPs of matrix products + 4 x 32 x 96 x 32 of attention
    expect("phi3 decode-step FLOPs", flops.decode_step(phi3, 1, 1).flops,
           2.0 * (32 * 113_246_208 + 98_500_608) + 4.0 * 32 * 96 * 32)


def check_draws() -> None:
    import jax
    hf = {"family": "dense", "hidden_size": 64, "num_hidden_layers": 3,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "intermediate_size": 96, "vocab_size": 250,
          "tie_word_embeddings": False}
    key = W.run_key(2**31 + 12345)
    whole = jax.jit(lambda k: W.draw_all(k, hf))(key)
    one = jax.jit(lambda k: W.draw_layer(k, hf, 2))(key)
    same = all(np.array_equal(np.asarray(whole[p][2]).view(np.uint8),
                              np.asarray(one[p]).view(np.uint8)) for p in one)
    expect("a layer drawn alone has the stacked draw's bits", same, True)


if __name__ == "__main__":
    check_trace()
    check_counts()
    check_draws()
    print("selfcheck: all ok")
