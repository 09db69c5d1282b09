"""Small-size versions of the cells, for the benchmark's own tests on the CPU."""

from __future__ import annotations

import copy
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def files(config: str, traffic: str, limits: dict) -> dict:
    """A configuration under a traffic mix, as one-chip cell files, with
    the sizes cut for a CPU (widths included: these are tests, not
    measurements) and the given limits."""
    bench = _load("..", "BENCHMARK.json")
    cell = {"name": f"{config}.{traffic}", "chips": 1}
    hf = copy.deepcopy(_load("configs", config + ".json"))
    traffic = _load("traffic", traffic + ".json")
    hf.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=4, vocab_size=256)
    hf["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                       d_ff=128, vocab=256)
    traffic.update(batch=4, prompt_len=16, gen_tokens=8)
    return {"bench": bench, "cell": cell, "hf": hf, "traffic": traffic,
            "limits": limits}
