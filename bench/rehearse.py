"""Compile each cell's programs for a TPU v5e that is described, not
attached, at the cell's sizes, and print what the compiler says of their
memory.  Needs no chip: run it on any machine with JAX's TPU compiler.

    JAX_PLATFORMS=cpu python bench/rehearse.py [<cell> ...]

A program that does not fit the chip's memory, or that the compiler
refuses, fails here at no chip time.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P  # noqa: E402

import common  # noqa: E402
import run as bench_run  # noqa: E402


def _gb(n: int) -> str:
    return f"{n / 1e9:.2f} GB"


def report(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"  {name}: arguments {_gb(m.argument_size_in_bytes)}, outputs "
          f"{_gb(m.output_size_in_bytes)}, temporaries "
          f"{_gb(m.temp_size_in_bytes)}, aliased {_gb(m.alias_size_in_bytes)}"
          f"; live at once {_gb(total)}", flush=True)


def rehearse(workload: str, topo) -> None:
    f = bench_run.cell_files(workload)
    hf, traffic, chips = f["hf"], f["traffic"], f["cell"]["chips"]
    cfg = common.model_config(hf)
    devices = topo.devices[:chips]
    mesh = jax.make_mesh((1, chips), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
    print(f"{workload} on {chips} described v5e chip(s):", flush=True)
    from repro.launch.serve import Server
    B, S, G = traffic["batch"], traffic["prompt_len"], traffic["gen_tokens"]
    server = Server(cfg, max_len=S + G)
    one = NamedSharding(mesh, P())
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    params = place(jax.eval_shape(server.api.init, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one)}
    report("prefill", server._prefill.lower(params, batch).compile())
    _, cache = jax.eval_shape(server._prefill, params, batch)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one)
    report("decode step", server._decode.lower(params, place(cache),
                                               tok).compile())


def main(argv=None) -> int:
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    names = argv if argv else [w["name"] for w in bench_run.load_json(
        os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))["workloads"]]
    for name in names:
        rehearse(name, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
