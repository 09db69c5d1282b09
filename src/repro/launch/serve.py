"""Serving driver: batched prefill + streaming decode through the mover.

The serving path is the paper's two workload classes composed:

* **bulk** — prefill: the prompt batch moves through the stack once and
  the KV cache is staged (the "data at rest" transfer),
* **streaming** — decode: tokens are produced step by step and move to
  the client sink *while being generated*, staged through a burst buffer
  so a slow client never stalls the accelerator (the low-jitter
  decoupling of §2.1),
* **fan-out** — pass ``generate`` a list of client sinks and the token
  stream replicates down one planned branch per client
  (:func:`~repro.core.basin.decode_fanout_basin` + the mover's parallel
  mirror mode): per-branch stage reports let ``replan`` pin a stall on
  the one slow client instead of degrading every stream.  Deliveries run
  through a **per-client drainer pool** (one small buffer + drainer
  thread per client), so one blocking client write no longer serializes
  its siblings at the merge buffer — a transient client stall is
  absorbed by that client's own staging depth while the other streams
  keep flowing.

Usage (CPU smoke):
  python -m repro.launch.serve --arch repro-100m --smoke --batch 4 \
      --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import contextlib
import time
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.basin import decode_fanout_basin, decode_stream_basin
from repro.core.codesign import CodesignPlan
from repro.core.mover import MoverConfig, UnifiedDataMover
from repro.core.planner import plan_transfer
from repro.core.telemetry import TelemetryRegistry, get_registry
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import ShapeSpec, build
from repro.models.blocks import ShardCtx

#: floor for the fed-back client drain-rate estimate — one stalled client
#: must not collapse the next request's plan to a zero-rate basin
MIN_CLIENT_GBPS = 1e-3

#: how many recent serve transfers the drain-rate estimate averages over
DRAIN_RATE_WINDOW = 4

#: a stream counts as client-limited evidence only when its staging hop
#: spent at least this fraction of the transfer backpressured by the sink
CLIENT_LIMITED_STALL = 0.1


def observed_client_gbps(registry: TelemetryRegistry) -> Optional[float]:
    """Client drain rate (Gbps) observed by recent decode streams.

    Only streams the client actually *limited* count as evidence: a
    stream's end-to-end rate is ``min(decode rate, client drain rate)``,
    so a transfer paced by decode compute (no downstream backpressure in
    its stage reports) says nothing about the client — feeding it back
    would ratchet the client-tier estimate down to the producer's rate
    with no way to recover.  Fan-out (mirror) transfers count bytes once
    per client delivery, so their aggregate rate is divided by the branch
    count to recover a per-client estimate.  Returns ``None`` when no
    client-limited stream has been recorded (the modeled default
    applies)."""
    rates = []
    for r in registry.reports("serve"):
        if r.elapsed_s <= 0 or r.bytes <= 0:
            continue
        if not any(s.stall_down_s >= CLIENT_LIMITED_STALL * r.elapsed_s
                   for s in r.stage_reports):
            continue                     # producer-paced: no client evidence
        n_clients = len({s.name.split("/")[0] for s in r.stage_reports
                         if "/" in s.name}) or 1
        rates.append(r.throughput_bytes_per_s / n_clients)
    if not rates:
        return None
    window = rates[-DRAIN_RATE_WINDOW:]
    return max(MIN_CLIENT_GBPS, (sum(window) / len(window)) * 8.0 / 1e9)


def _no_span(name: str, **meta):
    return contextlib.nullcontext()


class Server:
    """Holds params + compiled prefill/decode; streams tokens out through
    a burst buffer."""

    def __init__(self, cfg, mesh=None, *, max_len: int = 512,
                 plan: Optional[CodesignPlan] = None,
                 telemetry: Optional[TelemetryRegistry] = None,
                 replan_every_tokens: int = 0):
        self.cfg = cfg
        self.api = build(cfg)
        self.mesh = mesh
        self.max_len = max_len
        self.plan = plan or CodesignPlan(sharding="tp", seq_parallel=False)
        self.telemetry = telemetry if telemetry is not None else get_registry()
        self.replan_every_tokens = replan_every_tokens
        self.ctx = (steps_lib.make_ctx(self.api, mesh, self.plan)
                    if mesh is not None else ShardCtx())
        self.params = None
        self._requests = 0          # generate calls, the spans' ``request``
        self._prefill = jax.jit(
            lambda p, b: self.api.prefill(p, b, self.ctx, max_len=max_len))
        # the cache is donated: a step writes its K/V into it in place, so
        # a cache passed in is never read again
        self._decode = jax.jit(
            lambda p, c, t: self.api.decode_step(p, c, t, self.ctx),
            donate_argnums=(1,))

    def load(self, seed: int = 0) -> None:
        # one compiled program, not one dispatch per initializer
        self.params = jax.jit(self.api.init)(jax.random.PRNGKey(seed))

    def stream_basin(self):
        """The decode-stream basin, its client tier re-estimated from the
        drain rate previous requests actually observed (telemetry feedback
        between requests — ROADMAP item 2)."""
        drain = observed_client_gbps(self.telemetry)
        if drain is None:
            return decode_stream_basin()
        return decode_stream_basin(client_gbps=drain)

    def fanout_basin(self, n_clients: int):
        """The decode fan-out basin for ``n_clients`` concurrent streams,
        its per-client tier re-estimated from observed drain rates."""
        drain = observed_client_gbps(self.telemetry)
        if drain is None:
            return decode_fanout_basin(n_clients)
        return decode_fanout_basin(n_clients, client_gbps=drain)

    def generate(self, batch: dict, n_tokens: int,
                 sink=None) -> np.ndarray:
        """Greedy-decode ``n_tokens``; each step's tokens stream to ``sink``
        through the unified mover (streaming transfer).  Staging depth
        comes from the decode-stream basin plan — sized so an erratic
        client never stalls the accelerator; the plan is ``ordered``
        because the token stream must arrive in decode order.  The basin's
        client tier is re-estimated from observed drain rates between
        requests, and with ``replan_every_tokens`` set the plan also
        revises online inside one long generation.

        ``sink`` may be a *list* of callables — concurrent client streams.
        The token stream then replicates down one planned branch per
        client (decode fan-out, mover parallel mirror mode): every client
        receives every token, each branch carries its own staging depth,
        and the per-branch stage reports attribute a stall to the one
        slow client.  Deliveries drain through the mover's per-client
        drainer pool, so one client blocking on a write stalls only its
        own stream while its siblings keep receiving.

        With one sink the request opens profiler spans, each with its
        ``request`` (the count of ``generate`` calls) and, per decode step,
        its ``step``: ``serve.start`` from entry until the stream starts,
        then per step ``serve.dispatch`` (the decode call and its argmax),
        ``serve.fetch`` (the token's host copy) and ``serve.deliver``
        (the sink).  With no profiler session each costs about a
        microsecond.

        Returns every generated token, (batch, n_tokens), whatever the
        sinks."""
        request = self._requests
        self._requests += 1
        sinks = list(sink) if isinstance(sink, (list, tuple)) else None
        fan_out = bool(sinks) and len(sinks) > 1
        # profiler spans on the single-sink path; the fan-out path has no
        # benchmark cell to read them yet
        span = _no_span if fan_out else jax.profiler.TraceAnnotation
        with span("serve.start", request=request):
            logits, cache = self._prefill(self.params, batch)
            tok = jnp.argmax(logits[:, -1], axis=-1,
                             keepdims=True).astype(jnp.int32)
            out = [np.asarray(tok)]
            n_batch = int(tok.shape[0])
            basin = (self.fanout_basin(len(sinks)) if fan_out
                     else self.stream_basin())
            plan = plan_transfer(basin, item_bytes=max(1, n_batch * 4),
                                 stages=("token-stream",), ordered=True,
                                 path="auto")
            mover = UnifiedDataMover(MoverConfig(checksum=False), plan=plan,
                                     telemetry=self.telemetry, layer="serve")

        def produce() -> Iterator[np.ndarray]:
            nonlocal tok, cache
            for step in range(n_tokens - 1):
                with span("serve.dispatch", request=request, step=step):
                    logits_i, cache = self._decode(self.params, cache, tok)
                    tok = jnp.argmax(logits_i[:, -1], axis=-1,
                                     keepdims=True).astype(jnp.int32)
                with span("serve.fetch", request=request, step=step):
                    host = np.asarray(tok)
                # yielded outside the fetch span: the handoff to the
                # mover is not the fetch
                yield host

        collected: list[np.ndarray] = []
        if fan_out:
            # branch order follows basin link order == client order
            sink_map = {b.branch_id: s
                        for b, s in zip(plan.branches, sinks)}
            first = plan.branches[0].branch_id
            first_sink = sink_map[first]

            def tee(item):
                collected.append(item)
                first_sink(item)

            sink_map[first] = tee
            report = mover.parallel_transfer(
                produce(), sink_map, plan=plan, mode="mirror",
                replan_every_items=self.replan_every_tokens,
                drainer_pool=True)
        else:
            one_sink = sinks[0] if sinks else sink

            def deliver(item):
                with span("serve.deliver", request=request,
                          step=len(collected)):
                    collected.append(item)
                    if one_sink is not None:
                        one_sink(item)

            report = mover.streaming_transfer(
                produce(), deliver, plan=plan,
                replan_every_items=self.replan_every_tokens)
        out.extend(collected)
        self.last_report = report
        return np.concatenate(out, axis=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    server = Server(cfg, max_len=args.prompt_len + args.gen + 1)
    server.load()
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab,
                                    (args.batch, args.prompt_len),
                                    dtype=np.int32)}
    if cfg.family == "encdec":
        batch["frames"] = rng.standard_normal(
            (args.batch, args.prompt_len, cfg.d_model)).astype(np.float32)
    if cfg.frontend:
        batch["extra_embeds"] = rng.standard_normal(
            (args.batch, cfg.frontend_len, cfg.d_model)).astype(np.float32)

    t0 = time.monotonic()
    tokens = server.generate(batch, args.gen)
    dt = time.monotonic() - t0
    tps = args.batch * args.gen / dt
    print(f"[serve] generated {tokens.shape} in {dt:.2f}s ({tps:.1f} tok/s)")
    print(f"[serve] stream fidelity: throughput="
          f"{server.last_report.throughput_bytes_per_s:.0f} B/s "
          f"bottleneck={server.last_report.bottleneck_stage().name if server.last_report.stage_reports else 'n/a'}")


if __name__ == "__main__":
    main()
