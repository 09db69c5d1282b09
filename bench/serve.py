"""Driver of serving mixes (``"kind": "serve"``): ``Server.generate``
streaming greedy tokens through the mover to a client sink.

One client in a closed loop sends request after request, each a batch of
``batch`` prompts of ``prompt_len`` tokens drawn from the seed, and asks for
``gen_tokens`` tokens.  The sink timestamps every arrival.  The window
counts the tokens that reached the sink before it closed and the gaps
between a request's consecutive arrivals; a request still running at the
close runs to its end, outside the window.

Afterwards a sample of finished requests drawn from the seed goes through
the float32 reference (``reference.dense_logits``) with its prompt and
served tokens: the widest gap by which a served token's logit lies below
the reference's best is compared with its limit, and every request's sink
must have received every token in order.
"""

from __future__ import annotations

import gc
import time

import numpy as np

import common
import reference
import devtrace as tr
import weights as W


def prompts(hf: dict, seed: int, request: int, batch: int, length: int):
    """Request ``request``'s prompts; request -1 is the warm-up."""
    rng = np.random.default_rng([seed, 1, request + 1])
    return rng.integers(0, hf["vocab_size"], (batch, length), dtype=np.int32)


def run(*, hf, traffic, limits, seed, seconds, trace, chips, t_start, scratch,
        device_info, faults):
    import jax
    from repro.launch.serve import Server

    B, S, G = traffic["batch"], traffic["prompt_len"], traffic["gen_tokens"]
    cfg = common.model_config(hf)
    server = Server(cfg, max_len=S + G)
    abstract = jax.eval_shape(server.api.init, jax.random.PRNGKey(0))
    W.check_tree(abstract, hf)
    server.params = jax.jit(
        lambda k: W.as_program_tree(W.draw_all(k, hf), abstract))(
            W.run_key(seed))
    programs = [common.Recorded(server._prefill), common.Recorded(server._decode)]
    server._prefill, server._decode = programs
    if "alter_token" in faults:         # a wrong token where it is produced
        decode = server._decode

        def broken(p, c, t):
            logits, cache = decode(p, c, t)
            return logits.at[0, -1, 0].set(1e4), cache
        server._decode = broken

    # warm-up: compiles the prefill and the decode step, and runs the mover
    server.generate({"tokens": prompts(hf, seed, -1, B, S)}, 2,
                    sink=lambda item: None)
    jax.block_until_ready(server.params)
    setup_s = time.monotonic() - t_start

    requests = []                       # (prompt, tokens, [(t, item)])
    trace_dir = f"{scratch}/trace"
    with tr.capture(trace_dir, "window", trace):
        t0 = time.monotonic()
        t_end = t0 + seconds
        while time.monotonic() < t_end:
            prompt = prompts(hf, seed, len(requests), B, S)
            arrivals: list = []

            def sink(item, arrivals=arrivals):
                with tr.span("sink"):
                    arrivals.append((time.monotonic(), item))
            with tr.span("generate"):
                tokens = server.generate({"tokens": prompt}, G, sink=sink)
            requests.append((prompt, tokens, arrivals))
        t_close = time.monotonic()

    served = sum(B for _, _, arr in requests for t, _ in arr if t <= t_end)
    gaps = [b[0] - a[0] for _, _, arr in requests
            for a, b in zip(arr, arr[1:]) if b[0] <= t_end]
    metrics = {"setup_s": setup_s,
               "serve_tokens_per_s": served / seconds,
               "token_gap_p95_ms": 1e3 * common.percentile(gaps, 95)}
    device = device_info()

    # delivery: the sink got each decoded token, in order
    misordered = 0
    for _, tokens, arr in requests:
        got = [item for _, item in arr]
        if (len(got) != G - 1 or tokens.shape != (B, G)
                or not np.array_equal(np.concatenate(got, axis=1),
                                      tokens[:, 1:])):
            misordered += 1

    context = {}
    if trace:
        t = tr.load(trace_dir, "window")
        tr.label(t, [p.hlo_text() for p in programs])
        device.update(busy_s=tr.busy_s(t), window_s=t.window_s)
        work = {"prefill": [(B, S)] * len(requests),
                "decode": [(B, S + 1 + i) for _ in requests
                           for i in range(G - 1)]}
        context = {"trace": t, "work": work, "window_s": t.window_s,
                   "decode_steps": len(requests) * (G - 1)}
        breakdown = {"device_ops": tr.top_ops(t), "idle_gaps": tr.idle_gaps(t)}
    else:
        breakdown = None

    # the reference, once the program's state is freed
    server.params = None
    del server
    gc.collect()
    rng = np.random.default_rng([seed, 2])
    picks = rng.choice(len(requests), size=min(traffic["sample_requests"],
                                               len(requests)), replace=False)
    widest, t_ref = 0.0, time.monotonic()
    samples = []
    for r in picks:
        prompt, tokens, _ = requests[r]
        full = np.concatenate([prompt, tokens[:, :-1]], axis=1)
        logits = reference.dense_logits(hf, seed, full, first=S - 1)
        widest = max(widest, float(_widest_gap(logits, tokens)))
        del logits
        samples.append((full, tokens))
    t_ref = time.monotonic() - t_ref

    readings = {"sink_misordered_requests": misordered,
                "served_logit_gap": widest}
    notes = [f"{len(requests)} requests in {t_close - t0:.3f} s "
             f"(window {seconds} s), {len(gaps)} gaps; reference over "
             f"request(s) {sorted(int(p) for p in picks)} in {t_ref:.1f} s"]
    return {"metrics": metrics, "device": device,
            "readings": readings, "checks": judge(readings, limits),
            "attempted": len(requests), "failed": misordered,
            "context": context, "breakdown": breakdown, "notes": notes,
            "samples": samples}


def judge(readings: dict, limits: dict) -> dict:
    """The comparison that decides ``correct``: each reading beside its
    limit.  The sink order is exact; the logit gap has the cell's limit."""
    return {
        "sink_misordered_requests": common.check(
            readings["sink_misordered_requests"], 0, "=="),
        "served_logit_gap": common.check(readings["served_logit_gap"],
                                         limits["served_logit_gap"]),
    }


def _widest_gap(logits, tokens):
    import jax.numpy as jnp
    picked = jnp.take_along_axis(logits, jnp.asarray(tokens)[..., None], -1)
    return jnp.max(jnp.max(logits, -1) - picked[..., 0])


def control(hf: dict, traffic: dict, seed: int, samples) -> dict:
    """The lower-precision control at the run's own positions: the widest
    gap, under the float32 reference's best, of the tokens that the fp8
    reference puts first.  Its readings replace the program's logit gap;
    the sink's delivery is the program's own."""
    import jax.numpy as jnp
    widest = 0.0
    for full, _ in samples:
        first = traffic["prompt_len"] - 1
        exact = reference.dense_logits(hf, seed, full, first=first)
        low = reference.dense_logits(hf, seed, full, first=first, quant="fp8")
        widest = max(widest, float(_widest_gap(exact, jnp.argmax(low, -1))))
        del exact, low
    return {"served_logit_gap": widest}
