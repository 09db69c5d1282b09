"""Driver of hybrid serving mixes (``"kind": "serve_hybrid"``): a Mamba-2
and attention hybrid through ``Server.generate``, streaming greedy tokens
through the mover to a client sink.

The loop, the window and the checks are ``serve.py``'s: one client in a
closed loop sends request after request, each a batch of ``batch``
prompts of ``prompt_len`` tokens drawn from the seed, and asks for
``gen_tokens`` tokens; the sink timestamps every arrival.  What differs is
the model's side: weights from ``weights_hybrid.py``, the float32
reference ``reference_hybrid.hybrid_logits`` (computed in blocks of
sequences, each block's gap taken before the next is computed) and, for
the traced run, the work of the prefill's SSD and of the decode's state
update for the kernel readers.
"""

from __future__ import annotations

import gc
import time

import jax.numpy as jnp
import numpy as np

import common
import devtrace as tr
import reference_hybrid as ref
import serve
import weights as W
import weights_hybrid as WH

judge = serve.judge


def run(*, hf, traffic, limits, seed, seconds, trace, chips, t_start, scratch,
        device_info, faults):
    import jax
    from repro.launch.serve import Server

    B, S, G = traffic["batch"], traffic["prompt_len"], traffic["gen_tokens"]
    cfg = common.model_config(hf)
    server = Server(cfg, max_len=S + G)
    abstract = jax.eval_shape(server.api.init, jax.random.PRNGKey(0))
    WH.check_tree(abstract, hf)
    server.params = jax.jit(
        lambda k: W.as_program_tree(WH.draw_all(k, hf), abstract))(
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
    server.generate({"tokens": serve.prompts(hf, seed, -1, B, S)}, 2,
                    sink=lambda item: None)
    jax.block_until_ready(server.params)
    setup_s = time.monotonic() - t_start

    requests = []                       # (prompt, tokens, [(t, item)])
    trace_dir = f"{scratch}/trace"
    with tr.capture(trace_dir, "window", trace):
        t0 = time.monotonic()
        t_end = t0 + seconds
        while time.monotonic() < t_end:
            prompt = serve.prompts(hf, seed, len(requests), B, S)
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
        widest = max(widest, _gap(hf, seed, full, tokens, S - 1, "f32"))
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


def _gap(hf, seed, full, tokens, first, quant) -> float:
    """Widest gap, over blocks of sequences, by which the logit of the
    token served (with ``quant`` lower, of the token that reference puts
    first) lies under the float32 reference's best."""
    widest = 0.0
    for b0 in range(0, full.shape[0], ref.SEQ_BLOCK):
        rows = slice(b0, b0 + ref.SEQ_BLOCK)
        exact = ref.hybrid_logits(hf, seed, full[rows], first)
        chosen = tokens[rows]
        if quant != "f32":
            low = ref.hybrid_logits(hf, seed, full[rows], first, quant=quant)
            chosen = jnp.argmax(low, -1)
            del low
        widest = max(widest, float(serve._widest_gap(exact, chosen)))
        del exact
    return widest


def control(hf: dict, traffic: dict, seed: int, samples) -> dict:
    """The lower-precision control at the run's own positions: the widest
    gap, under the float32 reference's best, of the tokens that the fp8
    reference puts first.  Its readings replace the program's logit gap;
    the sink's delivery is the program's own."""
    first = traffic["prompt_len"] - 1
    return {"served_logit_gap": max(
        _gap(hf, seed, full, tokens, first, "fp8") for full, tokens in samples)}
