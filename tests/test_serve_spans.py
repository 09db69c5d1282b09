"""Profiler spans of the serve token loop: ``Server.generate`` opens
``serve.start`` once per request and ``serve.dispatch``, ``serve.fetch``
and ``serve.deliver`` once per decode step, each with ``request`` and
``step`` metadata, on the profiler's host plane.  The benchmark's readers
split the device's idle time at these spans' edges, so their counts,
metadata and order are checked here; opening them changes no token."""

import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core.mover import UnifiedDataMover
from repro.launch import serve
from repro.launch.serve import Server

N_TOKENS = 5
PER_STEP = ("serve.dispatch", "serve.fetch", "serve.deliver")


def _marked(source):
    """``source``'s items, each with a span opened as the mover takes it."""
    for item in source:
        with jax.profiler.TraceAnnotation("test.taken"):
            pass
        yield item


class _MarkingMover(UnifiedDataMover):
    def streaming_transfer(self, source, sink, **kw):
        return super().streaming_transfer(_marked(source), sink, **kw)


def _host_spans(trace_dir: str) -> list[dict]:
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "test.")):
                    start = int(e.start_ns)
                    out.append({"name": e.name, "start": start,
                                "end": start + int(e.duration_ns),
                                **dict(e.stats)})
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Request 0 with no profiler session (it also compiles), then
    requests 1 and 2 on the same prompts and a fan-out request 3 inside
    one session; in request 2 a span marks each token as the mover takes
    it from the token loop."""
    cfg = get_smoke_config("repro-100m")
    server = Server(cfg, max_len=16 + N_TOKENS)
    server.load()
    batch = {"tokens": np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 16), dtype=np.int32)}
    plain_items: list = []
    plain = server.generate(batch, N_TOKENS, sink=plain_items.append)
    trace_dir = str(tmp_path_factory.mktemp("serve_trace"))
    traced_items: list = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        traced = server.generate(batch, N_TOKENS, sink=traced_items.append)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(serve, "UnifiedDataMover", _MarkingMover)
            server.generate(batch, N_TOKENS, sink=lambda item: None)
        server.generate(batch, N_TOKENS, sink=[lambda item: None] * 2)
    finally:
        jax.profiler.stop_trace()
    return {"spans": _host_spans(trace_dir), "plain": plain,
            "plain_items": plain_items, "traced": traced,
            "traced_items": traced_items}


def _by_step(spans, name):
    """``{(request, step): span}`` of the spans named ``name``."""
    out = {}
    for s in spans:
        if s["name"] == name:
            key = (s["request"], s["step"])
            assert key not in out, f"two {name} spans for {key}"
            out[key] = s
    return out


def test_one_start_span_per_request(served):
    starts = [s["request"] for s in served["spans"]
              if s["name"] == "serve.start"]
    assert sorted(starts) == [1, 2]


@pytest.mark.parametrize("name", PER_STEP)
def test_each_decode_step_opens_one_span(served, name):
    steps = defaultdict(list)
    for s in served["spans"]:
        if s["name"] == name:
            steps[s["request"]].append(s["step"])
    assert {r: sorted(v) for r, v in steps.items()} == {
        1: list(range(N_TOKENS - 1)), 2: list(range(N_TOKENS - 1))}


def test_fan_out_opens_no_spans(served):
    assert not [s for s in served["spans"] if s.get("request") == 3]


def test_fetch_follows_its_dispatch(served):
    dispatch = _by_step(served["spans"], "serve.dispatch")
    fetch = _by_step(served["spans"], "serve.fetch")
    assert dispatch.keys() == fetch.keys()
    for key, f in fetch.items():
        assert f["start"] >= dispatch[key]["end"], key


def test_deliver_starts_after_its_fetch_ends(served):
    fetch = _by_step(served["spans"], "serve.fetch")
    deliver = _by_step(served["spans"], "serve.deliver")
    assert fetch.keys() == deliver.keys()
    for key, d in deliver.items():
        assert d["start"] >= fetch[key]["end"], key


def test_fetch_span_closes_before_the_mover_takes_the_token(served):
    fetch = sorted((s for s in served["spans"]
                    if s["name"] == "serve.fetch" and s["request"] == 2),
                   key=lambda s: s["step"])
    taken = sorted((s for s in served["spans"] if s["name"] == "test.taken"),
                   key=lambda s: s["start"])
    assert len(taken) == len(fetch) == N_TOKENS - 1
    for f, t in zip(fetch, taken):
        assert f["end"] <= t["start"], f["step"]


def test_tokens_unchanged_by_a_profiler_session(served):
    np.testing.assert_array_equal(served["traced"], served["plain"])
    assert served["traced"].shape == (2, N_TOKENS)


def test_sink_items_unchanged_by_a_profiler_session(served):
    assert len(served["traced_items"]) == N_TOKENS - 1
    for got, want in zip(served["traced_items"], served["plain_items"]):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.concatenate(served["traced_items"], axis=1), served["traced"][:, 1:])
