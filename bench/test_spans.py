"""The readers of the serve token loop's spans, on a hand-made trace:

    JAX_PLATFORMS=cpu python -m pytest bench/test_spans.py -q

``spans_trace.pbtxt`` holds two requests of three decode steps, with
device 0's idle time inside each part counted by hand in its header.
Each reader must give those counts, and nothing (None, not 0) on a trace
without the program's spans, as the program before them left, or with a
span missing.  In the trace each decode run starts at least 3 us after
its dispatch opens and ends at least 1 us before its fetch closes, so a
device clock read more than 3 us early or 1 us late must be brought back
to that bound.
"""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import devtrace as tr  # noqa: E402
import run as bench_run  # noqa: E402
import servespans  # noqa: E402

US = 1000     # ns
READERS = {   # metric: hand count (ms)
    "token_fetch_ms.serve": 3 * US / 1e6,
    "stream_turnaround_ms.serve": 1.5 * US / 1e6,
    "dispatch_ms.serve": 2.5 * US / 1e6,
    "stream_handoff_ms.serve": 2.75 * US / 1e6,
    "request_start_ms.serve": 6 * US / 1e6,
}


def _trace(name: str) -> tr.Trace:
    from jax.profiler import ProfileData
    with open(os.path.join(BENCH, name)) as f:
        text = "".join(l for l in f if not l.lstrip().startswith("#"))
    return tr.read(ProfileData.from_text_proto(text), "window")


def _shifted(ns: int) -> tr.Trace:
    """The hand-made trace with the device's events moved by ``ns``."""
    t = _trace("spans_trace.pbtxt")
    for dev in t.devices:
        for o in dev.ops + dev.modules:
            o.start += ns
            o.end += ns
    return t


def _readings(trace: tr.Trace) -> dict:
    return {m: bench_run.read_metric(m, _ctx(trace)) for m in READERS}


def _ctx(trace: tr.Trace) -> dict:
    return {"trace": trace, "decode_steps": 6, "traffic": {"gen_tokens": 4}}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_gives_the_hand_count(metric):
    got = bench_run.read_metric(metric, _ctx(_trace("spans_trace.pbtxt")))
    assert got == pytest.approx(READERS[metric], rel=1e-12)


def test_parts_are_the_hand_counts():
    loop = servespans.read(_ctx(_trace("spans_trace.pbtxt")))
    assert [i // US for i in loop.fetch_idle()] == [3, 5, 3, 3, 2, 2]
    assert [i // US for i in loop.turnaround_idle()] == [3, 1, 2, 1]
    assert [i // US for i in loop.dispatch_idle()] == [2, 3, 3, 2]
    assert [i // US for i in loop.start_idle()] == [7, 5]
    assert [i // US for i in loop.handoff()] == [1, 1, 2, 1, 3, 1]


def test_parts_and_the_gaps_between_requests_make_up_the_idle_time():
    t = _trace("spans_trace.pbtxt")
    loop = servespans.read(_ctx(t))
    parts = sum(loop.fetch_idle() + loop.turnaround_idle()
                + loop.dispatch_idle() + loop.start_idle())
    outside = (loop.idle(t.window[0], loop.starts[0].start)
               + loop.idle(loop.fetch[2].end, loop.starts[1].start)
               + loop.idle(loop.fetch[-1].end, t.window[1]))
    idle = tr.length(tr.subtract([t.window], tr.busy(t.devices[0], t.window)))
    assert (parts, outside, idle) == (47 * US, 18 * US, 65 * US)


def test_a_clock_inside_the_causal_bounds_is_left_as_it_is():
    t = _trace("spans_trace.pbtxt")
    decode = servespans.decode_runs(t.devices[0], 6)
    spans = {n: sorted((s for s in t.spans if s.name == n),
                       key=lambda s: s.start)
             for n in (servespans.DISPATCH, servespans.FETCH)}
    assert [m.start // US for m in decode] == [16, 32, 48, 73, 88, 97]
    assert servespans.offsets(spans[servespans.DISPATCH],
                              spans[servespans.FETCH], decode) == [0] * 6


@pytest.mark.parametrize("early_us", [3.5, 4])
def test_a_device_clock_read_early_is_brought_to_the_dispatch_bound(early_us):
    # 3 us early puts the first decode run's start on its dispatch's start;
    # counted by hand there: fetch 5 7 5 | 5 4 3, dispatch 0 1 | 1 1,
    # turnaround 3 1 | 2 1, request start 6 | 5
    assert _readings(_shifted(-int(early_us * US))) == pytest.approx({
        "token_fetch_ms.serve": 5 * US / 1e6,
        "stream_turnaround_ms.serve": 1.5 * US / 1e6,
        "dispatch_ms.serve": 1 * US / 1e6,
        "stream_handoff_ms.serve": 2.75 * US / 1e6,
        "request_start_ms.serve": 5.5 * US / 1e6}, rel=1e-12)


@pytest.mark.parametrize("late_us", [1.5, 4, 12])
def test_a_device_clock_read_late_is_brought_to_the_fetch_bound(late_us):
    # 1 us late puts the last two decode runs' ends on their fetches' ends,
    # where each part reads as it does unshifted
    assert _readings(_shifted(int(late_us * US))) == pytest.approx(
        READERS, rel=1e-12)


def test_the_split_still_makes_up_the_idle_time_once_shifted():
    t = _shifted(-4 * US)
    loop = servespans.read(_ctx(t))
    parts = sum(loop.fetch_idle() + loop.turnaround_idle()
                + loop.dispatch_idle() + loop.start_idle())
    outside = (loop.idle(t.window[0], loop.starts[0].start)
               + loop.idle(loop.fetch[2].end, loop.starts[1].start)
               + loop.idle(loop.fetch[-1].end, t.window[1]))
    assert parts + outside == loop.idle(*t.window) == 65 * US


def _steps(leads, tails):
    """Dispatch, fetch and decode runs of steps 100 ns apart, each decode
    run starting ``lead`` after its dispatch opens and ending ``tail``
    before its fetch closes."""
    op = tr.Op
    out = [], [], []
    for k, (lead, tail) in enumerate(zip(leads, tails)):
        t0 = 100 * k
        out[0].append(op(t0, t0 + 5, "dispatch"))
        out[1].append(op(t0 + 5, t0 + 90, "fetch"))
        out[2].append(op(t0 + lead, t0 + 90 - tail, "decode"))
    return out


def test_one_shift_fits_steps_whose_bounds_overlap():
    assert servespans.offsets(*_steps([1, 2], [1, 8])) == [0, 0]
    assert servespans.offsets(*_steps([-2, 4], [7, 8])) == [2, 2]
    assert servespans.offsets(*_steps([5, 4], [-3, 8])) == [-3, -3]


def test_a_clock_that_jumps_is_shifted_stretch_by_stretch():
    # the second step needs at least 3 ns added, the first at most 1
    assert servespans.offsets(*_steps([1, -3], [1, 8])) == [0, 3]
    leads, tails = [-2] * 100, [6] * 100
    for k in range(40, 50):          # read 28 ns early for ten steps
        leads[k], tails[k] = -30, 34
    assert servespans.offsets(*_steps(leads, tails)) == (
        [2] * 40 + [30] * 10 + [2] * 50)


def test_a_step_no_shift_fits_keeps_its_end_inside_its_fetch():
    assert servespans.offsets(*_steps([-5, 1], [-5, 1])) == [-5, 0]


def test_a_jump_between_requests_is_corrected_for_the_second_alone():
    t = _trace("spans_trace.pbtxt")
    for o in t.devices[0].ops + t.devices[0].modules:
        if o.start >= 70 * US:       # request 1's decode runs, 6 us early
            o.start -= 6 * US
            o.end -= 6 * US
    loop = servespans.read(_ctx(t))
    # request 0 as counted by hand in the header; request 1's decode runs
    # 3 us early (on their dispatch bound) beside its prefill at [63, 68]
    assert [i // US for i in loop.fetch_idle()] == [3, 5, 3, 5, 4, 3]
    assert [i // US for i in loop.turnaround_idle()] == [3, 1, 2, 1]
    assert [i // US for i in loop.dispatch_idle()] == [2, 3, 1, 1]
    assert [i // US for i in loop.start_idle()] == [7, 3]


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_gives_none_without_the_program_spans(metric):
    # the self-check trace has the benchmark's spans and none of serve.*
    assert bench_run.read_metric(
        metric, _ctx(_trace("selfcheck_trace.pbtxt"))) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_gives_none_with_a_fetch_missing(metric):
    t = _trace("spans_trace.pbtxt")
    fetch = [s for s in t.spans if s.name == "serve.fetch"]
    t.spans.remove(fetch[4])
    assert bench_run.read_metric(metric, _ctx(t)) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_gives_none_when_the_steps_do_not_match(metric):
    ctx = dict(_ctx(_trace("spans_trace.pbtxt")), decode_steps=9)
    assert bench_run.read_metric(metric, ctx) is None
