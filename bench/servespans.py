"""The serve token loop's own spans, and device 0's idle time between
their edges, for the per-layer readers of the host path.

``Server.generate`` opens ``serve.start`` from its entry until the token
stream starts (prefill, first token, plan, mover), and for each decode
step ``serve.dispatch`` (the decode call and the argmax of its logits),
``serve.fetch`` (the token's ``np.asarray``) and ``serve.deliver`` (the
sink).  The stream is ordered and the loop closed, so the k-th span of
each name belongs to the same step, and each request's steps follow one
another.  Between the start of fetch k and the start of fetch k+1 of one
request, the device's idle time splits at two edges into three parts:

    [fetch k start,      fetch k end]        token fetch
    [fetch k end,        dispatch k+1 start] stream turnaround (the mover)
    [dispatch k+1 start, fetch k+1 start]    dispatch

and a request's start is ``[serve.start start, its first fetch start]``.

The device's events reach the trace on the host's clock as the profiler
aligns the two, and that alignment can be off by more than the parts
measured (by about 1 ms for a whole run, or 2 ms for a second of it,
against parts of 0.1-2 ms).  Causality bounds it: each step's decode
program starts after its ``serve.dispatch`` opens and ends before its
``serve.fetch`` closes.  Each stretch of steps that one shift can fit
has device 0's operations shifted by the least offset that meets both
bounds on all its steps, and not at all where the profiler's alignment
meets them already.
"""

from __future__ import annotations

import bisect
import dataclasses
import itertools

import devtrace as tr

START = "serve.start"
DISPATCH, FETCH, DELIVER = "serve.dispatch", "serve.fetch", "serve.deliver"


@dataclasses.dataclass
class Loop:
    starts: list        # serve.start, one per request, in order
    dispatch: list      # the per-step spans, request by request, in order
    fetch: list
    deliver: list
    steps: int          # decode steps per request
    idle_starts: list   # device 0's idle intervals, shifted, ns
    idle_ends: list
    idle_cum: list      # idle ns before each interval

    def idle(self, a: int, b: int) -> int:
        """Device 0's idle ns inside [a, b]."""
        return self._idle_to(b) - self._idle_to(a)

    def _idle_to(self, x: int) -> int:
        j = bisect.bisect_right(self.idle_starts, x)
        if j == 0:
            return 0
        return self.idle_cum[j] - max(0, self.idle_ends[j - 1] - x)

    def pairs(self):
        """Indices (i, i + 1) of consecutive steps of one request."""
        return [(i, i + 1) for i in range(len(self.fetch) - 1)
                if (i + 1) % self.steps]

    def fetch_idle(self) -> list[int]:
        return [self.idle(f.start, f.end) for f in self.fetch]

    def turnaround_idle(self) -> list[int]:
        return [self.idle(self.fetch[i].end, self.dispatch[j].start)
                for i, j in self.pairs()]

    def dispatch_idle(self) -> list[int]:
        return [self.idle(self.dispatch[j].start, self.fetch[j].start)
                for _, j in self.pairs()]

    def start_idle(self) -> list[int]:
        return [self.idle(s.start, self.fetch[r * self.steps].start)
                for r, s in enumerate(self.starts)]

    def handoff(self) -> list[int]:
        """ns from each fetch's end to its token's delivery at the sink."""
        return [d.start - f.end for f, d in zip(self.fetch, self.deliver)]


def decode_runs(dev: tr.Device, n: int) -> list[tr.Op]:
    """The decode program's runs in time order: of the programs run ``n``
    times in the window, the one with the most device time."""
    runs: dict[str, list[tr.Op]] = {}
    for m in dev.modules:
        runs.setdefault(m.name, []).append(m)
    steps = [r for r in runs.values() if len(r) == n]
    if not steps:
        return []
    return sorted(max(steps, key=lambda r: sum(m.end - m.start for m in r)),
                  key=lambda m: m.start)


def offsets(dispatch: list, fetch: list, decode: list) -> list[int]:
    """Per step, ns to add to the device's times so that its decode run
    lies inside its [dispatch start, fetch end].  The steps are cut into
    runs of consecutive steps that one shift can fit; each run takes the
    least shift that fits it, 0 where the profiler's alignment does."""
    bounds = [(d.start - m.start, f.end - m.end)
              for d, f, m in zip(dispatch, fetch, decode)]
    out: list[int] = []
    while len(out) < len(bounds):
        lo, hi = bounds[len(out)]
        k = len(out) + 1
        while (k < len(bounds)
               and max(lo, bounds[k][0]) <= min(hi, bounds[k][1])):
            lo, hi = max(lo, bounds[k][0]), min(hi, bounds[k][1])
            k += 1
        out += [min(max(0, lo), hi)] * (k - len(out))
    return out


def read(ctx) -> Loop | None:
    """The window's token loop, device 0's idle time shifted onto the
    spans' clock; None where the trace lacks the spans or the decode
    program, or their counts or order do not match the run's steps."""
    t, n = ctx.get("trace"), ctx.get("decode_steps")
    steps = (ctx.get("traffic") or {}).get("gen_tokens", 0) - 1
    if t is None or not t.devices or not n or steps <= 0 or n % steps:
        return None
    spans = {name: sorted((s for s in t.spans if s.name == name),
                          key=lambda s: s.start)
             for name in (START, DISPATCH, FETCH, DELIVER)}
    if (len(spans[START]) != n // steps
            or any(len(spans[k]) != n for k in (DISPATCH, FETCH, DELIVER))):
        return None
    decode = decode_runs(t.devices[0], n)
    if not decode:
        return None
    shift = offsets(spans[DISPATCH], spans[FETCH], decode)
    first = [m.start for m in decode]

    def moved(o: tr.Op) -> tuple[int, int]:
        # the shift of the step whose decode run last started before it
        k = max(0, bisect.bisect_right(first, o.start) - 1)
        return o.start + shift[k], o.end + shift[k]

    busy = tr.union(moved(o) for o in tr.work_ops(t.devices[0]))
    idle = tr.subtract([t.window], tr.clip(busy, t.window))
    loop = Loop(spans[START], spans[DISPATCH], spans[FETCH], spans[DELIVER],
                steps, [s for s, _ in idle], [e for _, e in idle],
                [0, *itertools.accumulate(e - s for s, e in idle)])
    in_order = (
        all(s.start <= loop.fetch[r * steps].start
            for r, s in enumerate(loop.starts))
        and all(d.start <= f.start for d, f in zip(loop.dispatch, loop.fetch))
        and all(loop.fetch[i].end <= loop.dispatch[j].start
                for i, j in loop.pairs()))
    return loop if in_order else None
