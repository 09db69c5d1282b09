"""Median device time (ms) of one decode-step program: of the programs run
once per decode step in the traced window, the one with the most device
time (device trace)."""

import statistics

import devtrace as tr


def read(ctx):
    t, n = ctx.get("trace"), ctx.get("decode_steps")
    if t is None or not t.devices or not n:
        return None
    runs = [d for d in tr.module_runs(t.devices[0]).values() if len(d) == n]
    if not runs:
        return None
    return 1e3 * statistics.median(max(runs, key=sum))
