"""Median over consecutive decode steps of device 0's idle time (ms) from
the start of ``serve.dispatch`` k+1 to the start of ``serve.fetch`` k+1:
launching the next decode step (device trace, program spans)."""

import statistics

import servespans


def read(ctx):
    loop = servespans.read(ctx)
    if loop is None or not loop.pairs():
        return None
    return statistics.median(loop.dispatch_idle()) / 1e6
