"""Median over consecutive decode steps of device 0's idle time (ms) from
the end of ``serve.fetch`` k to the start of ``serve.dispatch`` k+1: the
token's yield through the mover's ``token-stream`` stage worker and its
next pull (device trace, program spans)."""

import statistics

import servespans


def read(ctx):
    loop = servespans.read(ctx)
    if loop is None or not loop.pairs():
        return None
    return statistics.median(loop.turnaround_idle()) / 1e6
