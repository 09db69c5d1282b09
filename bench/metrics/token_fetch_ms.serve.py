"""Median over decode steps of device 0's idle time (ms) inside the
program's ``serve.fetch`` span: the step has finished on the device and
the host still waits for its token (device trace, program spans)."""

import statistics

import servespans


def read(ctx):
    loop = servespans.read(ctx)
    if loop is None:
        return None
    return statistics.median(loop.fetch_idle()) / 1e6
