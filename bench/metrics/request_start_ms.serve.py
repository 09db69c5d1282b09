"""Median over requests of device 0's idle time (ms) from the start of
``serve.start`` to the start of the request's first ``serve.fetch``: the
prefill's dispatch and first token, the transfer plan, the mover and the
first decode step's dispatch (device trace, program spans)."""

import statistics

import servespans


def read(ctx):
    loop = servespans.read(ctx)
    if loop is None:
        return None
    return statistics.median(loop.start_idle()) / 1e6
