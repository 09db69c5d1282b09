"""95th percentile over tokens of the time (ms) from the end of a step's
``serve.fetch`` to the start of its ``serve.deliver``: the mover's staging
latency from the host to the sink, idle or not (program spans)."""

import common
import servespans


def read(ctx):
    loop = servespans.read(ctx)
    if loop is None:
        return None
    return common.percentile(loop.handoff(), 95) / 1e6
