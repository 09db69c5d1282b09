"""Share (%) of the traced serving window in which no operation ran on the
device, averaged over the chips (device trace)."""

import devtrace as tr


def read(ctx):
    return tr.idle_share(ctx.get("trace"))
