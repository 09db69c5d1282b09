"""Roofline time of the traced window's prefill SSD (every Mamba-2 layer's
chunked scan: x, dt, B and C read once, y and the final state written
once), counted from shapes, over the device time of the operations under
the ``flashable_ssd`` scope (%, device trace).  Nothing where the traced
program has no such scope or the configuration no Mamba-2 layers."""

import devtrace as tr
import flops_hybrid


def read(ctx):
    if ctx.get("peaks") is None:       # no published peaks: no share of them
        return None
    t, work, hf = ctx.get("trace"), ctx.get("work"), ctx.get("hf", {})
    if t is None or not t.devices or not work or "layer_types" not in hf:
        return None
    device_s = tr.scope_s(t.devices[0], "flashable_ssd", t.window)
    if device_s <= 0:
        return None
    least = sum(flops_hybrid.ssd_prefill(hf, b, s).least_s(ctx["peaks"])
                for b, s in work["prefill"])
    return 100.0 * least / device_s
