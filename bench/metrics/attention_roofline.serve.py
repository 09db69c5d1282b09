"""Roofline time of the traced window's attention (causal prefill, decode
against the keys and values written so far), counted from shapes, over
the device time of the operations under the ``flashable_attention`` scope
(%, device trace)."""

import flops
import devtrace as tr


def read(ctx):
    if ctx.get("peaks") is None:       # no published peaks: no share of them
        return None
    t, work = ctx.get("trace"), ctx.get("work")
    if t is None or not t.devices or not work:
        return None
    device_s = tr.scope_s(t.devices[0], "flashable_attention", t.window)
    if device_s <= 0:
        return None
    hf, peaks = ctx["hf"], ctx["peaks"]
    least = sum(flops.attention_prefill(hf, b, s).least_s(peaks)
                for b, s in work["prefill"])
    least += sum(flops.attention_decode(hf, b, k).least_s(peaks)
                 for b, k in work["decode"])
    return 100.0 * least / device_s
