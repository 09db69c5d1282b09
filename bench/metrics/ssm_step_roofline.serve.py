"""Bytes of the traced window's decode conv and SSM states (every Mamba-2
layer's, read and written once a step) over the memory bandwidth, over
the device time of the operations under the ``ssm_step`` scope (%,
device trace).  Nothing where the traced program has no such scope or the
configuration no Mamba-2 layers."""

import devtrace as tr
import flops_hybrid


def read(ctx):
    if ctx.get("peaks") is None:       # no published peaks: no share of them
        return None
    t, work, hf = ctx.get("trace"), ctx.get("work"), ctx.get("hf", {})
    if t is None or not t.devices or not work or "layer_types" not in hf:
        return None
    device_s = tr.scope_s(t.devices[0], "ssm_step", t.window)
    if device_s <= 0:
        return None
    nbytes = sum(flops_hybrid.ssm_step_bytes(hf, b) for b, _ in work["decode"])
    return 100.0 * nbytes / ctx["peaks"].hbm_bytes_per_s / device_s
