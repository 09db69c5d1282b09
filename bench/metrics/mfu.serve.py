"""Least time the chip could take for the traced window's prefill and
decode work, over the window (%).  Each step's least time is the larger of
its FLOPs over the peak and its bytes over the memory bandwidth; decode
bytes are the weights plus the keys and values written so far."""

import flops


def read(ctx):
    if ctx.get("peaks") is None:       # no published peaks: no share of them
        return None
    work = ctx.get("work")
    if not work:
        return None
    hf, peaks = ctx["hf"], ctx["peaks"]
    least = sum(flops.prefill_step(hf, b, s).least_s(peaks)
                for b, s in work["prefill"])
    least += sum(flops.decode_step(hf, b, k).least_s(peaks)
                 for b, k in work["decode"])
    return 100.0 * least / ctx["window_s"]
