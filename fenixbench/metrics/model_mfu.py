"""Whole-step model utilization: served inferences per second of the
traced window x 2 x MACs per inference, over the chips' int8 peak."""

from fenixbench import work


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    ops = (ctx["counters"]["served"] * 2 * work.macs_per_inference(
        ctx["mcfg"]) / tr["window_s"])
    return 100.0 * ops / (ctx["chips"] * ctx["peaks"]["int8_ops"])
