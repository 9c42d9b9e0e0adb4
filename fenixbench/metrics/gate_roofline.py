"""The admission gate kernel's share of its roofline: the bytes it must
move for the packets it gated (int32 inputs and grant output), over
peak HBM bandwidth, divided by its device time."""

from fenixbench import trace_reduce, work

KERNEL = "fenix_fused_gate"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    t = trace_reduce.kernel_seconds(tr, KERNEL)
    if t <= 0:
        return None
    t_min = (ctx["counters"]["packets"] * work.GATE_BYTES_PER_PKT
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * t_min / t
