"""The INT8 GEMM kernel's share of its roofline, on useful work: served
inferences x 2 x GEMM MACs per inference (the model's unpadded shapes)
against peak int8 ops, and weight bytes per service step plus activation
bytes per served inference against peak HBM bandwidth; the larger bound,
divided by the kernel's device time."""

from fenixbench import trace_reduce, work

KERNEL = "fenix_int8_gemm"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    t = trace_reduce.kernel_seconds(tr, KERNEL)
    if t <= 0:
        return None
    m, c, pk = ctx["mcfg"], ctx["counters"], ctx["peaks"]
    t_ops = (c["served"] * 2 * work.gemm_macs_per_inference(m)
             / pk["int8_ops"])
    t_bytes = (c["steps"] * work.gemm_weight_bytes(m)
               + c["served"] * work.gemm_act_bytes_per_inference(m)
               ) / pk["hbm_bytes_per_s"]
    return 100.0 * max(t_ops, t_bytes) / t
