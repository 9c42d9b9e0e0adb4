"""Device time per scan step of Model Engine inference: the INT8 model
over every served lane, its GEMM kernels included (scope
``fenix_infer``)."""

from fenixbench import spans


def read(ctx):
    return spans.stage_us(ctx, ("infer",))
