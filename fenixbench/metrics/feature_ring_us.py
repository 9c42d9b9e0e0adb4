"""Device time per scan step of the per-flow feature rings: the gather
that builds each packet's mirror payload and the ring scatter (scope
``fenix_feature_ring``)."""

from fenixbench import spans


def read(ctx):
    return spans.stage_us(ctx, ("feature_ring",))
