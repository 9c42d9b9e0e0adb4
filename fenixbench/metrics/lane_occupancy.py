"""Share of the Model Engine's lanes that carried a served packet over
the window: served over lanes run (scan steps x service lanes), from
the counters of the window's ``fenix.run_trace`` spans."""

from fenixbench import spans


def read(ctx):
    served = spans.call_sum(ctx, "served")
    lanes = spans.call_sum(ctx, "lanes")
    if served is None or not lanes:
        return None
    return 100.0 * served / lanes
