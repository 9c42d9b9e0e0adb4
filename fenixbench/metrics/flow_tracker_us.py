"""Device time per scan step of the flow tracker: hash, slot, table and
backlog-register reads and the bulk table update (scope
``fenix_flow_table``), and the sort/segment backlog count (scope
``fenix_sort_segment``)."""

from fenixbench import spans


def read(ctx):
    return spans.stage_us(ctx, ("flow_table", "sort_segment"))
