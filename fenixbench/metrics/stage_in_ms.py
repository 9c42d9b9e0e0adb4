"""Host milliseconds per ``run_trace`` call spent staging the segment:
columns to the device, chunking, the control-plane flags (span
``fenix.stage_in``)."""

from fenixbench import spans


def read(ctx):
    return spans.phase_ms(ctx, "fenix.stage_in")
