"""Host milliseconds per ``run_trace`` call spent, after the scan, reading
the drop counters and fetching the verdicts (span ``fenix.fetch_out``)."""

from fenixbench import spans


def read(ctx):
    return spans.phase_ms(ctx, "fenix.fetch_out")
