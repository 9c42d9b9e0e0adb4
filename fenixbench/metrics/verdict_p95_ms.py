"""95th percentile, over every segment completed in the window, of the
time from the segment being due (the previous call's return, in this
closed loop) to its verdicts being on the host."""

import numpy as np


def read(ctx):
    lat = ctx["window"]["latencies_s"]
    return float(np.percentile(np.asarray(lat), 95)) * 1e3
