"""Benchmark tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest fenixbench/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import fenixbench  # noqa: E402,F401  (puts src/ on the path)

# tiny widths and traffic for the CPU: the *_tiny model widths, a small
# flow table, batches and pool
TINY = {
    "config": {"model": {"embed_dim": 4, "conv_filters": [8],
                         "fc_dims": [16], "rnn_units": 16},
               "switch": {"batch_size": 256, "n_slots_log2": 10}},
    "mix": {"concurrent_flows": 512, "pool_trace_us": 200000,
            "segment_pkts": 2048, "base_flows": 512},
    "peaks": {"int8_ops": 1e12, "hbm_bytes_per_s": 1e10},
}


def bench_root(tmp_path) -> str:
    """A root holding a copy of the repo's BENCHMARK.json."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), str(tmp_path))
    return str(tmp_path)
