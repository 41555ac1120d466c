"""95th percentile of the same latencies as ``latency_p50_s``."""
from bench.harness import percentile


def read(run):
    return percentile(run.latencies, 95)
