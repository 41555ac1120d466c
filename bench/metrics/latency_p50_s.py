"""Median, over every segment due in the window, of the seconds from its
due time on the generator's schedule to its delivery by
``TrackingService``."""
from bench.harness import percentile


def read(run):
    return percentile(run.latencies, 50)
