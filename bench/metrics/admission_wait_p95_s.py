"""95th percentile, over the segments due in the window, of the seconds
from a segment's due time to the start of the ``TrackingService.step``
whose chunk admitted it into a lane (``StreamScheduler.admissions``)."""
from bench.harness import percentile


def read(run):
    return percentile(run.admission_waits, 95)
