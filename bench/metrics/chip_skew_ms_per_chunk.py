"""How long the host's fetch waits on the slowest chip after the fastest
is done: for each traced ``TrackingService.step`` (``bench.step``), each
chip's last device operation to end among those that start inside the
step, the latest of those ends less the earliest, in milliseconds,
averaged over the traced steps.  Nothing with fewer than two chips, or
where no step holds an operation on every chip."""
from bench.trace import STEP_SPAN


def read(run):
    ev = run.events
    if ev is None or len(ev.device) < 2:
        return None
    skews = []
    for name, s, e in ev.host:
        if name != STEP_SPAN:
            continue
        last = [max((b for _, a, b in ops if s <= a < e), default=None)
                for ops in ev.device.values()]
        if None not in last:
            skews.append((max(last) - min(last)) / 1e6)
    return sum(skews) / len(skews) if skews else None
