"""Milliseconds of each traced ``TrackingService.step`` that the device
was not busy (the step's wall time less the device-busy time inside it),
averaged over the traced steps: host planning, operand staging, waiting
on the result and unpacking it."""


def read(run):
    red = run.reduced
    if red is None or not red.host_s_per_step:
        return None
    return 1e3 * sum(red.host_s_per_step) / len(red.host_s_per_step)
