"""Share of the traced window (first step's start to the last step's end)
in which no operation ran on the device, in percent, averaged over the
chips the cell uses."""


def read(run):
    red = run.reduced
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
