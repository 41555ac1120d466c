"""Real sequence frames tracked in the window (the growth of
``StreamScheduler.frames_processed`` over whole chunks) per second of the
window, on the host clock."""


def read(run):
    return run.frames / run.window_s if run.window_s > 0 else None
