"""Seconds from the start of the process to the opening of the timed
window: imports, the scene pool, compilation or the compile cache, the
first submissions and the warm-up chunks."""


def read(run):
    return run.setup_s
