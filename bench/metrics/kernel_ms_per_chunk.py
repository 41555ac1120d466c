"""Device milliseconds per chunk of the chunk kernel's events
(``kernels/chunk.py::fused_chunk``), per chip."""


def read(run):
    red = run.reduced
    if red is None or red.steps == 0 or red.kernel_s <= 0:
        return None
    return 1e3 * red.kernel_s / red.steps
