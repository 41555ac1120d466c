"""Device milliseconds per chunk of every operation of the chunk program
other than the chunk kernel: the layout conversion of
``core/sort.py::run_chunk_ragged`` and the Hungarian pre-pass of
``kernels/ops.py::chunk_step``, per chip."""


def read(run):
    red = run.reduced
    if red is None or red.steps == 0:
        return None
    return 1e3 * red.xla_s / red.steps
