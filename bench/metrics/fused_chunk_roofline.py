"""The chunk kernel's share of its HBM roofline, in percent: the bytes it
must move per chunk (``bench/kernel_bytes.py``) over the chip's HBM peak
(``bench/peaks.json``), divided by its device time per chunk.  Nothing
when the trace shows no kernel time."""


def read(run):
    red = run.reduced
    if red is None or red.steps == 0 or red.kernel_s <= 0:
        return None
    least_s = run.kernel_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (red.kernel_s / red.steps)
