"""The control of ``correct``: the reference with its Kalman state in
bfloat16, one precision below the float32 the configurations state, put
in the program's place and run through the harness like any run.

    python3 bench/control.py --workload <cell> --seeds <n>[,<n>...] \
        [--seconds <s>]

In one process, for each seed, it runs the cell twice at its own size and
load: once as it is (the program's readings) and once with the control in
place (the control's readings), and prints each run's numbers beside the
configuration's limits.  The control takes the program's place where a
sequence's results are produced: ``StreamScheduler._finalize`` hands the
service the bfloat16 reference's tracks of that sequence's detections in
place of the ones the chunk kernel wrote, for every sequence the check
may sample.  They are worked out when first read, so only the sampled
ones cost the reference's time.  The program's own ``dtype="bfloat16"``
is not the control: Mosaic refuses that chunk kernel for a v5e.  The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
import time
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
ROUNDING = "bf16"


def reference_rows(config: dict, seq, rounding: str = ROUNDING):
    """The reference's tracks of one queued sequence, as the scheduler's
    per-frame rows ``(boxes [T, 4], uid [T], emit [T], cls [T])``."""
    from bench import check

    t = config["engine"]["max_trackers"]
    sub = types.SimpleNamespace(
        frames=seq.length, det_boxes=seq.det_boxes, det_mask=seq.det_mask,
        det_class=seq.det_class, det_embed=seq.det_embed)
    boxes, uid, emit, cls = [], [], [], []
    for frame in check.reference_frames(config, sub, rounding):
        b = np.zeros((t, 4), np.float32)
        u = np.zeros(t, np.int32)
        e = np.zeros(t, bool)
        c = np.zeros(t, np.int32)
        for k, (track, (box, klass)) in enumerate(list(frame.items())[:t]):
            b[k], u[k], e[k], c[k] = box, track, True, klass
        boxes.append(b)
        uid.append(u)
        emit.append(e)
        cls.append(c)
    return boxes, uid, emit, cls


class ControlTracks:
    """A finished sequence as the control delivers it: the fields of
    ``SequenceTracks``, from the reference's rows when first read."""

    def __init__(self, config: dict, seq):
        self.name = seq.name
        self._config, self._seq = config, seq
        self._classes = config["engine"].get("num_classes", 1) > 1

    @functools.cached_property
    def _rows(self):
        return [np.stack(r) for r in reference_rows(self._config,
                                                    self._seq)]

    boxes = property(lambda self: self._rows[0])
    uid = property(lambda self: self._rows[1])
    emit = property(lambda self: self._rows[2])
    cls = property(lambda self: self._rows[3] if self._classes else None)


@contextlib.contextmanager
def in_place(config: dict, seed: int):
    """While open, every sequence the run's seeded hash may sample is
    finalized with the control's tracks instead of the program's."""
    from bench import harness
    from repro.serve.scheduler import StreamScheduler

    real = StreamScheduler._finalize

    def finalize(self, seq):
        if harness._keep(seed, seq.index):
            self._ready.put(seq.index, ControlTracks(config, seq))
        else:
            real(self, seq)

    StreamScheduler._finalize = finalize
    try:
        yield
    finally:
        StreamScheduler._finalize = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.run import Chip, enable_compile_cache

    spec = harness.Spec(ROOT)
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    chip = Chip()
    devices = chip.devices(cell["chips"])
    enable_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        for who in ("program", "control"):
            ctx = in_place(config, seed) if who == "control" \
                else contextlib.nullcontext()
            with ctx:
                result = harness.run_cell(
                    spec, args.workload, seed, args.seconds, False, devices,
                    time.perf_counter(), chip.check_program)
            print(json.dumps({"who": who, "cell": args.workload,
                              "seed": seed, "correct": result["correct"],
                              "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
