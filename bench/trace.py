"""Profiler trace -> events -> per-layer numbers.

:func:`collect` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps two kinds of event, on the profiler's one clock:

* device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane;
* the benchmark's host annotations (:data:`HOST_SPANS`), from any host
  line.

:func:`reduce` turns them into the numbers the per-layer readers report.
The chunk kernel's device events are told from the rest of the chunk
program by :data:`KERNEL_EVENT`, a pattern on the event's operation
name (:func:`op_name`: a device event is named by its HLO instruction,
whose operands may name the kernel too); everything
else the device runs is the chunk program's XLA part (layout conversion
and the Hungarian pre-pass).
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Optional

# Host annotations the harness puts around its calls into the service.
HOST_SPANS = ("bench.submit", "bench.step", "bench.deliver")
STEP_SPAN = "bench.step"
# The chunk kernel (``kernels/chunk.py::fused_chunk``, body
# ``_chunk_kernel``) as its device events name it.
KERNEL_EVENT = re.compile(r"chunk_kernel|fused_chunk")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Events:
    """Events of one traced window, times in ns on the profiler's clock."""

    device: dict[str, list[tuple[str, int, int]]]   # plane -> ops
    host: list[tuple[str, int, int]]                # benchmark spans

    def to_json(self) -> dict:
        return {"device": self.device, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls({k: [tuple(e) for e in v] for k, v in d["device"].items()},
                   [tuple(e) for e in d["host"]])


def op_name(event: str) -> str:
    """The operation an event names: ``%fused_chunk.1`` of ``%fused_chunk.1
    = (f32[...]) custom-call(...)``."""
    return event.split(" = ", 1)[0]


def is_kernel(event: str) -> bool:
    return KERNEL_EVENT.search(op_name(event)) is not None


def find_xplane(logdir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return files[-1] if files else None


def collect(path: str, devices: Optional[set[int]] = None) -> Events:
    """Read one ``.xplane.pb``; ``devices`` keeps only those TPU ids."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if devices is not None and int(m.group(1)) not in devices:
                continue
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, int(e.start_ns), int(e.end_ns))
                               for e in line.events)
            device[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events if e.name in HOST_SPANS)
    return Events(device, sorted(host, key=lambda e: e[1]))


def save(events: Events, path: str) -> None:
    with gzip.open(path, "wt") as fh:
        json.dump(events.to_json(), fh)


def load(path: str) -> Events:
    with gzip.open(path, "rt") as fh:
        return Events.from_json(json.load(fh))


def union(intervals) -> list[tuple[int, int]]:
    """Merged ``[(start, end)]`` of possibly nested or overlapping ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: int, hi: int) -> int:
    """ns of ``[lo, hi)`` that the merged intervals cover."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclasses.dataclass
class Reduced:
    """Per-chip means over the traced window."""

    window_s: float          # first step start -> last step end
    busy_s: float            # device busy in the window, mean over chips
    busy_by_chip: dict       # plane -> busy seconds
    kernel_s: float          # chunk kernel device time, mean over chips
    xla_s: float             # the rest of the device time, mean over chips
    steps: int               # traced TrackingService.step calls
    host_s_per_step: list    # each step's wall time less device busy in it
    top_ops: list            # [[name, seconds]], summed over chips / chips
    idle_gaps: list          # [[host span in which it fell, seconds]]


def reduce(ev: Events, top: int = 10) -> Reduced:
    steps = [(s, e) for n, s, e in ev.host if n == STEP_SPAN]
    if not steps or not ev.device:
        raise ValueError(f"trace holds {len(steps)} step spans and "
                         f"{len(ev.device)} device planes")
    lo, hi = steps[0][0], steps[-1][1]
    chips = len(ev.device)
    busy_by_chip, kernel, per_op = {}, 0, {}
    merged_all = {}
    for plane, ops in ev.device.items():
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
               if e > lo and s < hi]
        merged = union((s, e) for _, s, e in ops)
        merged_all[plane] = merged
        busy_by_chip[plane] = sum(e - s for s, e in merged) / 1e9
        kern = [(s, e) for n, s, e in ops if is_kernel(n)]
        kernel += sum(e - s for s, e in union(kern))
        for n, s, e in ops:
            per_op[n] = per_op.get(n, 0) + (e - s)
    busy = sum(busy_by_chip.values()) / chips
    if busy > 0 and kernel == 0:
        raise ValueError(
            f"the traced steps hold device ops but none matches "
            f"KERNEL_EVENT {KERNEL_EVENT.pattern!r}: the chunk kernel's "
            f"events are named otherwise ({sorted(per_op)[:8]} ...)")
    kernel_s = kernel / 1e9 / chips
    host_per_step = []
    for s, e in steps:
        dev = sum(covered(m, s, e) for m in merged_all.values()) / chips
        host_per_step.append((e - s - dev) / 1e9)
    # idle gaps of the first chip, named by the host span around them
    first = merged_all[sorted(merged_all)[0]]
    gaps, prev = [], lo
    for s, e in first + [(hi, hi)]:
        if s > prev:
            mid = (s + prev) // 2
            where = next((n for n, a, b in ev.host if a <= mid < b),
                         "between spans")
            gaps.append([where, (s - prev) / 1e9])
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(([n, t / 1e9 / chips] for n, t in per_op.items()),
                     key=lambda o: -o[1])[:top]
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy,
                   busy_by_chip=busy_by_chip, kernel_s=kernel_s,
                   xla_s=busy - kernel_s, steps=len(steps),
                   host_s_per_step=host_per_step, top_ops=top_ops,
                   idle_gaps=gaps[:top])
