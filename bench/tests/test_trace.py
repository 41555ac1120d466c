"""The reduction from trace events to per-layer numbers."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def test_union_and_covered():
    merged = trace.union([(5, 9), (0, 2), (1, 3), (6, 7), (10, 12)])
    assert merged == [(0, 3), (5, 9), (10, 12)]
    assert trace.covered(merged, 2, 11) == 1 + 4 + 1


def synthetic() -> trace.Events:
    """Two chips, two steps of 100 ms: on each, 10 ms of pre-pass ops (one
    nested in another) and a 5 ms kernel, then idle."""
    dev = {}
    for chip, skew in (("/device:TPU:0", 0), ("/device:TPU:1", 2 * MS)):
        ops = []
        for s0 in (0, 100 * MS):
            a = s0 + 50 * MS + skew
            ops += [("while.3", a, a + 10 * MS),
                    ("fusion.1", a + 1 * MS, a + 2 * MS),
                    ("custom-call.7 _chunk_kernel", a + 10 * MS,
                     a + 15 * MS)]
        dev[chip] = ops
    host = [("bench.step", 0, 100 * MS), ("bench.deliver", 100 * MS,
                                          101 * MS),
            ("bench.step", 101 * MS, 200 * MS)]
    return trace.Events(dev, host)


def test_reduce_synthetic():
    red = trace.reduce(synthetic())
    assert red.steps == 2
    assert red.window_s == pytest.approx(0.2)
    assert red.busy_s == pytest.approx(0.030)
    assert red.kernel_s == pytest.approx(0.010)
    assert red.xla_s == pytest.approx(0.020)
    # each step's wall time less the 15 ms of device work inside it
    assert red.host_s_per_step == pytest.approx([0.085, 0.084])
    where, longest = red.idle_gaps[0]
    # 65 ms -> 150 ms on chip 0: its midpoint falls in the second step
    assert where == "bench.step" and longest == pytest.approx(0.085)
    assert red.top_ops[0][0] == "while.3"


def test_events_round_trip(tmp_path):
    ev = synthetic()
    trace.save(ev, str(tmp_path / "e.json.gz"))
    back = trace.load(str(tmp_path / "e.json.gz"))
    assert back.device == ev.device and back.host == ev.host


def test_reduce_rejects_a_trace_without_steps():
    with pytest.raises(ValueError):
        trace.reduce(trace.Events({"/device:TPU:0": []}, []))


def test_reduce_rejects_device_work_without_the_kernel():
    ev = synthetic()
    ev.device = {p: [e for e in ops if "kernel" not in e[0]]
                 for p, ops in ev.device.items()}
    with pytest.raises(ValueError, match="KERNEL_EVENT"):
        trace.reduce(ev)


def test_reduce_recorded_chip_trace():
    """One ``TrackingService.step`` of ``mot15-fleet`` traced on a v5e
    ("TPU v5 lite"): the device events of that step, named by their HLO
    operation (the events that mention the kernel keep their whole HLO
    text), and the host spans up to it."""
    ev = trace.load(str(DATA / "v5e_fleet_step.json.gz"))
    red = trace.reduce(ev)
    assert red.steps == 1
    assert red.window_s == pytest.approx(0.243196309)
    assert red.busy_s == pytest.approx(0.055252825)
    assert red.host_s_per_step == pytest.approx([0.187943484])
    # the kernel is the one operation named for it; the XLA ops whose
    # operands name it are the chunk program's
    ops = ev.device["/device:TPU:0"]
    named = {trace.op_name(n) for n, _, _ in ops
             if trace.KERNEL_EVENT.search(n)}
    assert "%fused_chunk.1" in named and len(named) > 1
    kernel = sum(e - s for n, s, e in ops
                 if trace.op_name(n) == "%fused_chunk.1")
    assert red.kernel_s == pytest.approx(kernel / 1e9)
    assert red.kernel_s == pytest.approx(0.000672981)
    assert red.xla_s == pytest.approx(red.busy_s - red.kernel_s)
    assert red.top_ops[0][0] == "%while.165"
