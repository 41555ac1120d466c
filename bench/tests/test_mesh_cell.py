"""The 4-chip lane-mesh cell: its configuration states the chips its cell
asks for, and its one reader of its own, ``chip_skew_ms_per_chunk``,
reads the spread of the chips' last device ends inside each step."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import harness, trace

ROOT = Path(__file__).resolve().parents[2]
MS = 1_000_000


def test_mesh_config_states_its_cells_chips():
    spec = harness.Spec(ROOT)
    cells = [w for w in spec.doc["workloads"]
             if w["config"] == "mot15-sort-4chip"]
    assert [w["name"] for w in cells] == ["mot15-backlog-4chip"]
    config = spec.config("mot15-sort-4chip")
    for cell in cells:
        assert config["chips"] == cell["chips"] == 4
    assert config["mesh"]["shape"] == [config["chips"]]
    # the one-chip configuration it scales, unchanged but for the service's
    # admission caps (4 x the lanes of the whole mesh)
    one = spec.config("mot15-sort")
    for key in ("engine", "lanes_per_chip", "chunk", "limits",
                "guarantees", "reference"):
        assert config[key] == one[key], key
    lanes = config["lanes_per_chip"] * config["chips"]
    assert config["service"] == {"max_pending": 4 * lanes,
                                 "per_client_pending": 4 * lanes}


def _record(events):
    class Rec:
        pass
    rec = Rec()
    rec.events = events
    return rec


def four_chips() -> trace.Events:
    """Two 100 ms steps on 4 chips; in each, chip ``k`` ends its last op
    at 60 + 2 k ms (step 1) and 60 + 3 k ms (step 2) into the step.  An op
    that starts before a step, and one that starts after it, are not
    the step's."""
    dev = {}
    for k in range(4):
        ops = [("while.1", -5 * MS, 90 * MS)]          # outside step 1
        for s0, gap in ((0, 2), (100 * MS, 3)):
            end = s0 + (60 + gap * k) * MS
            ops += [("while.165", s0 + 10 * MS, s0 + 50 * MS),
                    ("%fused_chunk.1", s0 + 50 * MS, end)]
        ops.append(("fusion.9", 250 * MS, 260 * MS))    # after both steps
        dev[f"/device:TPU:{k}"] = ops
    host = [("bench.step", 0, 100 * MS), ("bench.deliver", 100 * MS,
                                          100 * MS),
            ("bench.step", 100 * MS, 200 * MS)]
    return trace.Events(dev, host)


def reader():
    return harness.Spec(ROOT).reader("chip_skew_ms_per_chunk")


def test_chip_skew_of_four_chips():
    # step 1: ends 60, 62, 64, 66 ms -> 6 ms; step 2: 60 .. 69 -> 9 ms
    assert reader()(_record(four_chips())) == pytest.approx(7.5)


def test_chip_skew_needs_two_chips_and_a_trace():
    read = reader()
    assert read(_record(None)) is None
    one = four_chips()
    one.device = {"/device:TPU:0": one.device["/device:TPU:0"]}
    assert read(_record(one)) is None
    idle = four_chips()
    idle.device["/device:TPU:3"] = []        # no op in any step
    assert read(_record(idle)) is None


def test_mesh_metrics_read_through_their_base():
    """The ``.4chip`` entries name their base's reader and list only the
    4-chip cell; ``chip_skew_ms_per_chunk`` has a file of its own."""
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = harness.Spec(ROOT)
    mesh = [m for m in doc["per_layer"]
            if "mot15-backlog-4chip" in m["workloads"]]
    assert sorted(m["name"] for m in mesh) == sorted(
        [f"{b}.4chip" for b in ("host_ms_per_chunk", "device_idle_share",
                                "xla_ms_per_chunk", "kernel_ms_per_chunk",
                                "fused_chunk_roofline")]
        + ["chip_skew_ms_per_chunk"])
    for m in mesh:
        assert m["workloads"] == ["mot15-backlog-4chip"]
        assert m["moves"] == "frames_per_s"
        assert callable(spec.reader(m["name"]))
    assert (ROOT / "bench" / "metrics"
            / "chip_skew_ms_per_chunk.py").exists()
