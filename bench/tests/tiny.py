"""A tiny copy of the benchmark for CPU tests: the real harness, metric
readers and peaks, over a root whose configurations and mixes are cut to
a few lanes and short sequences."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


class CpuChip:
    """Stands in for the chip: the CPU's devices, no kernel check."""

    def devices(self, chips: int):
        import jax
        return jax.devices()[:chips]

    def check_program(self, hlo: str) -> None:
        pass


def tiny_root(tmp: Path, loop: str = "closed_loop",
              classes: int = 1) -> Path:
    """A root with one cell, ``tiny``, of 8 lanes in 4-frame chunks."""
    bench = tmp / "bench"
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"hbm_bytes_per_s": 1e11}  # test stand-in
    (bench / "peaks.json").write_text(json.dumps(peaks))
    cfg = json.loads((BENCH / "configs" / "mot15-sort.json").read_text())
    if classes > 1:
        cfg = json.loads((BENCH / "configs" / "kitti-mc.json").read_text())
    cfg.update(name="tiny", lanes_per_chip=8, chunk=4,
               service={"max_pending": 64, "per_client_pending": 64})
    (bench / "configs").mkdir()
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = {"kind": loop, "variants": 2, "shift_px": 8.0,
           "shapes": [{"name": f"s{f}", "frames": f, "objects": 3}
                      for f in (6, 9, 13, 17)]}
    if loop == "closed_loop":
        mix.update(queue_lanes=1.0, warm_chunks=1, first_fill="residual")
    else:
        mix.update(rate_per_s=20.0, cameras=4, warm_s=0.5, drain_s=30.0)
    (bench / "traffic").mkdir()
    (bench / "traffic" / "tinymix.json").write_text(json.dumps(mix))
    doc = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    doc["configs"] = [{"name": "tiny", "source": "test",
                       "file": "bench/configs/tiny.json", "reduced": [],
                       "why": "test"}]
    doc["workloads"] = [{"name": "tiny", "config": "tiny",
                         "traffic": "tinymix", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            keep = (m["name"].startswith("latency") or m["name"] in
                    ("admission_wait_p95_s", "host_ms_per_chunk.fleet")) \
                == (loop == "open_loop")
            m["workloads"] = ["tiny"] if keep else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp
