"""Serving throughput vs device count — the lane axis over a JAX mesh.

The paper's Table VI scales throughput by adding OpenMP workers, one video
per worker; DESIGN.md §7 takes the same model across *devices*: the
scheduler's lane budget is sharded contiguously over a 1-D ``("lanes",)``
mesh, each device scanning its own lane shard with zero collectives.
This benchmark serves one fixed ragged traffic mix through the same lane
budget at increasing device counts and reports real-frames-per-second —
the device-scaling analogue of ``benchmarks/scaling.py``'s thread sweep.

On CPU the devices are simulated host devices; run standalone (the
``__main__`` block forces 8 of them before jax initializes)::

    PYTHONPATH=src python benchmarks/device_scaling.py

or under the suite driver with the flag exported::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.run

What the rows mean by backend:

* **CPU (simulated devices)** — the shards share the host's cores, so
  expect <= 1x vs unsharded: the rows measure the sharded program's
  dispatch/placement *overhead*, not scaling.  The value of the sweep is
  that the harness, placement, and bit-identical outputs are exercised on
  every shard count that CI can reach.
* **TPU (real chips)** — scaling requires each shard to carry enough
  lanes to fill its kernel grid: the fused path pads every device's
  stream count up to ``block_s = block_b // max_trackers`` (128 by
  default), so size ``num_lanes >= block_s * devices`` or the padded
  blocks dominate and adding devices multiplies wasted compute instead
  of throughput.  The CPU default (``num_lanes=8``) is NOT that regime —
  CPU pads nothing (``SortEngine._block_s == 1``); rescale the knobs when
  pointing this at hardware.
"""
from __future__ import annotations

import time

import numpy as np


def _traffic(num_seqs: int, long_frames: int, skew: int, seed: int):
    """Arrival-interleaved ragged mix, same shape as benchmarks/ragged.py."""
    from repro.data.synthetic import SceneConfig, generate_scene

    seqs = []
    for i in range(num_seqs):
        f = long_frames if i % 2 == 0 else max(1, long_frames // skew)
        _, _, db, dm = generate_scene(
            SceneConfig(num_frames=f, max_objects=8, seed=seed + i))
        seqs.append((f"seq{i}", db, dm))
    d = max(s[1].shape[1] for s in seqs)
    padded = []
    for name, db, dm in seqs:
        grow = d - db.shape[1]
        padded.append((name, np.pad(db, ((0, 0), (0, grow), (0, 0))),
                       np.pad(dm, ((0, 0), (0, grow)))))
    return padded, d


def run(num_seqs: int = 16, long_frames: int = 96, skew: int = 4,
        num_lanes: int = 8, chunk: int = 16, seed: int = 0,
        repeats: int = 2, use_kernels: bool = True,
        device_counts: tuple = (1, 2, 4, 8)):
    if repeats < 1:
        raise ValueError(f"repeats must be >= 1, got {repeats}")
    # jax deferred so the __main__ block can force host devices first
    from repro.core import SortConfig, SortEngine
    from repro.serve import StreamScheduler
    from repro.sharding import lane_mesh

    import jax

    avail = jax.device_count()
    counts = [c for c in device_counts if c <= avail and num_lanes % c == 0]
    dropped = [c for c in device_counts if c not in counts]

    seqs, d = _traffic(num_seqs, long_frames, skew, seed)
    real_frames = sum(s[1].shape[0] for s in seqs)
    eng = SortEngine(SortConfig(max_trackers=16, max_detections=d,
                                use_kernels=use_kernels))

    def time_serve(mesh) -> float:
        sched = StreamScheduler(eng, num_lanes=num_lanes, max_dets=d,
                                chunk=chunk, mesh=mesh)
        best = np.inf
        for rep in range(repeats + 1):       # first rep warms the jit
            t0 = time.perf_counter()
            for name, db, dm in seqs:
                sched.submit(name, db, dm)
            n_done = len(sched.run())
            dt = time.perf_counter() - t0
            assert n_done == num_seqs
            if rep > 0:
                best = min(best, dt)
        return best

    rows = []
    t_base = time_serve(None)
    rows.append(("devices/unsharded_us_per_frame",
                 t_base / real_frames * 1e6,
                 f"fps={real_frames / t_base:,.0f} lanes={num_lanes} "
                 f"chunk={chunk} (no mesh)"))
    for n in counts:
        t = time_serve(lane_mesh(n))
        rows.append((f"devices/throughput_{n}dev_us_per_frame",
                     t / real_frames * 1e6,
                     f"fps={real_frames / t:,.0f} "
                     f"vs_unsharded={t_base / t:.2f}x "
                     f"lanes_per_device={num_lanes // n}"))
    if dropped:
        rows.append(("devices/unmeasured_counts", float(len(dropped)),
                     f"device counts {dropped} skipped: "
                     f"jax.device_count()={avail}, num_lanes={num_lanes} "
                     f"(set XLA_FLAGS=--xla_force_host_platform_device_"
                     f"count={max(device_counts)} before jax initializes)"))
    return rows


if __name__ == "__main__":
    import os
    import sys

    if "jax" not in sys.modules:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    for name, value, derived in run():
        print(f"{name},{value:.4f},{derived}")
