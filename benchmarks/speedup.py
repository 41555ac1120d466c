"""Paper Table V analogue: reference per-op Python SORT vs fused batched JAX.

The paper reports a 45-106x speedup of their C rewrite over the original
parallel-Python SORT.  Our analogue: the per-stream numpy/scipy reference
(same per-op dispatch pattern as the original) vs. the single fused jitted
batched engine, at equal work (same sequences).

Also the Table IV analogue (dispatch accounting, see DESIGN.md §4): frame
latency for the legacy per-phase engine vs the lane-persistent fused path
(``use_kernels=True``), which collapses the predict / IoU / update
dispatches and their layout round-trips into one ``fused_frame`` call per
frame on TPU.  Since PR 3 both engine rows run the same paper-exact
Hungarian association (DESIGN.md §6), so the comparison isolates layout
residency (+ launch overhead on TPU) — the association-algorithm axis
moved to ``benchmarks/association_ablation.py``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SortConfig, SortEngine
from repro.core.ref_numpy import Sort as RefSort
from repro.data.synthetic import SceneConfig, generate_scene


def run(num_streams: int = 64, num_frames: int = 120, seed: int = 0,
        repeats: int = 3):
    scenes = [generate_scene(SceneConfig(num_frames=num_frames,
                                         max_objects=10, seed=seed + i))
              for i in range(num_streams)]
    d = max(s[2].shape[1] for s in scenes)
    det = np.zeros((num_frames, num_streams, d, 4), np.float32)
    msk = np.zeros((num_frames, num_streams, d), bool)
    for i, (_, _, db, dm) in enumerate(scenes):
        det[:, i, :db.shape[1]] = db
        msk[:, i, :dm.shape[1]] = dm

    # --- reference: per-stream, per-op numpy (original-Python shaped) ---
    n_ref_streams = min(num_streams, 8)  # don't wait forever
    t0 = time.perf_counter()
    for i in range(n_ref_streams):
        ref = RefSort()
        for t in range(num_frames):
            ref.update(det[t, i][msk[t, i]])
    t_ref = (time.perf_counter() - t0) / (n_ref_streams * num_frames)

    # --- ours: jitted batch, legacy per-phase vs lane-persistent fused ---
    db, dm = jnp.asarray(det), jnp.asarray(msk)

    def time_engine(use_kernels: bool) -> float:
        eng = SortEngine(SortConfig(max_trackers=16, max_detections=d,
                                    use_kernels=use_kernels))
        run_fn = jax.jit(eng.run)
        jax.block_until_ready(run_fn(eng.init(num_streams), db, dm))
        best = np.inf
        for _ in range(repeats):
            st = eng.init(num_streams)
            t0 = time.perf_counter()
            out = run_fn(st, db, dm)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best / (num_streams * num_frames)

    t_ours = time_engine(False)
    t_fused = time_engine(True)

    # Table IV analogue: per-frame kernel dispatches on the filter hot path.
    # Paper: ~15 BLAS calls per tracker update; per-phase Pallas kernels: 3
    # (predict, IoU, update) + layout round-trips; fused frame kernel: 1
    # (+ the jitted lane-batched JV stage feeding it — same device program,
    # DESIGN.md §6).  The dispatch counts describe the TPU execution;
    # off-TPU the fused path runs the same-math jnp oracle (one XLA
    # program either way), so there the row isolates layout residency,
    # not kernel-launch overhead — association is Hungarian on both rows.
    on_tpu = jax.default_backend() == "tpu"
    fused_note = ("dispatches/frame=1" if on_tpu
                  else "cpu-oracle (hungarian assoc, resident lane layout)")
    rows = [
        ("tableV/ref_python_us_per_frame", t_ref * 1e6,
         "dispatches/frame~15 tiny BLAS per tracker (paper Table IV)"),
        ("tableV/jax_batched_us_per_frame", t_ours * 1e6,
         f"speedup={t_ref / t_ours:.1f}x hungarian assoc"),
        ("tableV/jax_fused_lane_us_per_frame", t_fused * 1e6,
         f"speedup={t_ref / t_fused:.1f}x {fused_note} "
         f"(vs unfused {t_ours / t_fused:.2f}x)"),
        ("tableV/jax_batched_fps", 1.0 / t_ours,
         f"streams={num_streams}"),
        ("tableV/jax_fused_lane_fps", 1.0 / t_fused,
         f"streams={num_streams}"),
    ]
    return rows
