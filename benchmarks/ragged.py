"""Ragged-traffic throughput: lane-recycling scheduler vs pad-to-max.

The paper's Table VI scales throughput by giving each worker one video
file — all workers busy because the 11 files were replicated to match the
core count.  Real traffic is ragged (Table I lengths span 71–1000 frames),
and the fixed-batch engine must pad every sequence in a batch to the
longest one, so a 4:1 length skew wastes most lane-steps on padding.

This benchmark runs the same 4:1 skewed mix (arrival-interleaved short and
long sequences, the adversarial order for batching) two ways at an equal
lane budget:

* **pad-to-max**: FIFO batches of ``num_lanes`` sequences, every sequence
  padded to the global maximum length, one ``SortEngine.run`` per batch —
  the serving strategy the fixed-batch API forces.
* **scheduler**: ``repro.serve.StreamScheduler`` — lanes recycled the
  moment a sequence ends, inactive lanes masked inside the fused step
  (DESIGN.md §3).

Throughput is *real* frames (no padding) per second, the end-to-end
serving metric Murray (arXiv:1709.03572) argues for.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SortConfig, SortEngine
from repro.data.synthetic import SceneConfig, generate_scene
from repro.serve import StreamScheduler


def _mix(num_seqs: int, long_frames: int, skew: int, seed: int):
    """Arrival-interleaved 4:1 mix: long, short, long, short, ..."""
    seqs = []
    for i in range(num_seqs):
        f = long_frames if i % 2 == 0 else max(1, long_frames // skew)
        _, _, db, dm = generate_scene(
            SceneConfig(num_frames=f, max_objects=8, seed=seed + i))
        seqs.append((f"seq{i}", db, dm))
    return seqs


def _pad_dets(seqs):
    d = max(s[1].shape[1] for s in seqs)
    out = []
    for name, db, dm in seqs:
        grow = d - db.shape[1]
        out.append((name, np.pad(db, ((0, 0), (0, grow), (0, 0))),
                    np.pad(dm, ((0, 0), (0, grow)))))
    return out, d


def _run_padmax(run_fn, eng, seqs, num_lanes: int, f_max: int, d: int) -> int:
    """FIFO batches of ``num_lanes``, every sequence padded to ``f_max``."""
    last = None
    for i in range(0, len(seqs), num_lanes):
        batch = seqs[i:i + num_lanes]
        det = np.zeros((f_max, num_lanes, d, 4), np.float32)
        msk = np.zeros((f_max, num_lanes, d), bool)
        for j, (_, db, dm) in enumerate(batch):
            det[:db.shape[0], j] = db
            msk[:dm.shape[0], j] = dm
        _, last = run_fn(eng.init(num_lanes), jnp.asarray(det),
                         jnp.asarray(msk))
    jax.block_until_ready(last.boxes)
    return -(-len(seqs) // num_lanes) * f_max * num_lanes  # lane-steps paid


def run(num_seqs: int = 16, long_frames: int = 120, skew: int = 4,
        num_lanes: int = 4, chunk: int = 32, seed: int = 0,
        repeats: int = 3, use_kernels: bool = True):
    seqs, d = _pad_dets(_mix(num_seqs, long_frames, skew, seed))
    f_max = max(s[1].shape[0] for s in seqs)
    real_frames = sum(s[1].shape[0] for s in seqs)
    eng = SortEngine(SortConfig(max_trackers=16, max_detections=d,
                                use_kernels=use_kernels))

    def time_sched() -> tuple[float, float]:
        # one scheduler for all reps: a serving process compiles its chunk
        # program once and then handles traffic forever (lane state
        # persists, but every admission starts from a masked re-init)
        sched = StreamScheduler(eng, num_lanes=num_lanes,
                                max_dets=d, chunk=chunk)
        best = np.inf
        for rep in range(repeats + 1):         # first rep warms the jit
            t0 = time.perf_counter()
            for name, db, dm in seqs:
                sched.submit(name, db, dm)
            n_done = len(sched.run())
            dt = time.perf_counter() - t0
            assert n_done == num_seqs
            if rep > 0:
                best = min(best, dt)
        return best, sched.utilization

    def time_padmax() -> tuple[float, int]:
        run_fn = jax.jit(eng.run)              # compiled once, like serving
        _run_padmax(run_fn, eng, seqs, num_lanes, f_max, d)  # warm the jit
        best, paid = np.inf, 0
        for _ in range(repeats):
            t0 = time.perf_counter()
            paid = _run_padmax(run_fn, eng, seqs, num_lanes, f_max, d)
            best = min(best, time.perf_counter() - t0)
        return best, paid

    t_sched, util = time_sched()
    t_pad, pad_steps = time_padmax()
    fps_sched = real_frames / t_sched
    fps_pad = real_frames / t_pad
    rows = [
        ("ragged/padmax_us_per_frame", t_pad / real_frames * 1e6,
         f"fps={fps_pad:,.0f} lane_steps={pad_steps} "
         f"pad_waste={1 - real_frames / pad_steps:.0%}"),
        ("ragged/scheduler_us_per_frame", t_sched / real_frames * 1e6,
         f"fps={fps_sched:,.0f} lane_util={util:.0%} "
         f"(working steps only) lanes={num_lanes} chunk={chunk}"),
        ("ragged/scheduler_speedup", fps_sched / fps_pad,
         f"{skew}:1 length skew, {num_seqs} seqs, "
         f"{'fused' if use_kernels else 'per-phase'} path"),
    ]
    return rows


if __name__ == "__main__":
    for name, value, derived in run():
        print(f"{name},{value:.4f},{derived}")
