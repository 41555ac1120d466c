"""Bursty-traffic throughput: elastic lane budget vs fixed budgets.

The ragged benchmark (``benchmarks/ragged.py``) shows lane recycling
beating pad-to-max at a *fixed* lane budget.  This benchmark attacks the
budget itself (DESIGN.md §8): real arrival traces are bursty, so a fixed
budget either starves bursts (``min`` lanes: admissions queue behind too
few lanes) or drags idle width through the quiet phases (``max`` lanes:
every dispatched step pays ``max`` lanes of kernel width for a handful of
live sequences — the right-sizing lever the edge-tracking measurement
study in PAPERS.md identifies as dominant).

The trace is a 4-phase arrival pattern — quiet, burst, quiet, burst —
served three ways at identical chunking:

* **fixed-min** — ``num_lanes = min_lanes`` (provisioned for the quiet
  phase; bursts serialize);
* **fixed-max** — ``num_lanes = max_lanes`` (provisioned for the burst;
  quiet phases run mostly-idle lanes);
* **elastic** — ``min_lanes..max_lanes`` ladder: grows the moment a
  burst's queue depth exceeds the width, shrinks back once the burst's
  lanes drain.  Outputs are bit-identical to fixed-max
  (``tests/test_autoscale.py``); only the dispatched width changes.

Reported per variant: wall-clock throughput over real frames and lane
utilization of the dispatched steps; the elastic row adds the resize
trail and mean dispatched width.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import SortConfig, SortEngine
from repro.data.synthetic import SceneConfig, generate_scene
from repro.serve import StreamScheduler


def _phases(light: int, heavy: int, frames: int, seed: int):
    """4-phase arrival trace: [light, heavy, light, heavy] sequence
    counts, each sequence ``frames`` long (uniform length isolates the
    budget effect from the raggedness effect ragged.py measures)."""
    out = []
    k = 0
    for n in (light, heavy, light, heavy):
        phase = []
        for _ in range(n):
            _, _, db, dm = generate_scene(SceneConfig(
                num_frames=frames, max_objects=8, seed=seed + k))
            phase.append((f"seq{k}", db, dm))
            k += 1
        out.append(phase)
    return out


def _pad_dets(phases):
    d = max(db.shape[1] for ph in phases for _, db, _ in ph)
    return [[(n, np.pad(db, ((0, 0), (0, d - db.shape[1]), (0, 0))),
              np.pad(dm, ((0, 0), (0, d - dm.shape[1]))))
             for n, db, dm in ph] for ph in phases], d


def _serve_trace(sched, phases) -> float:
    """Replay the trace: each phase's sequences arrive together and the
    scheduler drains before the next phase (the inter-phase idle gap)."""
    t0 = time.perf_counter()
    done = 0
    for phase in phases:
        for name, db, dm in phase:
            sched.submit(name, db, dm)
        done += len(sched.run())
    assert done == sum(len(p) for p in phases)
    return time.perf_counter() - t0


def _mean_width(sched) -> float:
    """Mean dispatched lane width over the run, from the resize trail."""
    if sched.chunks_run == 0:
        return float(sched.num_lanes)
    events = iter(sched.resizes + [(sched.chunks_run, sched.num_lanes,
                                    sched.num_lanes)])
    nxt = next(events)
    width = nxt[1] if sched.resizes else sched.num_lanes
    total = 0
    for c in range(sched.chunks_run):
        while c >= nxt[0]:
            width = nxt[2]
            nxt = next(events, (sched.chunks_run + 1, width, width))
        total += width
    return total / sched.chunks_run


def run(light: int = 2, heavy: int = 12, frames: int = 60,
        min_lanes: int = 2, max_lanes: int = 8, chunk: int = 8,
        seed: int = 0, repeats: int = 2, use_kernels: bool = True):
    if repeats < 1:
        raise ValueError(f"repeats must be >= 1 (rep 0 only warms the "
                         f"jit and is never timed), got {repeats}")
    phases, d = _pad_dets(_phases(light, heavy, frames, seed))
    real_frames = sum(len(p) for p in phases) * frames
    eng = SortEngine(SortConfig(max_trackers=16, max_detections=d,
                                use_kernels=use_kernels))

    def best_of(make_sched):
        """Best timed replay as (dt, utilization, resizes, mean width) —
        one snapshot, so every number in a row describes the SAME
        execution (reps can differ: the elastic scheduler starts each
        replay at the width the previous one ended at)."""
        sched = make_sched()
        best = None
        for rep in range(repeats + 1):         # first rep warms the jit
            # zero the accounting each replay so the stats describe ONE
            # timed replay of the trace, not the warm-up rep summed in
            sched.frames_processed = sched.lane_steps = sched.chunks_run = 0
            sched.admissions.clear()
            sched.resizes.clear()
            dt = _serve_trace(sched, phases)
            if rep > 0 and (best is None or dt < best[0]):
                best = (dt, sched.utilization, len(sched.resizes),
                        _mean_width(sched))
        return best

    t_min, u_min, _, _ = best_of(lambda: StreamScheduler(
        eng, num_lanes=min_lanes, max_dets=d, chunk=chunk))
    t_max, u_max, _, _ = best_of(lambda: StreamScheduler(
        eng, num_lanes=max_lanes, max_dets=d, chunk=chunk))
    t_el, u_el, n_resizes, mean_w = best_of(lambda: StreamScheduler(
        eng, max_dets=d, chunk=chunk,
        min_lanes=min_lanes, max_lanes=max_lanes))

    fps = {k: real_frames / t for k, t in
           (("min", t_min), ("max", t_max), ("el", t_el))}
    rows = [
        ("autoscale/fixed_min_us_per_frame", t_min / real_frames * 1e6,
         f"fps={fps['min']:,.0f} lanes={min_lanes} util={u_min:.0%}"),
        ("autoscale/fixed_max_us_per_frame", t_max / real_frames * 1e6,
         f"fps={fps['max']:,.0f} lanes={max_lanes} util={u_max:.0%}"),
        ("autoscale/elastic_us_per_frame", t_el / real_frames * 1e6,
         f"fps={fps['el']:,.0f} ladder={min_lanes}-{max_lanes} "
         f"util={u_el:.0%} resizes={n_resizes} "
         f"mean_width={mean_w:.1f}"),
        ("autoscale/elastic_vs_fixed_min", fps["el"] / fps["min"],
         f"burst speedup at {heavy} arrivals over {min_lanes} lanes"),
        ("autoscale/elastic_vs_fixed_max", u_el / max(u_max, 1e-9),
         "lane-utilization ratio (elastic right-sizes the quiet phases)"),
    ]
    return rows


if __name__ == "__main__":
    for name, value, derived in run():
        print(f"{name},{value:.4f},{derived}")
