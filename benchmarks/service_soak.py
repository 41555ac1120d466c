"""Service soak — the crash-exact front-end's overhead and recovery cost.

The scheduler benchmarks (``ragged``, ``autoscale``) price the lane
multiplexing; this one prices what production puts around it
(DESIGN.md §11): the :class:`repro.serve.TrackingService` front-end with
chunk-boundary checkpointing, admission bounds, and a circuit breaker.

One soak, four questions:

* **service overhead** — served throughput with checkpointing OFF vs the
  bare scheduler loop (the async/admission/delivery tax alone);
* **checkpoint tax** — served throughput with a full-state checkpoint at
  every chunk boundary vs checkpointing off (the double-buffered async
  writer should hide most of the disk time), plus the mean synchronous
  export+commit latency;
* **resume latency** — time from ``TrackingService.resume`` to the first
  delivered sequence of a mid-run checkpoint (the recovery-time term of
  the crash story);
* **shed behaviour** — an over-rate burst against a token bucket: every
  over-budget submission sheds with a positive ``retry_after`` hint and
  the pending count never exceeds the bound.
"""
from __future__ import annotations

import asyncio
import tempfile
import time

import numpy as np

from repro.core import SortConfig, SortEngine
from repro.data.synthetic import SceneConfig, generate_scene
from repro.serve import Overloaded, StreamScheduler, TrackingService


def _sequences(n: int, frames: int, seed: int):
    seqs = []
    for k in range(n):
        _, _, db, dm = generate_scene(SceneConfig(
            num_frames=frames, max_objects=8, seed=seed + k))
        seqs.append((f"seq{k}", db, dm))
    d = max(db.shape[1] for _, db, _ in seqs)
    return [(n_, np.pad(db, ((0, 0), (0, d - db.shape[1]), (0, 0))),
             np.pad(dm, ((0, 0), (0, d - dm.shape[1])))) for n_, db, dm
            in seqs], d


def _mk_sched(eng, d, num_lanes, chunk):
    return StreamScheduler(eng, num_lanes=num_lanes, max_dets=d, chunk=chunk)


async def _serve_all(svc, seqs) -> float:
    t0 = time.perf_counter()
    for s in seqs:
        await svc.submit(*s)
    await svc.drain()
    svc.close()
    return time.perf_counter() - t0


def run(num_seqs: int = 8, frames: int = 60, num_lanes: int = 4,
        chunk: int = 16, seed: int = 0, use_kernels: bool = False):
    seqs, d = _sequences(num_seqs, frames, seed)
    real_frames = num_seqs * frames
    eng = SortEngine(SortConfig(max_trackers=16, max_detections=d,
                                use_kernels=use_kernels))

    # bare scheduler baseline (warm rep 0, time rep 1)
    for rep in range(2):
        sched = _mk_sched(eng, d, num_lanes, chunk)
        for s in seqs:
            sched.submit(*s)
        t0 = time.perf_counter()
        list(sched.run())
        t_bare = time.perf_counter() - t0

    # service, checkpointing off
    t_svc = asyncio.run(_serve_all(
        TrackingService(_mk_sched(eng, d, num_lanes, chunk)), seqs))

    with tempfile.TemporaryDirectory() as ckpt_dir:
        # service, full-state checkpoint at every chunk boundary
        t_ckpt = asyncio.run(_serve_all(
            TrackingService(_mk_sched(eng, d, num_lanes, chunk),
                            ckpt_dir=ckpt_dir, ckpt_every=1), seqs))

        # synchronous checkpoint latency + resume latency, mid-run
        async def _mid_run():
            svc = TrackingService(_mk_sched(eng, d, num_lanes, chunk),
                                  ckpt_dir=ckpt_dir, ckpt_every=1)
            for s in seqs:
                await svc.submit(*s)
            for _ in range(3):
                await svc.step()
            t0 = time.perf_counter()
            svc.checkpoint(wait=True)
            dt_commit = time.perf_counter() - t0
            svc.close()
            return dt_commit

        dt_commit = asyncio.run(_mid_run())

        async def _resume():
            t0 = time.perf_counter()
            svc = TrackingService.resume(
                _mk_sched(eng, d, num_lanes, chunk), ckpt_dir)
            while svc.busy and not svc.completed:
                await svc.step()
            dt_first = time.perf_counter() - t0
            await svc.drain()
            svc.close()
            return dt_first

        dt_resume = asyncio.run(_resume())

    # shed behaviour: over-rate burst against a 1-token bucket
    async def _burst():
        svc = TrackingService(_mk_sched(eng, d, num_lanes, chunk),
                              rate=1.0, burst=1.0, max_pending=num_seqs)
        shed, hints, peak = 0, [], 0
        for s in seqs:
            try:
                await svc.submit(*s)
            except Overloaded as e:
                shed += 1
                hints.append(e.retry_after)
            peak = max(peak, svc.pending)
        await svc.drain()
        svc.close()
        return shed, hints, peak

    shed, hints, peak = asyncio.run(_burst())
    assert shed == num_seqs - 1 and all(h > 0 for h in hints), \
        "over-rate burst must shed with positive Retry-After hints"
    assert peak <= num_seqs, "pending exceeded the admission bound"

    fps = {k: real_frames / t for k, t in
           (("bare", t_bare), ("svc", t_svc), ("ckpt", t_ckpt))}
    rows = [
        ("service/bare_us_per_frame", t_bare / real_frames * 1e6,
         f"fps={fps['bare']:,.0f} (scheduler loop, no front-end)"),
        ("service/served_us_per_frame", t_svc / real_frames * 1e6,
         f"fps={fps['svc']:,.0f} overhead={t_svc / t_bare - 1:+.1%} "
         f"(async admission + delivery, no checkpoints)"),
        ("service/ckpt_us_per_frame", t_ckpt / real_frames * 1e6,
         f"fps={fps['ckpt']:,.0f} tax={t_ckpt / t_svc - 1:+.1%} "
         f"(full-state checkpoint every chunk, async writer)"),
        ("service/ckpt_commit_ms", dt_commit * 1e3,
         "synchronous export+commit of the full service state"),
        ("service/resume_to_first_result_ms", dt_resume * 1e3,
         "TrackingService.resume to first delivered sequence"),
        ("service/shed_rate", shed / num_seqs,
         f"over-rate burst: {shed}/{num_seqs} shed, mean "
         f"retry_after={np.mean(hints):.2f}s, peak pending={peak}"),
    ]
    return rows


if __name__ == "__main__":
    for name, value, derived in run():
        print(f"{name},{value:.4f},{derived}")
