"""Dispatch-overhead sweep: per-frame scan vs the chunk-resident megakernel.

The paper's Table IV complaint is per-op dispatch overhead around tiny
matrices; DESIGN.md §4 tracks how each PR collapsed it.  PR 6 moves the
*frame loop itself* inside ``pallas_call`` (DESIGN.md §9), so the number
that matters is **device dispatches per serving chunk**: the per-frame
path issues one fused kernel per frame (``F`` per chunk, via
``lax.scan``), the megakernel issues exactly one regardless of ``F``.

The dispatch counts here are *structural*, not sampled: we trace the
engine's ``run_chunk_ragged`` (``mode="interpret"`` so the Pallas path is
traced off-TPU too) and walk the jaxpr counting ``pallas_call`` equations,
multiplying through ``lax.scan`` trip counts.  Latency rows time the
``mode="auto"`` program at each chunk size; on TPU that is the real
kernel-vs-kernel comparison, off-TPU both rows run the same-math XLA
oracle so the latency delta collapses and the dispatch column is the
story.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SortConfig, SortEngine
from repro.data.synthetic import SceneConfig, generate_scene

CHUNK_SIZES = (1, 4, 16, 32, 64)


def _sub_jaxprs(params: dict):
    """Yield every jaxpr reachable from one equation's params."""
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jax.core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jax.core.Jaxpr):
                yield v


def count_pallas_dispatches(jaxpr) -> int:
    """Count ``pallas_call`` equations reachable from ``jaxpr``, weighting
    sub-jaxprs under ``scan`` by the scan trip count (a kernel inside a
    ``lax.scan`` launches once per iteration)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
            continue
        mult = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        for sub in _sub_jaxprs(eqn.params):
            total += mult * count_pallas_dispatches(sub)
    return total


def chunk_dispatches(engine: SortEngine, det, dm, active, reset) -> int:
    """Structural dispatches-per-chunk for ``engine.run_chunk_ragged`` on
    the given planned chunk (traced with ``mode="interpret"`` so the
    Pallas program shape is counted even off-TPU)."""
    closed = jax.make_jaxpr(
        lambda st, d, m, a, r: engine.run_chunk_ragged(st, d, m, a, r,
                                                       mode="interpret")
    )(engine.init_ragged(active.shape[1]), det, dm, active, reset)
    return count_pallas_dispatches(closed.jaxpr)


def _planned_chunk(num_frames: int, num_lanes: int, seed: int):
    """A fully-occupied planned chunk: every lane active for all ``F``
    frames, recycled (reset) at frame 0 — the steady-state serving shape."""
    scenes = [generate_scene(SceneConfig(num_frames=num_frames,
                                         max_objects=6, seed=seed + i))
              for i in range(num_lanes)]
    d = max(s[2].shape[1] for s in scenes)
    det = np.zeros((num_frames, num_lanes, d, 4), np.float32)
    msk = np.zeros((num_frames, num_lanes, d), bool)
    for i, (_, _, db, dm) in enumerate(scenes):
        det[:, i, :db.shape[1]] = db
        msk[:, i, :dm.shape[1]] = dm
    active = np.ones((num_frames, num_lanes), bool)
    reset = np.zeros((num_frames, num_lanes), bool)
    reset[0, :] = True
    return (jnp.asarray(det), jnp.asarray(msk), jnp.asarray(active),
            jnp.asarray(reset), d)


def run(chunk_sizes=CHUNK_SIZES, num_lanes: int = 4, seed: int = 0,
        repeats: int = 3):
    def engine(chunk_kernel: bool, d: int) -> SortEngine:
        return SortEngine(SortConfig(max_trackers=8, max_detections=d,
                                     use_kernels=True, assoc="greedy",
                                     chunk_kernel=chunk_kernel))

    on_tpu = jax.default_backend() == "tpu"
    rows = []
    for f in chunk_sizes:
        det, dm, active, reset, d = _planned_chunk(f, num_lanes, seed)
        variants = [("scan", engine(False, d)), ("megakernel", engine(True, d))]
        timings, counts = {}, {}
        for label, eng in variants:
            counts[label] = chunk_dispatches(eng, det, dm, active, reset)
            run_fn = jax.jit(eng.run_chunk_ragged)
            st = eng.init_ragged(num_lanes)
            jax.block_until_ready(run_fn(st, det, dm, active, reset))
            best = np.inf
            for _ in range(repeats):
                t0 = time.perf_counter()
                out = run_fn(st, det, dm, active, reset)
                jax.block_until_ready(out)
                best = min(best, time.perf_counter() - t0)
            timings[label] = best / (f * num_lanes)
        note = "" if on_tpu else " (cpu-oracle timing)"
        rows.append((f"dispatch/scan_chunk{f}_us_per_frame",
                     timings["scan"] * 1e6,
                     f"dispatches_per_chunk={counts['scan']} per-frame lax.scan"
                     + note))
        rows.append((f"dispatch/megakernel_chunk{f}_us_per_frame",
                     timings["megakernel"] * 1e6,
                     f"dispatches_per_chunk={counts['megakernel']} "
                     f"dispatch_ratio={counts['scan'] / counts['megakernel']:.0f}x"
                     + note))

    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for row_name, value, derived in run():
        print(f"{row_name},{value:.4f},{derived}")
