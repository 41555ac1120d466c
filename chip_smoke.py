"""Chip smoke test: the served tracking path, end to end, on a TPU.

    python chip_smoke.py             # one chip: phases A, B and C
    python chip_smoke.py --chips 4   # the 4-chip lane mesh vs one chip

Everything runs in this one process, which holds the chip.  The workload
is seeded synthetic MOT15-shaped traffic (``data/mot.py::TABLE_I`` object
counts, ragged lengths of 48-80 frames), enough sequences to fill every
lane of a chip and then recycle lanes as sequences end.  The engines are
the presets of ``configs/sort_mot.py`` at their served width: T = D = 16,
``max_age`` 1, ``min_hits`` 3, IoU 0.3, 2,048 lanes per chip in 32-frame
chunks.

* Phase A: ``SERVICE`` (fused frame kernel, Hungarian) behind
  ``TrackingService``, checkpointing every chunk.  Any dispatch failure
  ends the run.
* Phase B: ``MEGAKERNEL`` (chunk kernel, Hungarian) on the same
  submissions; track ids and emit flags must equal phase A's.
* Phase C: ``MEGAKERNEL_GREEDY`` (chunk kernel, in-kernel greedy).
* ``--chips 4``: ``SERVICE`` on a 4-chip ``("lanes",)`` mesh, 4 x 2,048
  lanes, against the same submissions on one chip; the sharded program
  must hold the kernel and no collective, and its state must stay spread
  over the four chips.

Every phase checks that its compiled chunk program holds the Pallas kernel
(``tpu_custom_call``), so an oracle silently standing in for the kernel
fails, and that a seeded sample of sequences matches the numpy reference
``core/ref_numpy.py::Sort``: identical track ids and emit flags, emitted
boxes within ``BOX_TOL``.  Times printed here are smoke timings of one
run, compile included, not benchmark numbers.

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.
Without a TPU the script exits non-zero before doing anything.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "configs")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import sort_mot  # noqa: E402
from repro.ckpt import committed_steps  # noqa: E402
from repro.core import SortEngine  # noqa: E402
from repro.core.ref_numpy import Sort as RefSort  # noqa: E402
from repro.data import mot  # noqa: E402
from repro.data.synthetic import SceneConfig, generate_scene  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import StreamScheduler, TrackingService  # noqa: E402
from repro.sharding import lane_mesh  # noqa: E402

LANES_PER_CHIP = 2048
CHUNK = 32
DETS = 16
NUM_SCENES = 2560
FRAMES = (48, 80)
ORACLE_SAMPLE = 16
SEED = 0
# float32 engine vs float64 numpy reference, as tests/test_oracle_parity.py
BOX_TOL = {"rtol": 1e-3, "atol": 0.5}
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


def make_scenes(num_scenes: int, seed: int):
    """``[(name, det_boxes [F, D_i, 4], det_mask [F, D_i])]``, seeded."""
    rng = np.random.default_rng(seed)
    shapes = list(mot.TABLE_I.items())
    scenes = []
    for i in range(num_scenes):
        name, (_, max_objects) = shapes[i % len(shapes)]
        cfg = SceneConfig(num_frames=int(rng.integers(*FRAMES, endpoint=True)),
                          max_objects=max_objects,
                          seed=int(rng.integers(2**31)))
        _, _, db, dm = generate_scene(cfg)
        scenes.append((f"{name}-{i:05d}", db, dm))
    return scenes


def compile_chunk_program(sched: StreamScheduler):
    """AOT-compile the scheduler's chunk program at its serving shapes;
    returns ``(hlo_text, seconds)``."""
    c, l, d = sched.chunk, sched.num_lanes, sched.max_dets
    zeros = (np.zeros((c, l, d, 4), np.float32), np.zeros((c, l, d), bool),
             np.zeros((c, l), bool), np.zeros((c, l), bool))
    operands = (sched._sharding.place(*zeros) if sched._sharding is not None
                else tuple(jnp.asarray(a) for a in zeros))
    t0 = time.perf_counter()
    compiled = sched._chunk_fn.lower(sched._state, *operands).compile()
    return compiled.as_text(), time.perf_counter() - t0


def kernel_in_program(hlo: str) -> bool:
    return "tpu_custom_call" in hlo


async def serve(sched: StreamScheduler, scenes, ckpt_dir=None):
    """The served path: every scene through ``TrackingService``, pumped
    one chunk at a time so the first dispatch failure ends the run."""
    svc = TrackingService(sched, max_pending=len(scenes),
                          per_client_pending=len(scenes),
                          ckpt_dir=ckpt_dir, ckpt_every=1)
    index = [await svc.submit(name, db, dm) for name, db, dm in scenes]
    while svc.busy:
        await svc.step()
    svc.close()
    return [svc.completed[i] for i in index]


def run_phase(label: str, cfg, scenes, lanes: int, *, mesh=None,
              ckpt_dir=None):
    """Build the scheduler, compile its chunk program, serve ``scenes``;
    returns ``(tracks, scheduler, hlo)``."""
    sched = StreamScheduler(SortEngine(cfg), num_lanes=lanes, max_dets=DETS,
                            chunk=CHUNK, mesh=mesh)
    hlo, compile_s = compile_chunk_program(sched)
    t0 = time.perf_counter()
    tracks = asyncio.run(serve(sched, scenes, ckpt_dir))
    wall = time.perf_counter() - t0
    frames = sum(t.num_frames for t in tracks)
    print(f"phase {label} [smoke timing, not a benchmark]: compile "
          f"{compile_s:.1f} s, serve {wall:.1f} s for {len(tracks)} "
          f"sequences / {frames} frames in {sched.chunks_run} chunks on "
          f"{lanes} lanes, utilization {sched.utilization:.3f}", flush=True)
    return tracks, sched, hlo


def check_kernel(label: str, hlo: str) -> list[str]:
    ok = kernel_in_program(hlo)
    print(f"phase {label}: tpu_custom_call in the compiled chunk program: "
          f"{ok}", flush=True)
    return [] if ok else [f"{label}: no Pallas kernel in the chunk program"]


def check_oracle(label: str, tracks, scenes, assoc: str) -> list[str]:
    """A seeded sample of sequences against ``core/ref_numpy.py::Sort``."""
    rng = np.random.default_rng(SEED + 1)
    sample = sorted(rng.choice(len(scenes), size=min(ORACLE_SAMPLE,
                                                     len(scenes)),
                               replace=False).tolist())
    frames = id_bad = box_bad = 0
    worst = 0.0
    for i in sample:
        _, db, dm = scenes[i]
        ref, tr = RefSort(assoc=assoc), tracks[i]
        for f in range(db.shape[0]):
            want = ref.update(db[f][dm[f]])
            em = tr.emit[f]
            got = {int(u): tr.boxes[f, k] for k, u in enumerate(tr.uid[f])
                   if em[k]}
            frames += 1
            if sorted(got) != sorted(int(o[4]) for o in want):
                id_bad += 1
                continue
            for o in want:
                err = np.abs(got[int(o[4])] - o[:4])
                worst = max(worst, float(err.max()))
                if not np.allclose(got[int(o[4])], o[:4], **BOX_TOL):
                    box_bad += 1
    print(f"phase {label}: numpy oracle (assoc={assoc}) on {len(sample)} "
          f"sequences, {frames} frames: {frames - id_bad} with identical "
          f"ids+emit, {id_bad} not; emitted boxes outside {BOX_TOL}: "
          f"{box_bad}, max abs error {worst:.4g} px", flush=True)
    return ([f"{label}: {id_bad} frames differ from the oracle in ids/emit"]
            if id_bad else []) + \
        ([f"{label}: {box_bad} boxes outside {BOX_TOL}"] if box_bad else [])


def compare_runs(label: str, got, want) -> list[str]:
    """Per-sequence outputs of two runs of the same submissions: ids and
    emit flags identical, emitted boxes within ``BOX_TOL``."""
    id_bad, box_bad, exact, worst = [], 0, 0, 0.0
    for a, b in zip(got, want):
        if not (np.array_equal(a.uid, b.uid)
                and np.array_equal(a.emit, b.emit)):
            id_bad.append(a.name)
            continue
        ea, eb = a.boxes[a.emit], b.boxes[b.emit]
        if ea.size:
            worst = max(worst, float(np.abs(ea - eb).max()))
        box_bad += int(not np.allclose(ea, eb, **BOX_TOL))
        exact += int(np.array_equal(a.boxes, b.boxes))
    print(f"{label}: {len(got)} sequences, ids+emit identical in "
          f"{len(got) - len(id_bad)}, emitted boxes outside {BOX_TOL} in "
          f"{box_bad}, max abs difference {worst:.4g} px, all boxes "
          f"bit-identical in {exact}"
          + (f"; ids/emit differ in {id_bad[:8]}" if id_bad else ""),
          flush=True)
    return ([f"{label}: ids/emit differ in {len(id_bad)} sequences"]
            if id_bad else []) + \
        ([f"{label}: boxes outside tolerance in {box_bad} sequences"]
         if box_bad else [])


def one_chip(scenes) -> list[str]:
    failures = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        a, _, hlo = run_phase("A SERVICE", sort_mot.SERVICE, scenes,
                              LANES_PER_CHIP, ckpt_dir=ckpt_dir)
        steps = committed_steps(ckpt_dir)
        print(f"phase A SERVICE: {len(steps)} checkpoints retained, last "
              f"at chunk {steps[-1] if steps else None}", flush=True)
        failures += [] if steps else ["A SERVICE: no checkpoint committed"]
    failures += check_kernel("A SERVICE", hlo)
    failures += check_oracle("A SERVICE", a, scenes, "hungarian")

    b, _, hlo = run_phase("B MEGAKERNEL", sort_mot.MEGAKERNEL, scenes,
                          LANES_PER_CHIP)
    failures += check_kernel("B MEGAKERNEL", hlo)
    failures += check_oracle("B MEGAKERNEL", b, scenes, "hungarian")
    failures += compare_runs("phase B MEGAKERNEL vs phase A SERVICE", b, a)

    c, _, hlo = run_phase("C MEGAKERNEL_GREEDY", sort_mot.MEGAKERNEL_GREEDY,
                          scenes, LANES_PER_CHIP)
    failures += check_kernel("C MEGAKERNEL_GREEDY", hlo)
    failures += check_oracle("C MEGAKERNEL_GREEDY", c, scenes, "greedy")
    return failures


def four_chips(scenes) -> list[str]:
    """``SERVICE`` sharded over a 4-chip lane mesh vs the same submissions
    on one chip at the same lane count."""
    if len(jax.devices()) < 4:
        return [f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}"]
    replicas = [(f"{name}#{r}", db, dm) for r in range(4)
                for name, db, dm in scenes]
    lanes = 4 * LANES_PER_CHIP
    mesh = lane_mesh(4)
    sharded, sched, hlo = run_phase("4-chip SERVICE", sort_mot.SERVICE,
                                    replicas, lanes, mesh=mesh)
    failures = check_kernel("4-chip SERVICE", hlo)
    found = [c for c in COLLECTIVES if c in hlo]
    print(f"phase 4-chip SERVICE: collectives in the compiled program: "
          f"{found or 'none'}", flush=True)
    failures += [f"4-chip SERVICE: collectives {found}"] if found else []

    devices = set(mesh.devices.flat)
    misplaced = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(sched._state):
        if leaf.size == 0:          # the embed block of a cost without one
            continue
        shards = leaf.addressable_shards
        widths = {s.data.shape[-1] for s in shards}
        if {s.device for s in shards} != devices or \
                widths != {leaf.shape[-1] // 4}:
            misplaced.append(jax.tree_util.keystr(path))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in mesh.devices.flat]
    print(f"phase 4-chip SERVICE: resident state leaves not split 4 ways "
          f"over the mesh: {misplaced or 'none'}; device bytes in use "
          f"{in_use}", flush=True)
    failures += [f"4-chip SERVICE: state not lane-sharded: {misplaced}"] \
        if misplaced else []
    if in_use[0] > 2 * max(in_use[1:]):
        failures.append(f"4-chip SERVICE: device 0 holds {in_use[0]} bytes, "
                        f"the others at most {max(in_use[1:])}")
    failures += check_oracle("4-chip SERVICE", sharded, replicas, "hungarian")
    del sched

    single, _, hlo = run_phase("1-chip SERVICE", sort_mot.SERVICE, replicas,
                               lanes)
    failures += check_kernel("1-chip SERVICE", hlo)
    failures += compare_runs("4-chip SERVICE vs 1-chip SERVICE", sharded,
                             single)
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip lane-mesh phase and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's backend is {backend!r}",
              file=sys.stderr)
        return 1
    print(f"chip_smoke: compile cache at {enable_compile_cache()}; devices "
          f"{jax.devices()}", flush=True)
    t0 = time.perf_counter()
    scenes = make_scenes(NUM_SCENES, SEED)
    print(f"chip_smoke: {len(scenes)} seeded scenes, "
          f"{sum(db.shape[0] for _, db, _ in scenes)} frames, made in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    failures = four_chips(scenes) if args.chips == 4 else one_chip(scenes)
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
