"""The lane-mesh served path, in any test run: ``TrackingService`` over
``StreamScheduler(mesh=lane_mesh(4))`` with ``mot15-sort``'s engine (the
chunk kernel and Hungarian association) on four devices.

A single-device test process cannot make devices, so the module runs itself
as a child process with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(the device count is fixed when JAX starts) and reads what the child found:
the tracks against ``core/ref_numpy.py::Sort``, against the same
submissions served on one device, and the compiled chunk program.
"""
import asyncio
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SHARDS, LANES, CHUNK, T = 4, 16, 4, 16
# ragged lengths, more sequences than lanes: lanes recycle mid-chunk
LENGTHS = [3, 9, 14, 5, 1, 11, 7, 17, 6, 2, 13, 4, 10, 8, 15, 5,
           12, 3, 9, 6, 16, 2, 7, 11]
CHILD_TIMEOUT_S = 600
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


def _scenes():
    from repro.data.synthetic import SceneConfig, generate_scene
    rng = np.random.default_rng(1504)
    scenes = []
    for i, f in enumerate(LENGTHS):
        _, _, db, dm = generate_scene(SceneConfig(
            num_frames=f, max_objects=int(rng.integers(2, 7)),
            seed=int(rng.integers(2**31))))
        scenes.append((f"m{i:02d}", db, dm))
    return scenes


def _serve(scenes, mesh):
    """Every scene through the service; the tracks in submission order
    and the scheduler."""
    from repro.core import SortConfig, SortEngine
    from repro.serve import StreamScheduler, TrackingService

    engine = SortEngine(SortConfig(
        max_trackers=T, max_detections=T, iou_threshold=0.3, max_age=1,
        min_hits=3, assoc="hungarian", use_kernels=True, chunk_kernel=True))
    sched = StreamScheduler(engine, num_lanes=LANES, chunk=CHUNK, mesh=mesh)
    svc = TrackingService(sched, max_pending=4 * LANES,
                          per_client_pending=4 * LANES)

    async def run():
        index = [await svc.submit(name, db, dm) for name, db, dm in scenes]
        await svc.drain()
        return [svc.completed[i] for i in index]
    return asyncio.run(run()), sched


def _oracle_mismatches(tracks, scenes) -> list:
    """Frames whose emitted ids differ from the reference's, or whose
    boxes lie outside the parity suite's tolerance
    (``tests/test_oracle_parity.py``: rtol 1e-3, atol 0.5 px)."""
    from repro.core.ref_numpy import Sort as RefSort
    bad = []
    for tr, (name, db, dm) in zip(tracks, scenes):
        ref = RefSort(assoc="hungarian")
        for t in range(db.shape[0]):
            want = ref.update(db[t][dm[t]])
            uid, emit = tr.uid[t], tr.emit[t]
            got = {int(u): tr.boxes[t, k] for k, u in enumerate(uid)
                   if emit[k]}
            if sorted(got) != sorted(int(o[4]) for o in want) or not all(
                    np.allclose(got[int(o[4])], o[:4], rtol=1e-3, atol=0.5)
                    for o in want):
                bad.append(f"{name} frame {t}")
    return bad


def child() -> dict:
    """Serve on the 4-device mesh and on one device; what a test checks."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.sharding import lane_mesh, state_pspecs

    scenes = _scenes()
    mesh_tracks, sched = _serve(scenes, lane_mesh(SHARDS))
    one_tracks, _ = _serve(scenes, None)
    c, d = CHUNK, sched.max_dets
    operands = sched._sharding.place(
        np.zeros((c, LANES, d, 4), np.float32), np.zeros((c, LANES, d), bool),
        np.ones((c, LANES), bool), np.zeros((c, LANES), bool))
    hlo = sched._chunk_fn.lower(sched._state, *operands).compile().as_text()
    leaves = jax.tree.leaves(sched._state)
    specs = jax.tree.leaves(state_pspecs(sched._state),
                            is_leaf=lambda x: isinstance(x, PartitionSpec))
    sharded = [isinstance(a.sharding, NamedSharding) and
               a.sharding.spec == s for a, s in zip(leaves, specs)
               if a.size > 0]
    identical = all(
        a.name == b.name and all(np.array_equal(x, y) for x, y in zip(
            (a.uid, a.emit, a.boxes), (b.uid, b.emit, b.boxes)))
        for a, b in zip(mesh_tracks, one_tracks))
    return {
        "devices": len(jax.devices()),
        "sequences": [len(mesh_tracks), len(scenes)],
        "frames": [int(t.uid.shape[0]) for t in mesh_tracks],
        "mid_chunk_admissions": sum(step % c != 0
                                    for _, step in sched.admissions),
        "chunks": sched.chunks_run,
        "oracle_mismatches": _oracle_mismatches(mesh_tracks, scenes),
        "identical_to_one_device": bool(identical),
        "collectives": [op for op in COLLECTIVES if op in hlo],
        "chunk_programs_compiled": sched._chunk_fn._cache_size(),
        "chunk_programs_traced": len(sched.trace_log),
        "nonempty_leaves_lane_sharded": [len(sharded), all(sharded)],
    }


@pytest.fixture(scope="module")
def mesh_run() -> dict:
    root = Path(__file__).resolve().parents[1]
    path = os.pathsep.join(p for p in (str(root / "src"),
                                       os.environ.get("PYTHONPATH")) if p)
    flags = " ".join(f for f in (
        os.environ.get("XLA_FLAGS"),
        f"--xla_force_host_platform_device_count={SHARDS}") if f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path,
               XLA_FLAGS=flags)
    r = subprocess.run([sys.executable, __file__], capture_output=True,
                       text=True, timeout=CHILD_TIMEOUT_S, env=env, cwd=root)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == SHARDS
    assert out["sequences"] == [len(LENGTHS)] * 2
    assert out["frames"] == LENGTHS
    return out


def test_mesh_service_matches_the_reference(mesh_run):
    assert mesh_run["mid_chunk_admissions"] > 0
    assert mesh_run["oracle_mismatches"] == []


def test_mesh_service_is_bit_identical_to_one_device(mesh_run):
    assert mesh_run["identical_to_one_device"]


def test_mesh_chunk_program_has_no_collective_and_compiles_once(mesh_run):
    """Lanes never talk, so the program holds no collective; the state
    comes back from every chunk with the shardings it went in with, so the
    program is traced and compiled once for all the chunks."""
    assert mesh_run["chunks"] >= 3
    assert mesh_run["collectives"] == []
    assert mesh_run["chunk_programs_traced"] == 1
    assert mesh_run["chunk_programs_compiled"] == 1
    count, all_sharded = mesh_run["nonempty_leaves_lane_sharded"]
    assert count > 0 and all_sharded


if __name__ == "__main__":
    print(json.dumps(child()))
