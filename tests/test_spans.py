"""Profiler spans and byte counters of the served path.

A chunk's host time splits into the scheduler's spans (``scheduler.SPANS``:
plan, stage, fetch, unpack, release), a submission's into ``svc.submit``,
a commit's into ``svc.checkpoint``.  Each is a ``jax.profiler``
annotation, so a profile of the service holds them on the same clock as
the device's operations; these tests read them back from a CPU profile.
``bytes_staged`` / ``bytes_fetched`` count the chunk operands moved to
the device and the results moved back, which follow from the shapes.
"""
import asyncio
import gc
import glob
import os
import weakref

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import cost as cost_mod
from repro.core.sort import SortConfig, SortEngine
from repro.serve import StreamScheduler, TrackingService, scheduler, service
from repro.sharding import lane_mesh, lanes

T, D, LANES, CHUNK, EMBED = 8, 5, 3, 4, 4


def _engine(multiclass=False):
    extra = (dict(num_classes=3, cost=cost_mod.iou_embed(EMBED))
             if multiclass else {})
    return SortEngine(SortConfig(max_trackers=T, max_detections=D, **extra))


def _seq(i, frames, multiclass=False):
    rng = np.random.default_rng(i)
    xy = rng.uniform(0, 200, (frames, D, 2)).astype(np.float32)
    boxes = np.concatenate([xy, xy + 20], axis=-1)
    mask = rng.random((frames, D)) < 0.7
    kw = {}
    if multiclass:
        kw = dict(det_class=rng.integers(0, 3, (frames, D)),
                  det_embed=rng.random((frames, D, EMBED)).astype(np.float32))
    return (f"s{i}", boxes, mask), kw


def _service(multiclass=False, mesh=None, **knobs):
    sched = StreamScheduler(_engine(multiclass), num_lanes=LANES,
                            max_dets=D, chunk=CHUNK, mesh=mesh)
    return TrackingService(sched, **knobs)


def _submit(svc, i, frames, multiclass=False):
    args, kw = _seq(i, frames, multiclass)
    return asyncio.run(svc.submit(*args, **kw))


# each module's spans, by the prefix every one of its names carries
MODULES = {"sched.": scheduler, "svc.": service, "lanes.": lanes}


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; return the program's spans in start
    order as ``(name, start_ns, end_ns, stats)``: every host event named
    with one of the program's prefixes."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.end_ns, {k: v for k, v in e.stats})
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(tuple(MODULES))]
    return sorted(spans, key=lambda s: s[1])


def test_span_names_are_distinct_and_layered():
    names = scheduler.SPANS + service.SPANS + lanes.SPANS
    assert len(set(names)) == len(names) == 8
    for prefix, module in MODULES.items():
        assert all(n.startswith(prefix) for n in module.SPANS)


def test_each_chunk_writes_its_spans_in_order(tmp_path):
    svc = _service()
    for i, f in enumerate((6, 3, 9)):
        _submit(svc, i, f)

    def serve():
        for _ in range(3):
            asyncio.run(svc.step())

    spans = _profiled(tmp_path, serve)
    sched = [s for s in spans if s[0] in scheduler.SPANS]
    assert [s[0] for s in sched] == list(scheduler.SPANS) * 3
    assert [s[3] for s in sched] == [{"chunk": n, "shards": 1}
                                     for n in range(3)
                                     for _ in scheduler.SPANS]
    # one after the other, none nested in another
    for a, b in zip(sched, sched[1:]):
        assert a[2] <= b[1]
    # without a mesh nothing is placed by the sharding layer
    assert not [s for s in spans if s[0] in lanes.SPANS]


def test_mesh_chunk_places_its_operands_inside_the_stage(tmp_path):
    """On a lane mesh (of the one device a test process has) each chunk
    writes one ``lanes.place`` inside its ``sched.stage``, and every span
    says how many devices the mesh holds."""
    svc = _service(mesh=lane_mesh(1))
    for i, f in enumerate((6, 3, 9)):
        _submit(svc, i, f)

    def serve():
        for _ in range(3):
            asyncio.run(svc.step())

    spans = _profiled(tmp_path, serve)
    for name, *_ in spans:
        assert name in MODULES[name.split(".")[0] + "."].SPANS, name
    stages = [s for s in spans if s[0] == "sched.stage"]
    places = [s for s in spans if s[0] == "lanes.place"]
    assert len(stages) == len(places) == svc.sched.chunks_run == 3
    for stage, place in zip(stages, places):
        assert stage[1] <= place[1] and place[2] <= stage[2]
        assert place[3] == {"shards": 1}
    sched = [s for s in spans if s[0] in scheduler.SPANS]
    assert [s[0] for s in sched] == list(scheduler.SPANS) * 3
    assert all(s[3]["shards"] == 1 for s in sched)


def test_no_step_work_writes_no_chunk_spans(tmp_path):
    svc = _service()
    spans = _profiled(tmp_path, lambda: asyncio.run(svc.step()))
    assert spans == []
    assert svc.sched.chunks_run == 0


def test_each_submit_writes_one_span_with_its_index(tmp_path):
    svc = _service()

    def submit_three():
        _submit(svc, 0, 5)
        _submit(svc, 1, 0)            # zero frames: finalized inside
        _submit(svc, 2, 7)

    spans = _profiled(tmp_path, submit_three)
    assert [(s[0], s[3]) for s in spans] == [
        ("svc.submit", {"seq": i}) for i in range(3)]


def test_shed_submit_still_writes_its_span(tmp_path):
    from repro.serve import Overloaded
    svc = _service(max_pending=1)
    _submit(svc, 0, 5)

    def shed():
        with pytest.raises(Overloaded):
            _submit(svc, 1, 5)

    spans = _profiled(tmp_path, shed)
    assert [(s[0], s[3]) for s in spans] == [("svc.submit", {"seq": 1})]


def test_checkpointing_service_writes_one_span_per_commit(tmp_path):
    svc = _service(ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=1)
    for i, f in enumerate((5, 9)):
        _submit(svc, i, f)

    def serve():
        asyncio.run(svc.drain())

    spans = _profiled(tmp_path / "trace", serve)
    svc.close()
    commits = [s for s in spans if s[0] == "svc.checkpoint"]
    chunks = svc.sched.chunks_run
    assert chunks == 3
    assert [s[3] for s in commits] == [{"chunk": n} for n in
                                       range(1, chunks + 1)]
    # each commit follows the release of the chunk it covers
    releases = [s for s in spans if s[0] == "sched.release"]
    assert all(r[2] <= c[1] for r, c in zip(releases, commits))


def test_release_frees_the_finished_rows():
    """A released sequence's arrays are exactly its length, and the
    scheduler keeps nothing of it once it is released."""
    svc = _service()
    _submit(svc, 0, 3)
    seq = weakref.ref(svc.sched._pending[0])
    asyncio.run(svc.step())                 # 3 frames: done in one chunk
    tracks = svc.completed[0]
    assert tracks.boxes.shape == (3, T, 4)
    assert tracks.uid.shape == tracks.emit.shape == (3, T)
    gc.collect()
    assert seq() is None


def _expected_bytes(multiclass):
    c, l = CHUNK, LANES
    staged = c * l * D * 4 * 4 + c * l * D + 2 * c * l
    fetched = c * l * T * 4 * 4 + c * l * T * 4 + c * l * T + l * 4
    if multiclass:
        staged += c * l * D * 4 + c * l * D * EMBED * 4
        fetched += c * l * T * 4
    return staged, fetched


@pytest.mark.parametrize("multiclass", [False, True],
                         ids=["single_class", "class_embed"])
def test_byte_counters_follow_the_shapes(multiclass):
    svc = _service(multiclass)
    for i, f in enumerate((6, 3, 9, 2)):
        _submit(svc, i, f, multiclass)
    sched = svc.sched
    assert (sched.bytes_staged, sched.bytes_fetched) == (0, 0)
    staged, fetched = _expected_bytes(multiclass)
    asyncio.run(svc.step())
    assert (sched.bytes_staged, sched.bytes_fetched) == (staged, fetched)
    asyncio.run(svc.drain())
    n = sched.chunks_run
    assert n >= 3
    assert (sched.bytes_staged, sched.bytes_fetched) == (n * staged,
                                                        n * fetched)


def test_byte_counters_survive_export_import():
    svc = _service()
    for i, f in enumerate((6, 11)):
        _submit(svc, i, f)
    asyncio.run(svc.step())
    meta, arrays = svc.sched.export_state()
    assert meta["counters"]["bytes_staged"] == svc.sched.bytes_staged > 0
    assert meta["counters"]["bytes_fetched"] == svc.sched.bytes_fetched > 0

    again = StreamScheduler(_engine(), num_lanes=LANES, max_dets=D,
                            chunk=CHUNK)
    again.import_state(meta, arrays)
    assert again.bytes_staged == svc.sched.bytes_staged
    assert again.bytes_fetched == svc.sched.bytes_fetched

    # a snapshot written before the counters existed resumes at 0
    for key in ("bytes_staged", "bytes_fetched"):
        del meta["counters"][key]
    older = StreamScheduler(_engine(), num_lanes=LANES, max_dets=D,
                            chunk=CHUNK)
    older.import_state(meta, arrays)
    assert (older.bytes_staged, older.bytes_fetched) == (0, 0)
    assert older.chunks_run == svc.sched.chunks_run
