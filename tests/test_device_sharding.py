"""Device-sharded lane serving: bit-parity with single-device (DESIGN.md §7).

The load-bearing invariant: sharding the lane axis over a ``("lanes",)``
mesh changes *where* each lane's math runs and nothing else — a sharded
run is bit-identical to the single-device run on both engine paths and
both association modes, including mid-chunk lane recycling, and the
compiled chunk program contains zero cross-device collectives.

The multi-device cases need simulated devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_device_sharding.py

(the CI ``multi-device`` job runs exactly that); under a plain
single-device session they skip, and the mesh-of-one cases still run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import SortConfig, SortEngine, cost as cost_mod
from repro.data.synthetic import (SceneConfig, generate_multiclass_scene,
                                  generate_scene)
from repro.serve import StreamScheduler
from repro.sharding import LaneSharding, lane_mesh, state_pspecs
from repro.sharding.lanes import lane_view, mesh_view

NDEV = jax.device_count()
needs_multi = pytest.mark.skipif(
    NDEV < 4, reason="needs >=4 devices: run with XLA_FLAGS="
                     "--xla_force_host_platform_device_count=8")

MAX_DETS = 7
LENGTHS = [12, 5, 9, 5, 1, 7]   # ragged mix, forces mid-chunk recycling


def _scene(seed, frames):
    _, _, db, dm = generate_scene(
        SceneConfig(num_frames=frames, max_objects=4, seed=seed))
    d = db.shape[1]
    assert d <= MAX_DETS, d
    return (np.pad(db, ((0, 0), (0, MAX_DETS - d), (0, 0))),
            np.pad(dm, ((0, 0), (0, MAX_DETS - d))))


def _engine(use_kernels, assoc="hungarian", chunk_kernel=False):
    return SortEngine(SortConfig(max_trackers=8, max_detections=MAX_DETS,
                                 use_kernels=use_kernels, assoc=assoc,
                                 chunk_kernel=chunk_kernel))


def _serve(eng, seqs, mesh, num_lanes=4, chunk=4):
    sched = StreamScheduler(eng, num_lanes=num_lanes, chunk=chunk, mesh=mesh)
    for name, db, dm in seqs:
        sched.submit(name, db, dm)
    return sched, sched.run()


def _assert_results_equal(a, b):
    assert [r.name for r in a] == [r.name for r in b]
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.uid, rb.uid, err_msg=ra.name)
        np.testing.assert_array_equal(ra.emit, rb.emit, err_msg=ra.name)
        np.testing.assert_array_equal(ra.boxes, rb.boxes, err_msg=ra.name)


# ------------------------------------------------------------- bit parity
@needs_multi
@pytest.mark.parametrize("use_kernels", [False, True])
@pytest.mark.parametrize("assoc", ["hungarian", "greedy"])
def test_sharded_bit_identical_to_single_device(use_kernels, assoc):
    """2x2 grid (engine path x association mode): a ragged mix served over
    a 4-device lane mesh equals the unsharded run bit for bit."""
    seqs = [(f"s{i}", *_scene(i, f)) for i, f in enumerate(LENGTHS)]
    eng = _engine(use_kernels, assoc)
    _, solo = _serve(eng, seqs, mesh=None)
    _, shard = _serve(eng, seqs, mesh=lane_mesh(4))
    _assert_results_equal(solo, shard)


def test_mesh_of_one_matches_unsharded():
    """The sharding layer with a single-device mesh is the identity —
    runs in any session, keeping the shard_map path exercised even where
    simulated devices are unavailable."""
    seqs = [(f"m{i}", *_scene(40 + i, f)) for i, f in enumerate([6, 3, 8])]
    eng = _engine(True)
    _, solo = _serve(eng, seqs, mesh=None, num_lanes=2)
    _, shard = _serve(eng, seqs, mesh=lane_mesh(1), num_lanes=2)
    _assert_results_equal(solo, shard)


@needs_multi
def test_sharded_drain_and_zero_frame_sequences():
    """The drain/lifecycle surface behaves identically in mesh mode:
    zero-frame sequences surface via pop_ready without a dispatch."""
    sched = StreamScheduler(_engine(True), num_lanes=4, chunk=4,
                            mesh=lane_mesh(4))
    sched.submit("empty", np.zeros((0, MAX_DETS, 4), np.float32),
                 np.zeros((0, MAX_DETS), bool))
    assert sched.busy
    assert [t.name for t in sched.pop_ready()] == ["empty"]
    assert sched.chunks_run == 0 and not sched.busy


# ----------------------------------------------- chunk-resident megakernel
@needs_multi
@pytest.mark.parametrize("assoc", ["hungarian", "greedy"])
def test_sharded_megakernel_bit_identical_to_single_device(assoc):
    """The chunk-resident dispatch mode (DESIGN.md §9) composes with the
    lane mesh: the same ragged mix served by the megakernel over 4
    devices equals the unsharded per-frame-scan run bit for bit."""
    seqs = [(f"k{i}", *_scene(20 + i, f)) for i, f in enumerate(LENGTHS)]
    _, solo = _serve(_engine(True, assoc), seqs, mesh=None)
    _, shard = _serve(_engine(True, assoc, chunk_kernel=True), seqs,
                      mesh=lane_mesh(4))
    _assert_results_equal(solo, shard)


@pytest.mark.parametrize("assoc", ["hungarian", "greedy"])
def test_megakernel_mesh_of_one_matches_unsharded(assoc):
    """Mesh-of-one megakernel (shard_map wrapping the chunk dispatch) is
    the identity — runs in any session."""
    seqs = [(f"ko{i}", *_scene(30 + i, f)) for i, f in enumerate([6, 3, 8])]
    _, solo = _serve(_engine(True, assoc), seqs, mesh=None, num_lanes=2)
    _, shard = _serve(_engine(True, assoc, chunk_kernel=True), seqs,
                      mesh=lane_mesh(1), num_lanes=2)
    _assert_results_equal(solo, shard)


# --------------------------------------- multiclass operands (DESIGN.md §10)
MC_EMBED = 4


def _mc_scene(seed, frames):
    _, _, _, db, dm, dc, de = generate_multiclass_scene(
        SceneConfig(num_frames=frames, max_objects=4, seed=seed),
        num_classes=3, embed_dim=MC_EMBED)
    d = db.shape[1]
    assert d <= MAX_DETS, d
    pad = MAX_DETS - d
    return (np.pad(db, ((0, 0), (0, pad), (0, 0))),
            np.pad(dm, ((0, 0), (0, pad))),
            np.pad(dc, ((0, 0), (0, pad))),
            np.pad(de, ((0, 0), (0, pad), (0, 0))))


def _mc_engine(chunk_kernel=False):
    return SortEngine(SortConfig(max_trackers=8, max_detections=MAX_DETS,
                                 use_kernels=True, chunk_kernel=chunk_kernel,
                                 cost=cost_mod.iou_embed(MC_EMBED),
                                 num_classes=3))


def _serve_mc(eng, seqs, mesh, num_lanes=4, chunk=4):
    sched = StreamScheduler(eng, num_lanes=num_lanes, chunk=chunk, mesh=mesh)
    for name, db, dm, dc, de in seqs:
        sched.submit(name, db, dm, det_class=dc, det_embed=de)
    return sched, sched.run()


def _assert_mc_results_equal(a, b):
    _assert_results_equal(a, b)
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.cls, rb.cls, err_msg=ra.name)


@needs_multi
@pytest.mark.parametrize("chunk_kernel", [False, True])
def test_sharded_multiclass_bit_identical_to_single_device(chunk_kernel):
    """The det_class/det_embed extras and the track-class output ride the
    same lane partitioning as every other chunk operand: a multiclass
    composed-cost mix (iou+embed, 3 classes) served over a 4-device mesh —
    including the embedding leaf in the resident state — equals the
    unsharded run bit for bit, classes included, under both dispatch
    modes."""
    seqs = [(f"mc{i}", *_mc_scene(50 + i, f)) for i, f in enumerate(LENGTHS)]
    _, solo = _serve_mc(_mc_engine(), seqs, mesh=None)
    _, shard = _serve_mc(_mc_engine(chunk_kernel=chunk_kernel), seqs,
                         mesh=lane_mesh(4))
    _assert_mc_results_equal(solo, shard)


def test_multiclass_mesh_of_one_matches_unsharded():
    """Mesh-of-one multiclass serving (extras + cls through shard_map) is
    the identity — runs in any session."""
    seqs = [(f"mo{i}", *_mc_scene(70 + i, f)) for i, f in enumerate([6, 3, 8])]
    _, solo = _serve_mc(_mc_engine(), seqs, mesh=None, num_lanes=2)
    _, shard = _serve_mc(_mc_engine(), seqs, mesh=lane_mesh(1), num_lanes=2)
    _assert_mc_results_equal(solo, shard)


@needs_multi
def test_sharded_multiclass_chunk_program_has_no_collectives():
    """Zero-collective claim survives the extra operands: the lowered
    multiclass chunk program (class + embed inputs, cls output) contains
    no cross-device collectives."""
    c, lanes, d = 3, 4, MAX_DETS
    sched = StreamScheduler(_mc_engine(), num_lanes=lanes, chunk=c,
                            mesh=lane_mesh(4))
    det = np.zeros((c, lanes, d, 4), np.float32)
    dm = np.zeros((c, lanes, d), bool)
    active = np.ones((c, lanes), bool)
    reset = np.zeros((c, lanes), bool)
    extras = sched._zero_extras(c, lanes, d)
    lowered = sched._chunk_fn.lower(
        sched._state,
        *sched._sharding.place(det, dm, active, reset, *extras))
    text = lowered.as_text()
    for op in ("all_reduce", "all_gather", "all_to_all",
               "collective_permute", "psum", "ppermute"):
        assert op not in text, f"collective {op} in multiclass chunk program"


# ---------------------------------------------------------- mesh plumbing
@needs_multi
def test_lane_budget_must_divide_shard_count():
    with pytest.raises(ValueError, match="divide"):
        StreamScheduler(_engine(True), num_lanes=3, mesh=lane_mesh(2))


def test_lane_mesh_rejects_oversubscription():
    with pytest.raises(ValueError, match="device_count"):
        lane_mesh(NDEV + 1)


def test_mesh_lane_state_views_are_exact_inverses():
    eng = _engine(True)
    lane = eng.init_ragged(6)
    back = lane_view(mesh_view(lane))
    for a, b in zip(jax.tree.leaves(lane), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@needs_multi
def test_state_stays_lane_sharded_across_chunks():
    """The resident state never collapses to a replicated/single-device
    layout between chunks — every leaf keeps a 'lanes' NamedSharding, so
    no chunk pays a resharding copy."""
    from jax.sharding import NamedSharding, PartitionSpec

    seqs = [(f"r{i}", *_scene(60 + i, f)) for i, f in enumerate([9, 4, 7])]
    eng = _engine(True)
    sched, _ = _serve(eng, seqs, mesh=lane_mesh(4))
    assert sched.chunks_run >= 3 and len(sched.trace_log) == 1
    specs = state_pspecs(sched._state)
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    for leaf, spec in zip(jax.tree.leaves(sched._state), spec_leaves):
        assert isinstance(leaf.sharding, NamedSharding), leaf.shape
        if leaf.size == 0:
            # the embed block of a cost without one, [0, T, L]: it holds no
            # data to shard, and XLA returns a zero-size output replicated
            # whatever its out-spec, so the sharding layer places it so
            # from the start (lanes._placement) and the chunk program
            # compiles once; only its shape is the state's
            assert leaf.shape == (0, eng.config.max_trackers,
                                  sched._state.frame_count.shape[0])
            continue
        assert leaf.sharding.spec == spec, (leaf.shape, leaf.sharding.spec)


@needs_multi
@pytest.mark.parametrize("use_kernels,chunk_kernel",
                         [(False, False), (True, False), (True, True)])
def test_sharded_chunk_program_has_no_collectives(use_kernels, chunk_kernel):
    """Sequences are independent, so the sharded chunk must lower to N
    disjoint per-device scans: no collective op of any kind may appear in
    the lowered program (the zero-collectives claim, checked not asserted
    from prose) — including the megakernel dispatch mode."""
    c, lanes, d = 3, 4, MAX_DETS
    sched = StreamScheduler(_engine(use_kernels, chunk_kernel=chunk_kernel),
                            num_lanes=lanes, chunk=c, mesh=lane_mesh(4))
    det = np.zeros((c, lanes, d, 4), np.float32)
    dm = np.zeros((c, lanes, d), bool)
    active = np.ones((c, lanes), bool)
    reset = np.zeros((c, lanes), bool)
    lowered = sched._chunk_fn.lower(
        sched._state, *sched._sharding.place(det, dm, active, reset))
    text = lowered.as_text()
    for op in ("all_reduce", "all_gather", "all_to_all",
               "collective_permute", "psum", "ppermute"):
        assert op not in text, f"collective {op} in sharded chunk program"


# ------------------------------------------------------- property coverage
@pytest.mark.slow
@needs_multi
@settings(max_examples=4, deadline=None, derandomize=True)
@given(lengths=st.lists(st.sampled_from([1, 4, 9, 12]), min_size=1,
                        max_size=6),
       shards=st.sampled_from([2, 4]))
def test_sharded_exactness_property(lengths, shards):
    """Any ragged mix over any shard count stays bit-identical to the
    unsharded run (fused Hungarian path; recycling churn included)."""
    seqs = [(f"p{i}", *_scene(80 + i, f)) for i, f in enumerate(lengths)]
    eng = _engine(True)
    _, solo = _serve(eng, seqs, mesh=None)
    _, shard = _serve(eng, seqs, mesh=lane_mesh(shards))
    _assert_results_equal(solo, shard)


# ------------------------------------- checkpoint topology neutrality §11
@needs_multi
def test_export_import_across_topologies():
    """The serving checkpoint is topology-neutral (DESIGN.md §11): state
    exported from a 4-device mesh resumes bit-exactly on a single device
    and vice versa — the engine-layout crossing erases the placement."""
    seqs = [(f"s{i}", *_scene(40 + i, f)) for i, f in enumerate(LENGTHS)]
    eng = _engine(True)
    _, ref = _serve(eng, seqs, mesh=lane_mesh(4))

    def interrupted(save_mesh, load_mesh):
        a = StreamScheduler(_engine(True), num_lanes=4, chunk=4,
                            mesh=save_mesh)
        for name, db, dm in seqs:
            a.submit(name, db, dm)
        out = a.run_chunk()
        meta, arrays = a.export_state()
        b = StreamScheduler(_engine(True), num_lanes=4, chunk=4,
                            mesh=load_mesh)
        b.import_state(meta, arrays)
        while b.busy:
            out.extend(b.run_chunk())
        return out

    _assert_results_equal(interrupted(lane_mesh(4), None), ref)
    _assert_results_equal(interrupted(None, lane_mesh(4)), ref)
