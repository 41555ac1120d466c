"""Elastic lane budgets: equivalence + recompilation lockdown (DESIGN.md §8).

The load-bearing invariants:

* **equivalence** — an elastic scheduler's per-sequence outputs are
  bit-identical to a fixed ``max_lanes`` scheduler's, on both engine
  paths and both association modes, under arbitrary admission/drain churn
  and forced resizes, including over a ``("lanes",)`` device mesh.
  Migration moves every kept lane (mid-sequence lanes included) bit for
  bit; appended lanes are a masked re-init.
* **recompilation lock** — the chunk scan compiles at most once per
  ladder width; repeated grow/shrink cycles never retrace (the
  scheduler's ``trace_log`` records one entry per chunk-shape trace).
* **shrink-by-drain** — a requested shrink never drops the budget while
  an evacuating lane holds a live sequence; uids never alias; the reorder
  buffer stays in submission order (cross-checked against the numpy
  oracle, the PR 4 lifecycle-audit pattern).

The mesh cases need simulated devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the CI
``multi-device`` job) and skip elsewhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import SortConfig, SortEngine, resize_streams, slots
from repro.core.ref_numpy import Sort as RefSort
from repro.core.sort import sort_state_of
from repro.data.synthetic import SceneConfig, generate_scene
from repro.serve import StreamScheduler, lane_ladder
from repro.sharding import lane_mesh

NDEV = jax.device_count()
needs_multi = pytest.mark.skipif(
    NDEV < 4, reason="needs >=4 devices: run with XLA_FLAGS="
                     "--xla_force_host_platform_device_count=8")

MAX_DETS = 7
PATHS = [(False, "hungarian"), (False, "greedy"),
         (True, "hungarian"), (True, "greedy")]
_ENGINES: dict = {}


def _scene(seed, frames):
    _, _, db, dm = generate_scene(
        SceneConfig(num_frames=frames, max_objects=4, seed=seed))
    d = db.shape[1]
    assert d <= MAX_DETS, d
    return (np.pad(db, ((0, 0), (0, MAX_DETS - d), (0, 0))),
            np.pad(dm, ((0, 0), (0, MAX_DETS - d))))


def _engine(use_kernels, assoc="hungarian"):
    key = (use_kernels, assoc)
    if key not in _ENGINES:
        _ENGINES[key] = SortEngine(SortConfig(
            max_trackers=8, max_detections=MAX_DETS,
            use_kernels=use_kernels, assoc=assoc))
    return _ENGINES[key]


def _assert_results_equal(a, b):
    assert [r.name for r in a] == [r.name for r in b]
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.uid, rb.uid, err_msg=ra.name)
        np.testing.assert_array_equal(ra.emit, rb.emit, err_msg=ra.name)
        np.testing.assert_array_equal(ra.boxes, rb.boxes, err_msg=ra.name)


def _churn(el, ref, seqs, widths):
    """Interleave submits, chunk dispatches, and forced resizes on the
    elastic scheduler; feed the fixed reference the same sequences.
    Returns (elastic results, reference results), both submission-order
    complete."""
    got = []
    for i, (name, db, dm) in enumerate(seqs):
        el.submit(name, db, dm)
        ref.submit(name, db, dm)
        if widths and i % 2 == 1:
            el.request_width(widths[(i // 2) % len(widths)])
            got.extend(el._run_chunk())
    el.request_width(None)          # release the pin; drain on policy
    got.extend(el.run())
    return got, ref.run()


# ------------------------------------------------------------- equivalence
@pytest.mark.parametrize("use_kernels,assoc", PATHS)
def test_elastic_bit_identical_to_fixed_max(use_kernels, assoc):
    """2x2 grid (engine path x assoc mode): ragged churn with forced
    grow/shrink through every ladder width equals a fixed max_lanes run
    bit for bit — migration never perturbs a lane mid-sequence."""
    lengths = [12, 5, 9, 1, 7, 12, 3, 5]
    seqs = [(f"e{i}", *_scene(i, f)) for i, f in enumerate(lengths)]
    eng = _engine(use_kernels, assoc)
    el = StreamScheduler(eng, chunk=4, min_lanes=1, max_lanes=4)
    ref = StreamScheduler(eng, num_lanes=4, chunk=4)
    out_el, out_ref = _churn(el, ref, seqs, widths=[4, 1, 2])
    _assert_results_equal(out_el, out_ref)
    assert len(el.resizes) > 0          # the churn really resized


def test_elastic_policy_grows_and_shrinks_without_forcing():
    """Demand-driven policy alone: a burst grows the budget, the drain
    tail shrinks it back after `shrink_patience` boundaries, and outputs
    still equal the fixed max_lanes run."""
    eng = _engine(True)
    el = StreamScheduler(eng, chunk=4, min_lanes=2, max_lanes=8,
                         shrink_patience=2)
    ref = StreamScheduler(eng, num_lanes=8, chunk=4)
    seqs = [("long", *_scene(0, 40))] + \
        [(f"s{i}", *_scene(1 + i, 4)) for i in range(7)]
    for name, db, dm in seqs:
        el.submit(name, db, dm)
        ref.submit(name, db, dm)
    _assert_results_equal(el.run(), ref.run())
    grew = [r for r in el.resizes if r[2] > r[1]]
    shrank = [r for r in el.resizes if r[2] < r[1]]
    assert grew and shrank, el.resizes
    assert el.num_lanes < 8             # drained back down


# ------------------------------------------------------ recompilation lock
def test_ladder_precompiles_once_per_width():
    """Construction pre-compiles every ladder width exactly once (on
    throwaway all-inactive chunks), and repeated grow/shrink cycles add
    ZERO new traces — resizing is recompilation-free."""
    eng = _engine(True)
    el = StreamScheduler(eng, chunk=4, min_lanes=1, max_lanes=4)
    assert sorted(el.trace_log) == [1, 2, 4]    # one trace per width
    n0 = len(el.trace_log)
    for cycle in range(3):
        for w in (4, 1, 2, 4, 2):
            el.request_width(w)
            for i in range(2):
                el.submit(f"c{cycle}w{w}s{i}", *_scene(i, 5))
            el.run()
    el.request_width(None)
    assert len(el.trace_log) == n0, (
        f"resizing retraced the chunk program: {el.trace_log}")


def test_lazy_compile_is_still_once_per_width():
    """precompile=False compiles lazily but still at most once per
    ladder width across arbitrarily many resizes."""
    eng = _engine(True)
    el = StreamScheduler(eng, chunk=4, min_lanes=1, max_lanes=4,
                         precompile=False)
    assert el.trace_log == []
    for w in (1, 4, 2, 1, 4, 2, 4, 1):
        el.request_width(w)
        el.submit(f"w{w}", *_scene(0, 5))
        el.run()
    assert len(el.trace_log) <= len(el.ladder)
    assert len(set(el.trace_log)) == len(el.trace_log)  # no width twice


# -------------------------------------------------- accounting regressions
def test_utilization_zero_before_any_dispatch():
    """utilization on a never-dispatched scheduler is 0.0, not a division
    error — fixed and elastic alike."""
    eng = _engine(False)
    assert StreamScheduler(eng, num_lanes=2).utilization == 0.0
    el = StreamScheduler(eng, min_lanes=1, max_lanes=2, precompile=False)
    assert el.utilization == 0.0
    assert el.lane_steps == 0 and el.frames_processed == 0


def test_lane_steps_use_the_width_active_at_each_chunk():
    """The utilization denominator must charge each chunk at the width it
    actually dispatched, not the construction width."""
    eng = _engine(False)
    el = StreamScheduler(eng, chunk=2, min_lanes=2, max_lanes=4,
                         precompile=False)
    for i in range(2):                       # phase A: width 2, saturated
        el.submit(f"a{i}", *_scene(i, 4))
    el.run()
    assert el.num_lanes == 2 and el.lane_steps == 8   # 4 steps x 2 lanes
    for i in range(4):                       # phase B: grows to 4
        el.submit(f"b{i}", *_scene(i, 4))
    el.run()
    assert el.num_lanes == 4
    # + 4 steps x 4 lanes; at the construction width it would be +8
    assert el.lane_steps == 8 + 16
    assert el.frames_processed == 2 * 4 + 4 * 4
    assert el.utilization == 1.0


def test_fifo_fairness_across_a_forced_shrink():
    """A pinned shrink re-queues admissions into the surviving lanes:
    admission order stays exactly submission order, admission steps stay
    monotone, and every sequence completes."""
    eng = _engine(True)
    el = StreamScheduler(eng, num_lanes=4, chunk=2, min_lanes=2,
                         max_lanes=4)
    ref = StreamScheduler(eng, num_lanes=4, chunk=2)
    seqs = [(f"f{i}", *_scene(i, 8 if i < 4 else 4)) for i in range(8)]
    got = []
    for name, db, dm in seqs[:4]:
        el.submit(name, db, dm)
        ref.submit(name, db, dm)
    got.extend(el._run_chunk())              # all four lanes occupied
    el.request_width(2)                      # evacuate lanes 2-3
    for name, db, dm in seqs[4:]:            # these must re-queue
        el.submit(name, db, dm)
        ref.submit(name, db, dm)
    while el.busy:
        got.extend(el._run_chunk())
    _assert_results_equal(got, ref.run())
    assert el.num_lanes == 2                 # shrink landed once drained
    admitted = [i for i, _ in el.admissions]
    steps = [s for _, s in el.admissions]
    assert admitted == list(range(8))        # FIFO, nothing skipped
    assert steps == sorted(steps)


# --------------------------------------------------- shrink-by-drain trace
def test_shrink_waits_for_evacuating_lanes_to_drain():
    """Hand-stepped shrink-drain protocol (the PR 4 lifecycle-audit
    pattern): a shrink pinned while lanes 2-3 still hold live sequences
    must hold the budget at 4 until both drain, then land exactly once;
    per-sequence outputs match the numpy oracle (so no uid ever aliases
    and no frame is lost), and the reorder buffer releases in submission
    order even though the evacuating lanes finish first."""
    eng = _engine(True, "hungarian")
    el = StreamScheduler(eng, num_lanes=4, chunk=2, min_lanes=2,
                         max_lanes=4)
    lengths = {"a": 12, "b": 12, "evac_c": 5, "evac_d": 7}
    seqs = [(n, *_scene(40 + i, f))
            for i, (n, f) in enumerate(lengths.items())]
    got = []
    for name, db, dm in seqs:
        el.submit(name, db, dm)
    got.extend(el._run_chunk())              # chunk 0: lanes 0..3 occupied
    el.request_width(2)
    widths = []
    while el.busy:
        got.extend(el._run_chunk())
        widths.append(el.num_lanes)
    # evac_c ends at step 5 (chunk 2), evac_d at step 7 (chunk 3): the
    # budget must hold at 4 through chunk 3 and drop at the chunk-4
    # boundary — exactly one resize, never mid-occupancy.
    assert widths[:3] == [4, 4, 4] and set(widths[3:]) == {2}, widths
    assert el.resizes == [(4, 4, 2)]
    # in-order release despite the evacuating lanes finishing first
    assert [t.name for t in got] == [n for n, _, _ in seqs]
    # numpy-oracle cross-check: identities and boxes per frame
    for (name, db, dm), tracks in zip(seqs, got):
        ref = RefSort(assoc="hungarian")
        for t in range(db.shape[0]):
            ref_rows = ref.update(db[t][dm[t]])
            em = tracks.emit[t]
            ids_ours = sorted(int(u) for u in tracks.uid[t][em])
            ids_ref = sorted(int(r[4]) for r in ref_rows)
            assert ids_ours == ids_ref, f"{name} frame {t}"
            boxes = {int(u): tracks.boxes[t, k]
                     for k, u in enumerate(tracks.uid[t]) if em[k]}
            for r in ref_rows:
                np.testing.assert_allclose(
                    boxes[int(r[4])], r[:4], rtol=1e-3, atol=0.5,
                    err_msg=f"{name} frame {t} uid {r[4]}")


# ------------------------------------------------------- migration (unit)
@pytest.mark.parametrize("use_kernels", [False, True])
def test_resize_round_trip_is_bit_exact(use_kernels):
    """grow -> shrink returns the original state bit for bit on both
    layouts, and grown lanes equal a fresh init (the masked re-init)."""
    eng = _engine(use_kernels)
    state = eng.init_ragged(3)
    db, dm = _scene(7, 6)
    frames = jnp.asarray(np.stack([db] * 3, axis=1))
    masks = jnp.asarray(np.stack([dm] * 3, axis=1))
    active = jnp.ones((3,), bool)
    for f in range(6):
        state, _ = eng.step_ragged(state, frames[f], masks[f], active)
    big = eng.resize_ragged(state, 3, 8)
    back = eng.resize_ragged(big, 8, 3)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    big_e = sort_state_of(big, 8) if use_kernels else big
    fresh = eng.init(8)
    for a, b in zip(jax.tree.leaves(big_e), jax.tree.leaves(fresh)):
        np.testing.assert_array_equal(np.asarray(a)[3:], np.asarray(b)[3:])


def test_resize_pool_and_streams_validation():
    pool = slots.init_pool((4,), 3)
    assert slots.resize_pool(pool, 4) is pool
    small = slots.resize_pool(pool, 2)
    assert small.alive.shape == (2, 3) and small.next_uid.shape == (2,)
    big = slots.resize_pool(pool, 6)
    assert bool((~np.asarray(big.alive[4:])).all())
    assert (np.asarray(big.uid[4:]) == -1).all()
    assert (np.asarray(big.next_uid[4:]) == 1).all()
    with pytest.raises(ValueError):
        slots.resize_pool(pool, 0)
    with pytest.raises(ValueError):
        resize_streams(_engine(False).init(2), 0)


def test_ladder_and_constructor_validation():
    assert lane_ladder(2, 16) == (2, 4, 8, 16)
    assert lane_ladder(3, 12) == (3, 6, 12)
    assert lane_ladder(4, 4) == (4,)
    with pytest.raises(ValueError, match="2\\*\\*k"):
        lane_ladder(2, 12)
    with pytest.raises(ValueError, match="min_lanes"):
        lane_ladder(0, 4)
    with pytest.raises(ValueError, match=">="):
        lane_ladder(8, 4)
    eng = _engine(False)
    with pytest.raises(ValueError, match="both"):
        StreamScheduler(eng, min_lanes=2)
    with pytest.raises(ValueError, match="ladder width"):
        StreamScheduler(eng, num_lanes=3, min_lanes=2, max_lanes=8)
    with pytest.raises(ValueError, match="num_lanes"):
        StreamScheduler(eng)
    fixed = StreamScheduler(eng, num_lanes=2)
    with pytest.raises(ValueError, match="elastic"):
        fixed.request_width(2)
    el = StreamScheduler(eng, min_lanes=2, max_lanes=4, precompile=False)
    with pytest.raises(ValueError, match="ladder"):
        el.request_width(3)


# ------------------------------------------------------------- mesh mode
def test_elastic_mesh_of_one_matches_fixed_unsharded():
    """The sharded elastic path with a single-device mesh equals the
    fixed max_lanes unsharded run — keeps the shard_map + migrate path
    exercised in every session."""
    eng = _engine(True)
    seqs = [(f"m{i}", *_scene(60 + i, f)) for i, f in enumerate([6, 3, 8, 2])]
    el = StreamScheduler(eng, chunk=4, mesh=lane_mesh(1),
                         min_lanes=1, max_lanes=4)
    ref = StreamScheduler(eng, num_lanes=4, chunk=4)
    out_el, out_ref = _churn(el, ref, seqs, widths=[4, 1])
    _assert_results_equal(out_el, out_ref)
    assert len(el.resizes) > 0


@needs_multi
@pytest.mark.parametrize("use_kernels", [False, True])
def test_elastic_sharded_bit_identical_to_fixed_max(use_kernels):
    """Elastic over a 4-device ("lanes",) mesh: churn + forced resizes
    equal the fixed max_lanes unsharded run bit for bit — migration
    crosses shard boundaries (lanes redistribute over devices at every
    width change) without perturbing a single lane."""
    eng = _engine(use_kernels)
    seqs = [(f"s{i}", *_scene(80 + i, f))
            for i, f in enumerate([12, 5, 9, 5, 1, 7, 3, 10])]
    el = StreamScheduler(eng, chunk=4, mesh=lane_mesh(4),
                         min_lanes=4, max_lanes=16)
    ref = StreamScheduler(eng, num_lanes=16, chunk=4)
    out_el, out_ref = _churn(el, ref, seqs, widths=[16, 4, 8])
    _assert_results_equal(out_el, out_ref)
    assert len(el.resizes) > 0


@needs_multi
def test_migrated_state_stays_lane_sharded():
    """After a resize the resident state is already placed with the new
    width's NamedSharding — no leaf collapses to a replicated or
    single-device layout, so no chunk pays a resharding copy."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.sharding import state_pspecs

    eng = _engine(True)
    el = StreamScheduler(eng, chunk=4, mesh=lane_mesh(4),
                         min_lanes=4, max_lanes=8)
    for i, f in enumerate([9, 4, 7, 6, 5, 8]):
        el.submit(f"r{i}", *_scene(90 + i, f))
    el.run()
    assert len(el.resizes) > 0
    specs = state_pspecs(el._state)
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    for leaf, spec in zip(jax.tree.leaves(el._state), spec_leaves):
        assert isinstance(leaf.sharding, NamedSharding), leaf.shape
        if leaf.size == 0:
            # the embed block of a cost without one holds no data to shard;
            # it is placed replicated, as XLA returns it from every chunk
            # (test_device_sharding.py::
            # test_state_stays_lane_sharded_across_chunks)
            assert leaf.shape == (0, eng.config.max_trackers,
                                  el._state.frame_count.shape[0])
            continue
        assert leaf.sharding.spec == spec, (leaf.shape, leaf.sharding.spec)


@needs_multi
def test_every_ladder_width_must_divide_the_mesh():
    with pytest.raises(ValueError, match="divide"):
        StreamScheduler(_engine(True), mesh=lane_mesh(4),
                        min_lanes=2, max_lanes=8)


# ------------------------------------------------------- property coverage
@pytest.mark.slow
@pytest.mark.parametrize("use_kernels,assoc", PATHS)
@settings(max_examples=5, deadline=None, derandomize=True)
@given(lengths=st.lists(st.sampled_from([1, 4, 9, 12]), min_size=1,
                        max_size=8),
       widths=st.lists(st.sampled_from([1, 2, 4]), min_size=1, max_size=4))
def test_elastic_equivalence_property(use_kernels, assoc, lengths, widths):
    """Any ragged admission/drain churn with any forced-resize pattern
    stays bit-identical to the fixed max_lanes scheduler, on every
    engine path x assoc mode (schedulers are reused across examples so
    the ladder compiles once per combination)."""
    key = ("prop", use_kernels, assoc)
    if key not in _ENGINES:
        eng = _engine(use_kernels, assoc)
        _ENGINES[key] = (
            StreamScheduler(eng, chunk=4, min_lanes=1, max_lanes=4),
            StreamScheduler(eng, num_lanes=4, chunk=4))
    el, ref = _ENGINES[key]
    seqs = [(f"p{i}", *_scene(20 + i, f)) for i, f in enumerate(lengths)]
    out_el, out_ref = _churn(el, ref, seqs, widths)
    _assert_results_equal(out_el, out_ref)
