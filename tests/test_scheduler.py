"""Online multi-stream scheduler: ragged lane recycling (DESIGN.md §3).

The load-bearing invariant: a sequence multiplexed through recycled lanes
emits tracks **bit-identical** to running it alone — on both engine paths.
Plus: FIFO admission-order fairness, in-order drain at shutdown, reuse
after drain, and degenerate sequences (single-frame, empty).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import SortConfig, SortEngine
from repro.data.synthetic import SceneConfig, generate_scene
from repro.serve import StreamScheduler

# one detection budget for every test so jit caches are shared
MAX_DETS = 7
_SOLO: dict = {}


def _scene(seed, frames):
    _, _, db, dm = generate_scene(
        SceneConfig(num_frames=frames, max_objects=4, seed=seed))
    d = db.shape[1]
    assert d <= MAX_DETS, d
    return (np.pad(db, ((0, 0), (0, MAX_DETS - d), (0, 0))),
            np.pad(dm, ((0, 0), (0, MAX_DETS - d))))


def _engine(use_kernels, chunk_kernel=False):
    return SortEngine(SortConfig(max_trackers=8, max_detections=MAX_DETS,
                                 use_kernels=use_kernels,
                                 chunk_kernel=chunk_kernel))


def _solo_run(eng, db, dm):
    key = (db.shape[0], eng.config.use_kernels)
    if key not in _SOLO:
        _SOLO[key] = jax.jit(eng.run)
    _, out = _SOLO[key](eng.init(1), jnp.asarray(db)[:, None],
                        jnp.asarray(dm)[:, None])
    return out


def _assert_tracks_equal_solo(tracks, solo, ctx=""):
    np.testing.assert_array_equal(tracks.uid, np.asarray(solo.uid[:, 0]),
                                  err_msg=f"uid {ctx}")
    np.testing.assert_array_equal(tracks.emit, np.asarray(solo.emit[:, 0]),
                                  err_msg=f"emit {ctx}")
    np.testing.assert_array_equal(tracks.boxes, np.asarray(solo.boxes[:, 0]),
                                  err_msg=f"boxes {ctx}")


# ------------------------------------------------------ recycling exactness
@pytest.mark.parametrize("use_kernels", [False, True])
def test_ragged_mix_bit_identical_to_solo_runs(use_kernels):
    """Six ragged sequences through a 3-lane scheduler (lanes recycled
    mid-run) emit tracks bit-identical to per-sequence solo runs."""
    lengths = [12, 5, 9, 5, 12, 1]
    seqs = [(f"s{i}", *_scene(i, f)) for i, f in enumerate(lengths)]
    eng = _engine(use_kernels)
    sched = StreamScheduler(eng, num_lanes=3, chunk=4)
    for name, db, dm in seqs:
        sched.submit(name, db, dm)
    results = sched.run()
    assert [r.name for r in results] == [s[0] for s in seqs]
    assert not sched.busy
    for (name, db, dm), tracks in zip(seqs, results):
        assert tracks.num_frames == db.shape[0]
        _assert_tracks_equal_solo(tracks, _solo_run(eng, db, dm),
                                  f"{name} uk={use_kernels}")


@pytest.mark.parametrize("use_kernels", [False, True])
def test_lane_budget_smaller_than_traffic(use_kernels):
    """More waiting sequences than lanes: a single lane serializes five
    sequences through the same recycled slot, still bit-exact."""
    lengths = [5, 9, 1, 12, 5]
    seqs = [(f"q{i}", *_scene(10 + i, f)) for i, f in enumerate(lengths)]
    eng = _engine(use_kernels)
    sched = StreamScheduler(eng, num_lanes=1, chunk=5)
    for name, db, dm in seqs:
        sched.submit(name, db, dm)
    results = sched.run()
    assert [r.name for r in results] == [s[0] for s in seqs]
    for (name, db, dm), tracks in zip(seqs, results):
        _assert_tracks_equal_solo(tracks, _solo_run(eng, db, dm),
                                  f"{name} uk={use_kernels}")


# ------------------------------------------------------- admission fairness
def test_admission_order_is_fifo():
    """Lanes admit strictly in submission order, and admission steps are
    monotone: a later submission never jumps an earlier one."""
    lengths = [6, 6, 2, 2, 2, 2]
    eng = _engine(True)
    sched = StreamScheduler(eng, num_lanes=2, chunk=4)
    for i, f in enumerate(lengths):
        sched.submit(f"a{i}", *_scene(i, f))
    sched.run()
    admitted = [idx for idx, _ in sched.admissions]
    steps = [step for _, step in sched.admissions]
    assert admitted == list(range(len(lengths)))
    assert steps == sorted(steps)
    # first two sequences go straight into the two free lanes at step 0
    assert steps[:2] == [0, 0]


def test_recycle_admits_in_the_freed_step():
    """A lane freed at step t admits the next sequence at step t+1 — the
    masked re-init and the new sequence's first frame share that step (no
    idle step between back-to-back sequences on one lane)."""
    eng = _engine(True)
    sched = StreamScheduler(eng, num_lanes=1, chunk=8)
    sched.submit("first", *_scene(0, 5))
    sched.submit("second", *_scene(1, 3))
    sched.run()
    assert sched.admissions == [(0, 0), (1, 5)]


# ------------------------------------------------------------------- drain
def test_drain_emits_in_submission_order():
    """A short sequence submitted after a long one *finishes* first but is
    *released* second: drain order is submission order."""
    eng = _engine(True)
    long = _scene(3, 14)
    short = _scene(4, 2)
    sched = StreamScheduler(eng, num_lanes=2, chunk=4)
    sched.submit("long", *long)
    sched.submit("short", *short)
    results = sched.run()
    assert [r.name for r in results] == ["long", "short"]
    _assert_tracks_equal_solo(results[0], _solo_run(eng, *long), "long")
    _assert_tracks_equal_solo(results[1], _solo_run(eng, *short), "short")


def test_scheduler_reusable_after_drain():
    """submit() after run() keeps working; recycled lanes start every new
    admission from a masked re-init, so earlier traffic cannot leak."""
    eng = _engine(True)
    db, dm = _scene(5, 9)
    sched = StreamScheduler(eng, num_lanes=2, chunk=4)
    sched.submit("warm", *_scene(6, 12))
    sched.run()
    sched.submit("later", db, dm)
    (tracks,) = sched.run()
    _assert_tracks_equal_solo(tracks, _solo_run(eng, db, dm), "later")


def test_empty_and_single_frame_sequences():
    eng = _engine(True)
    sched = StreamScheduler(eng, num_lanes=2, chunk=4)
    db1, dm1 = _scene(7, 1)
    sched.submit("empty", np.zeros((0, MAX_DETS, 4), np.float32),
                 np.zeros((0, MAX_DETS), bool))
    sched.submit("one", db1, dm1)
    results = sched.run()
    assert [r.name for r in results] == ["empty", "one"]
    assert results[0].num_frames == 0 and results[0].emit.shape[1] == 8
    _assert_tracks_equal_solo(results[1], _solo_run(eng, db1, dm1), "one")


def test_empty_run_returns_nothing():
    sched = StreamScheduler(_engine(True), num_lanes=2, chunk=4)
    assert sched.run() == []
    assert not sched.busy


def test_rejects_oversized_detection_rows():
    sched = StreamScheduler(_engine(True), num_lanes=1)
    with pytest.raises(ValueError):
        sched.submit("big", np.zeros((3, MAX_DETS + 1, 4), np.float32),
                     np.zeros((3, MAX_DETS + 1), bool))


# ------------------------------------------------------- property coverage
@pytest.mark.slow
@settings(max_examples=6, deadline=None, derandomize=True)
@given(lengths=st.lists(st.sampled_from([1, 5, 9, 12]), min_size=1,
                        max_size=7),
       num_lanes=st.integers(1, 3))
def test_scheduler_exactness_property(lengths, num_lanes):
    """Any ragged length mix over any lane budget stays bit-identical to
    solo runs (fused path; lengths drawn from a fixed set so hypothesis
    examples share the solo-run jit cache)."""
    seqs = [(f"p{i}", *_scene(20 + i, f)) for i, f in enumerate(lengths)]
    eng = _engine(True)
    sched = StreamScheduler(eng, num_lanes=num_lanes, chunk=4)
    for name, db, dm in seqs:
        sched.submit(name, db, dm)
    results = sched.run()
    assert [r.name for r in results] == [s[0] for s in seqs]
    for (name, db, dm), tracks in zip(seqs, results):
        _assert_tracks_equal_solo(tracks, _solo_run(eng, db, dm), name)


# ------------------------------------------------- stranded-result draining
def test_zero_frame_sequence_is_not_stranded():
    """Regression: a zero-frame sequence submitted while the scheduler is
    idle finalizes straight into the reorder buffer, but `busy` ignored
    buffered results and results only popped inside the chunk path — the
    documented `while sched.busy` drain loop never surfaced it."""
    sched = StreamScheduler(_engine(True), num_lanes=2, chunk=4)
    sched.submit("empty", np.zeros((0, MAX_DETS, 4), np.float32),
                 np.zeros((0, MAX_DETS), bool))
    assert sched.busy                       # was False before the fix
    got = sched.pop_ready()                 # no dispatch required
    assert [t.name for t in got] == ["empty"]
    assert got[0].num_frames == 0
    assert not sched.busy
    assert sched.chunks_run == 0            # nothing was ever dispatched


def test_drain_releases_buffered_results_without_empty_chunk():
    """drain() surfaces buffered zero-frame results alongside real work,
    in submission order, and never dispatches an empty chunk for them."""
    eng = _engine(True)
    db, dm = _scene(8, 5)
    sched = StreamScheduler(eng, num_lanes=2, chunk=4)
    sched.submit("empty0", np.zeros((0, MAX_DETS, 4), np.float32),
                 np.zeros((0, MAX_DETS), bool))
    sched.submit("real", db, dm)
    results = sched.drain()
    assert [t.name for t in results] == ["empty0", "real"]
    _assert_tracks_equal_solo(results[1], _solo_run(eng, db, dm), "real")
    chunks_for_real = sched.chunks_run
    # drain again with only a buffered result: no new chunk may run
    sched.submit("empty1", np.zeros((0, MAX_DETS, 4), np.float32),
                 np.zeros((0, MAX_DETS), bool))
    (only,) = sched.drain()
    assert only.name == "empty1"
    assert sched.chunks_run == chunks_for_real
    assert not sched.busy


# ------------------------------------------------------------- uid headroom
@pytest.mark.parametrize("use_kernels", [False, True])
def test_uid_guard_trips_before_int32_overflow(use_kernels):
    """A lane whose uid counter crosses slots.UID_LIMIT mid-sequence must
    fail loudly (silent int32 wraparound could alias live track ids)."""
    from repro.core import slots

    eng = _engine(use_kernels)
    sched = StreamScheduler(eng, num_lanes=1, chunk=4)
    sched.submit("monster", *_scene(30, 8))
    sched._run_chunk()                       # first 4 frames, uids live
    st = sched._state
    sched._state = st._replace(pool=st.pool._replace(
        next_uid=jnp.full_like(st.pool.next_uid, slots.UID_LIMIT + 1)))
    with pytest.raises(RuntimeError, match="uid counter"):
        sched.run()


@pytest.mark.parametrize("use_kernels", [False, True])
def test_recycled_lane_never_reuses_a_live_uid(use_kernels):
    """Lane recycling resets the uid namespace: after reset_ragged the
    recycled lane holds no live uid and its counter restarts at
    uid_start, while the other lane's uids and counter are untouched —
    so a new sequence's ids can never collide with live trackers."""
    from repro.core import sort as sort_mod

    eng = _engine(use_kernels)
    state = eng.init_ragged(2)
    db, dm = _scene(31, 6)
    both = jnp.asarray(np.stack([db, db], axis=1))
    masks = jnp.asarray(np.stack([dm, dm], axis=1))
    active = jnp.ones((2,), bool)
    for f in range(6):                       # populate live uids on both
        state, _ = eng.step_ragged(state, both[f], masks[f], active)
    pool_before = jax.device_get(state.pool)
    reset = jnp.asarray(np.array([True, False]))
    state = sort_mod.reset_ragged(state, reset)
    pool = jax.device_get(state.pool)
    uid = pool.uid if not use_kernels else pool.uid.T      # -> [lanes, T]
    uid_before = (pool_before.uid if not use_kernels
                  else pool_before.uid.T)
    assert (uid_before[0] >= 1).any()        # lane 0 really had live uids
    assert (uid[0] == -1).all()              # ...all cleared by the reset
    assert int(pool.next_uid[0]) == 1        # fresh namespace
    np.testing.assert_array_equal(uid[1], uid_before[1])   # lane 1 intact
    assert int(pool.next_uid[1]) == int(pool_before.next_uid[1])


# ------------------------------------------- chunk-kernel dispatch mode
def test_chunk_kernel_results_and_accounting_match_per_frame_mode():
    """The megakernel dispatch mode (DESIGN.md §9) is invisible to the
    scheduler: same traffic through chunk_kernel=True and =False yields
    bit-identical tracks AND an identical accounting tuple (frames,
    lane-steps, chunks, utilization, admission schedule).  The mix forces
    a ragged tail chunk (lengths not divisible by chunk=7) and mid-chunk
    lane recycles."""
    lengths = [12, 5, 9, 3]
    seqs = [(f"ck{i}", *_scene(40 + i, f)) for i, f in enumerate(lengths)]
    accounting = {}
    results = {}
    for chunk_kernel in (False, True):
        sched = StreamScheduler(_engine(True, chunk_kernel=chunk_kernel),
                                num_lanes=2, chunk=7)
        for name, db, dm in seqs:
            sched.submit(name, db, dm)
        results[chunk_kernel] = sched.run()
        accounting[chunk_kernel] = (sched.frames_processed,
                                    sched.lane_steps, sched.chunks_run,
                                    sched.utilization,
                                    list(sched.admissions))
    assert accounting[False] == accounting[True]
    for ra, rb in zip(results[False], results[True]):
        assert ra.name == rb.name
        np.testing.assert_array_equal(ra.uid, rb.uid, err_msg=ra.name)
        np.testing.assert_array_equal(ra.emit, rb.emit, err_msg=ra.name)
        np.testing.assert_array_equal(ra.boxes, rb.boxes, err_msg=ra.name)
    # and both modes stay bit-identical to per-sequence solo runs
    eng = _engine(True)
    for (name, db, dm), tracks in zip(seqs, results[True]):
        _assert_tracks_equal_solo(tracks, _solo_run(eng, db, dm),
                                  f"{name} (megakernel)")


# --------------------------------------------------- utilization accounting
def test_lane_steps_exclude_fully_idle_drain_tail():
    """Regression: the utilization denominator used to count the
    fully-idle tail steps of a draining chunk (`chunk * num_lanes` per
    chunk); it must come from the planned `active` mask instead."""
    eng = _engine(False)
    sched = StreamScheduler(eng, num_lanes=2, chunk=8)
    db, dm = _scene(0, frames=3)
    sched.submit("only", db, dm)
    (tracks,) = sched.run()
    assert tracks.boxes.shape[0] == 3
    assert sched.frames_processed == 3
    # one chunk ran; only its first 3 steps carried any work
    assert sched.chunks_run == 1
    assert sched.lane_steps == 3 * 2          # not 8 * 2
    assert sched.utilization == pytest.approx(3 / 6)


def test_utilization_full_when_lanes_saturated():
    """Two equal-length sequences on two lanes: every working step is
    fully occupied, so utilization is exactly 1."""
    eng = _engine(False)
    sched = StreamScheduler(eng, num_lanes=2, chunk=4)
    for i in range(2):
        db, dm = _scene(i, frames=8)
        sched.submit(f"s{i}", db, dm)
    sched.run()
    assert sched.frames_processed == 16
    assert sched.lane_steps == 16
    assert sched.utilization == 1.0


# --------------------------------------------- checkpoint/restore hooks
@pytest.mark.parametrize("use_kernels", [False, True])
def test_export_import_midrun_roundtrip(use_kernels):
    """export_state at a chunk boundary, import into a FRESH scheduler,
    continue: the combined output stream equals an uninterrupted run and
    every sequence stays bit-identical to its solo run (DESIGN.md §11)."""
    eng = _engine(use_kernels)
    seqs = [(f"s{i}", *_scene(i, frames=f))
            for i, f in enumerate([17, 30, 9, 23])]

    sched = StreamScheduler(eng, num_lanes=2, chunk=8)
    for name, db, dm in seqs:
        sched.submit(name, db, dm)
    results = []
    for _ in range(2):
        results.extend(sched.run_chunk())
    meta, arrays = sched.export_state()
    import json
    json.dumps(meta)                    # the meta half must be JSON-able

    fresh = StreamScheduler(_engine(use_kernels), num_lanes=2, chunk=8)
    fresh.import_state(meta, arrays)
    assert fresh.chunks_run == sched.chunks_run
    while fresh.busy:
        results.extend(fresh.run_chunk())
    assert [t.name for t in results] == [n for n, _, _ in seqs]
    for (name, db, dm), tracks in zip(seqs, results):
        _assert_tracks_equal_solo(tracks, _solo_run(eng, db, dm), name)


def test_export_import_preserves_held_reorder_results():
    """A finished-but-unreleased completion (parked above the reorder
    watermark) must cross the checkpoint and release in order."""
    eng = _engine(False)
    sched = StreamScheduler(eng, num_lanes=2, chunk=8)
    long = _scene(0, frames=30)
    short = _scene(1, frames=4)
    sched.submit("long", *long)
    sched.submit("short", *short)       # finishes first, held for "long"
    out = sched.run_chunk()
    assert out == [] and len(sched._ready) == 1
    meta, arrays = sched.export_state()
    fresh = StreamScheduler(_engine(False), num_lanes=2, chunk=8)
    fresh.import_state(meta, arrays)
    results = []
    while fresh.busy:
        results.extend(fresh.run_chunk())
    assert [t.name for t in results] == ["long", "short"]
    _assert_tracks_equal_solo(results[1], _solo_run(eng, *short), "short")


def test_import_rejects_mismatched_engine_and_width():
    eng = _engine(False)
    sched = StreamScheduler(eng, num_lanes=2, chunk=8)
    db, dm = _scene(0, frames=6)
    sched.submit("s", db, dm)
    sched.run_chunk()
    meta, arrays = sched.export_state()

    other = SortEngine(SortConfig(max_trackers=8, max_detections=MAX_DETS,
                                  iou_threshold=0.5))
    with pytest.raises(ValueError, match="engine config"):
        StreamScheduler(other, num_lanes=2, chunk=8).import_state(
            meta, arrays)
    with pytest.raises(ValueError, match="ladder"):
        StreamScheduler(_engine(False), num_lanes=4, chunk=8).import_state(
            meta, arrays)
    with pytest.raises(ValueError, match="schema"):
        StreamScheduler(_engine(False), num_lanes=2, chunk=8).import_state(
            {**meta, "schema": 99}, arrays)
    lane_key = next(k for k in arrays if k.startswith("lane/"))
    broken = {k: v for k, v in arrays.items() if k != lane_key}
    with pytest.raises(ValueError, match="missing device-state"):
        StreamScheduler(_engine(False), num_lanes=2, chunk=8).import_state(
            meta, broken)


# ----------------------------------------------- segment planner parity
def _lane_step_plan(sched):
    """The planner as it was before segments, kept as the oracle: one
    pass over every (step, lane), step-major, admitting FIFO into each
    free lane below the admission limit and copying one frame a
    lane-step.  Returns the operands and the ``(t, lane, seq index,
    frame)`` of every lane-step that carries a frame."""
    c, l, d = sched.chunk, sched.num_lanes, sched.max_dets
    admit_limit = (l if sched._shrink_target is None
                   else sched._shrink_target)
    det = np.zeros((c, l, d, 4), np.float32)
    dm = np.zeros((c, l, d), bool)
    active = np.zeros((c, l), bool)
    reset = np.zeros((c, l), bool)
    extras = sched._zero_extras(c, l, d)
    it = iter(extras)
    dc = next(it) if sched._need_class else None
    de = next(it) if sched._need_embed else None
    steps = []
    for t in range(c):
        for lane in range(l):
            if sched._occupant[lane] is None and sched._pending \
                    and lane < admit_limit:
                sched._occupant[lane] = sched._pending.popleft()
                sched._cursor[lane] = 0
                reset[t, lane] = True
                sched.admissions.append((sched._occupant[lane].index,
                                         sched.chunks_run * c + t))
            seq = sched._occupant[lane]
            if seq is None:
                continue
            k = sched._cursor[lane]
            det[t, lane] = seq.det_boxes[k]
            dm[t, lane] = seq.det_mask[k]
            if dc is not None:
                dc[t, lane] = seq.det_class[k]
            if de is not None:
                de[t, lane] = seq.det_embed[k]
            active[t, lane] = True
            steps.append((t, lane, seq.index, k))
            sched._cursor[lane] = k + 1
            if k + 1 == seq.length:
                sched._occupant[lane] = None
    return det, dm, active, reset, extras, steps


EMBED = 4


def _plan_engine(multiclass):
    from repro.core import cost as cost_mod
    extra = (dict(num_classes=3, cost=cost_mod.iou_embed(EMBED))
             if multiclass else {})
    return SortEngine(SortConfig(max_trackers=8, max_detections=MAX_DETS,
                                 **extra))


def _random_submission(rng, frames, multiclass):
    d = int(rng.integers(1, MAX_DETS + 1))        # padded by submit
    xy = rng.uniform(0, 200, (frames, d, 2)).astype(np.float32)
    kw = {}
    if multiclass:
        kw = dict(det_class=rng.integers(0, 3, (frames, d)),
                  det_embed=rng.random((frames, d, EMBED)).astype(
                      np.float32))
    return (np.concatenate([xy, xy + 20], -1), rng.random((frames, d)) < 0.6,
            kw)


@pytest.mark.parametrize("multiclass", [False, True],
                         ids=["single_class", "class_embed"])
@pytest.mark.parametrize("case", ["ragged", "one_lane", "elastic_shrink"])
def test_segment_plan_matches_lane_step_oracle(case, multiclass):
    """The segment planner writes byte-identical operands, admissions and
    lane bookkeeping to the lane-step walk, chunk after chunk: seeded
    ragged arrivals of 0 to 3 x chunk frames, a queue longer than the
    lanes, and (elastic) a pinned shrink that evacuates while the queue
    keeps admitting into the surviving lanes."""
    chunk = 6
    rng = np.random.default_rng(["ragged", "one_lane",
                                 "elastic_shrink"].index(case))

    def make():
        eng = _plan_engine(multiclass)
        if case == "elastic_shrink":
            return StreamScheduler(eng, min_lanes=2, max_lanes=8,
                                   num_lanes=8, chunk=chunk,
                                   precompile=False)
        return StreamScheduler(eng, num_lanes=1 if case == "one_lane" else 5,
                               chunk=chunk)

    seg, ref = make(), make()
    evacuated_while_queued = False
    for n in range(14):
        for _ in range(int(rng.integers(0, 5)) if n < 10 else 0):
            db, dm, kw = _random_submission(
                rng, int(rng.integers(0, 3 * chunk + 1)), multiclass)
            for s in (seg, ref):
                s.submit(f"r{s._num_submitted}", db, dm, **kw)
        if case == "elastic_shrink" and n == 3:
            for s in (seg, ref):
                s.request_width(2)
        for s in (seg, ref):
            s._maybe_resize()
        assert seg.num_lanes == ref.num_lanes
        evacuated_while_queued |= (seg._shrink_target is not None
                                   and bool(seg._pending))
        *got, segments = seg._plan_chunk()
        *want, steps = _lane_step_plan(ref)
        for a, b in zip(got[:4] + list(got[4]), want[:4] + list(want[4])):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes(), f"chunk {n}"
        assert len(got[4]) == len(want[4]) == len(seg._extra_ndims)
        assert sorted((t0 + i, lane, s.index, k0 + i)
                      for t0, m, lane, s, k0 in segments
                      for i in range(m)) == sorted(steps)
        assert seg.admissions == ref.admissions
        assert seg._cursor == ref._cursor
        assert ([o and o.index for o in seg._occupant]
                == [o and o.index for o in ref._occupant])
        assert ([p.index for p in seg._pending]
                == [p.index for p in ref._pending])
        seg.chunks_run += 1
        ref.chunks_run += 1
    assert any(step % chunk for _, step in seg.admissions)  # mid-chunk
    assert len(seg.admissions) > seg.ladder[-1]             # queue waited
    if case == "elastic_shrink":
        assert evacuated_while_queued
        assert seg.num_lanes == 2 and seg.resizes[-1][1:] == (8, 2)


# ----------------------------------------- checkpoints and segment counter
def test_export_mid_sequence_holds_exactly_the_filled_frames():
    """A snapshot taken mid-sequence carries the frames filled so far and
    no more (queued sequences none), equal to the solo run's first
    frames; the import continues bit-exactly."""
    eng = _engine(True)
    seqs = [(f"s{i}", *_scene(i, frames=f))
            for i, f in enumerate([17, 30, 9, 23])]
    sched = StreamScheduler(eng, num_lanes=2, chunk=8)
    for name, db, dm in seqs:
        sched.submit(name, db, dm)
    results = []
    for _ in range(2):
        results.extend(sched.run_chunk())
    meta, arrays = sched.export_state()
    filled = {i: c for i, c in zip(meta["occupant"], meta["cursor"])
              if i is not None}
    assert filled == {0: 16, 1: 16}
    for i in meta["pending"]:
        filled[i] = 0
    assert sorted(filled) == [0, 1, 2, 3]
    for i, k in filled.items():
        solo = _solo_run(eng, *seqs[i][1:])
        for name, full in (("boxes", solo.boxes), ("uid", solo.uid),
                           ("emit", solo.emit)):
            got = arrays[f"seq/{i}/out_{name}"]
            assert got.shape[0] == k
            np.testing.assert_array_equal(got, np.asarray(full[:k, 0]))

    fresh = StreamScheduler(_engine(True), num_lanes=2, chunk=8)
    fresh.import_state(meta, arrays)
    while fresh.busy:
        results.extend(fresh.run_chunk())
    assert [t.name for t in results] == [n for n, _, _ in seqs]
    for (name, db, dm), tracks in zip(seqs, results):
        _assert_tracks_equal_solo(tracks, _solo_run(eng, db, dm), name)


def test_import_snapshot_of_stacked_rows_without_segment_counter():
    """A snapshot in the earlier format — each sequence's outputs stacked
    from a list of per-frame rows, no ``segments_planned`` counter —
    still imports and continues bit-exactly."""
    eng = _engine(True)
    seqs = [(f"s{i}", *_scene(i, frames=f))
            for i, f in enumerate([17, 30, 9, 23])]
    sched = StreamScheduler(eng, num_lanes=2, chunk=8)
    for name, db, dm in seqs:
        sched.submit(name, db, dm)
    results = []
    for _ in range(2):
        results.extend(sched.run_chunk())
    meta, arrays = sched.export_state()
    del meta["counters"]["segments_planned"]
    t = eng.config.max_trackers
    empty = {"out_boxes": ((0, t, 4), np.float32),
             "out_uid": ((0, t), np.int32), "out_emit": ((0, t), bool)}
    older = dict(arrays)
    for key, a in arrays.items():
        name = key.rsplit("/", 1)[-1]
        if key.startswith("seq/") and name in empty:
            rows = [np.array(r) for r in a]
            older[key] = (np.stack(rows) if rows
                          else np.zeros(*empty[name]))
    fresh = StreamScheduler(_engine(True), num_lanes=2, chunk=8)
    fresh.import_state(meta, older)
    assert fresh.segments_planned == 0
    while fresh.busy:
        results.extend(fresh.run_chunk())
    assert [r.name for r in results] == [n for n, _, _ in seqs]
    for (name, db, dm), tracks in zip(seqs, results):
        _assert_tracks_equal_solo(tracks, _solo_run(eng, db, dm), name)


def test_segments_planned_counts_lane_runs_and_round_trips():
    """On a hand-built schedule ``segments_planned`` is the number of
    (lane, sequence) runs of each chunk, and it crosses a checkpoint.

    Two lanes, chunk 4, lengths 6, 3, 2, 5: chunk 0 runs s0 on lane 0,
    s1 then s2 (admitted at step 3) on lane 1; chunk 1 runs s0 on lane 0,
    s2 then s3 (admitted at step 5) on lane 1; chunk 2 runs s3."""
    eng = _engine(True)
    lengths = [6, 3, 2, 5]
    seqs = [(f"h{i}", *_scene(50 + i, f)) for i, f in enumerate(lengths)]
    sched = StreamScheduler(eng, num_lanes=2, chunk=4)
    for name, db, dm in seqs:
        sched.submit(name, db, dm)
    counts, results = [], []
    for _ in range(2):
        results.extend(sched.run_chunk())
        counts.append(sched.segments_planned)
    assert counts == [3, 6]
    meta, arrays = sched.export_state()
    assert meta["counters"]["segments_planned"] == 6
    again = StreamScheduler(_engine(True), num_lanes=2, chunk=4)
    again.import_state(meta, arrays)
    assert again.segments_planned == 6
    results.extend(again.drain())
    assert again.segments_planned == 7
    assert again.admissions == [(0, 0), (1, 0), (2, 3), (3, 5)]
    assert again.frames_processed == sum(lengths)
    assert [r.name for r in results] == ["h0", "h1", "h2", "h3"]
    for (name, db, dm), tracks in zip(seqs, results):
        _assert_tracks_equal_solo(tracks, _solo_run(eng, db, dm), name)
