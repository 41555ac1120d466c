"""Online multi-stream scheduler — ragged-length lane recycling.

The paper parallelizes throughput by giving each OpenMP worker one video
file (§VI); its TPU analogue (``SortEngine.run``) scans a *fixed* batch of
equal-length streams.  Real serving traffic is neither fixed nor
equal-length: sequences arrive over time with lengths spanning an order of
magnitude (paper Table I: 71–1000 frames), so a pad-to-max batch wastes
most of its lane-steps on padding and a re-batch-per-departure recompiles
constantly.

This scheduler multiplexes an unbounded queue of ragged sequences onto a
fixed budget of ``num_lanes`` engine lanes (DESIGN.md §3):

* **Admission** is FIFO: the moment a lane's sequence ends, the lane is
  recycled — masked re-init (``core.sort.reset_ragged``) plus the new
  sequence's first frame execute in the *same* fused step.
* **Ragged stepping**: every step runs ``SortEngine.step_ragged`` with a
  per-lane ``active`` mask, so lanes between sequences are exact no-ops
  inside the single dispatch — membership churns every frame with no
  re-dispatch and no recompilation.
* **Chunked execution**: the host plans ``chunk`` steps at a time (the
  admission schedule is data-independent, so it can be planned ahead) and
  runs them as one jitted ``lax.scan`` — one host round-trip per chunk,
  not per frame.
* **Drain**: finished sequences are emitted **in submission order** via
  :class:`repro.data.stream.ReorderBuffer`; each carries its dense track
  stream (:class:`repro.data.stream.SequenceTracks`), bit-identical to a
  solo run of that sequence (the lane-recycling invariant, locked down by
  ``tests/test_scheduler.py``).
* **Device sharding** (DESIGN.md §7): pass ``mesh=`` (a 1-D ``("lanes",)``
  mesh from :func:`repro.sharding.lane_mesh`) and the lane axis is split
  contiguously over the mesh's devices — each device scans its own lane
  shard with the same single fused dispatch per step, zero collectives,
  and bit-identical outputs (``tests/test_device_sharding.py``).  Host-
  side planning is unchanged; chunk operands are placed with
  ``NamedSharding`` so the jitted scan never inserts a resharding copy.
* **Elastic lane budgets** (DESIGN.md §8): pass ``min_lanes``/``max_lanes``
  and the budget resizes itself between chunks over a pre-compiled ladder
  of power-of-two widths — grow is immediate (appended lanes are a masked
  re-init), shrink waits for the evacuating lanes to drain, and migrated
  lanes (including lanes mid-sequence) survive the move bit for bit, so
  an elastic run's per-sequence outputs equal a fixed ``max_lanes`` run
  (``tests/test_autoscale.py``).
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import slots
from repro.core.sort import SortEngine, lane_state_of, sort_state_of
from repro.data.stream import ReorderBuffer, SequenceTracks

# Profiler spans (``jax.profiler.TraceAnnotation``) of one dispatched
# chunk, in the order it runs them; each carries the stats ``chunk=<n>``,
# the chunk's number (``chunks_run`` before it), and ``shards=<n>``, the
# devices of the lane mesh (1 without one).  In mesh mode ``sched.stage``
# holds ``sharding/lanes.py``'s ``lanes.place``.
SPANS = ("sched.plan", "sched.stage", "sched.fetch", "sched.unpack",
         "sched.release")


def lane_ladder(min_lanes: int, max_lanes: int) -> tuple[int, ...]:
    """The pre-compiled width ladder (DESIGN.md §8): power-of-two
    multiples of ``min_lanes`` up to ``max_lanes``.

    Every resize lands on a ladder width, so the chunk scan compiles at
    most once per width and never again — ``max_lanes`` must therefore be
    ``min_lanes * 2**k`` exactly (a width off the ladder would force a
    fresh compile at resize time, the thing the ladder exists to avoid).
    """
    if min_lanes < 1:
        raise ValueError(f"min_lanes must be >= 1, got {min_lanes}")
    if max_lanes < min_lanes:
        raise ValueError(f"max_lanes={max_lanes} must be >= "
                         f"min_lanes={min_lanes}")
    widths = [min_lanes]
    while widths[-1] < max_lanes:
        widths.append(widths[-1] * 2)
    if widths[-1] != max_lanes:
        raise ValueError(
            f"max_lanes={max_lanes} must be min_lanes * 2**k "
            f"(min_lanes={min_lanes} reaches {widths[-2]} or {widths[-1]})")
    return tuple(widths)


@dataclasses.dataclass
class _Seq:
    """One submitted sequence and its in-flight output buffers."""

    index: int
    name: str
    det_boxes: np.ndarray          # [F, D, 4] padded to the scheduler's D
    det_mask: np.ndarray           # [F, D]
    det_class: Optional[np.ndarray] = None   # [F, D] int32 (multi-class)
    det_embed: Optional[np.ndarray] = None   # [F, D, E] (embed costs)
    # whole-length outputs, allocated by the first chunk that steps the
    # sequence: (boxes [F, T, 4], uid [F, T], emit [F, T][, cls [F, T]])
    out: tuple = ()
    filled: int = 0                # frames of ``out`` written so far

    @property
    def length(self) -> int:
        return self.det_boxes.shape[0]


class StreamScheduler:
    """Multiplex ragged sequences onto ``num_lanes`` recycled engine lanes.

    Works with both engine paths: ``use_kernels=True`` keeps a resident
    :class:`~repro.core.LaneSortState` and masks inside the fused kernel;
    ``use_kernels=False`` masks the per-phase engine step.  Both
    association modes (``SortConfig.assoc``, DESIGN.md §6) serve through
    the same chunked scan — the fused-Hungarian JV stage sees the masked
    per-lane detections, so inactive lanes stay exact no-ops.  Either way
    a sequence's emitted tracks are bit-identical to running it alone.

    Usage::

        sched = StreamScheduler(engine, num_lanes=4)
        for name, db, dm in sequences:
            sched.submit(name, db, dm)
        for tracks in sched.run():      # submission order
            ...

    ``submit`` may be called again after ``run`` returns; lane state
    persists but every admission starts from a masked re-init, so earlier
    traffic cannot leak into later sequences.

    **Elastic mode** (DESIGN.md §8): pass ``min_lanes``/``max_lanes`` and
    the budget autoscales over the pre-compiled ladder
    (:func:`lane_ladder`) between chunks.  Resize policy knobs:

    * ``min_lanes`` / ``max_lanes`` — the ladder bounds; ``max_lanes``
      must be ``min_lanes * 2**k``.  ``num_lanes`` (optional here) picks
      the starting width, default ``min_lanes``.
    * **grow** is demand-driven and immediate: when occupied lanes plus
      queue depth exceed the current width, the budget steps up to the
      smallest ladder width covering demand before the next chunk is
      planned (appended lanes are a masked re-init).
    * **shrink** is utilization-driven and patient: when demand fits a
      smaller ladder width for ``shrink_patience`` consecutive chunk
      boundaries (hysteresis against bursty arrivals), admissions to the
      evacuating lanes stop, and the budget drops only once those lanes
      have drained — no live sequence is ever moved or cancelled.
    * ``precompile`` — compile every ladder width's chunk program at
      construction (on throwaway all-inactive chunks), so a mid-burst
      resize never pays compile latency.  Repeated resizes never retrace
      a compiled width either way (``trace_log`` records one entry per
      chunk-shape trace; ``tests/test_autoscale.py`` locks this).
    * :meth:`request_width` — pin a target width (tests, external
      autoscalers); it overrides the demand policy until released with
      ``request_width(None)``.  A pinned shrink still waits for the
      evacuating lanes to drain.
    """

    def __init__(self, engine: SortEngine, num_lanes: Optional[int] = None,
                 max_dets: Optional[int] = None, chunk: int = 32,
                 mesh=None, *, min_lanes: Optional[int] = None,
                 max_lanes: Optional[int] = None, shrink_patience: int = 2,
                 precompile: bool = True):
        self.elastic = min_lanes is not None or max_lanes is not None
        if self.elastic:
            if min_lanes is None or max_lanes is None:
                raise ValueError(
                    "elastic mode needs both min_lanes and max_lanes")
            self.ladder = lane_ladder(min_lanes, max_lanes)
            num_lanes = self.ladder[0] if num_lanes is None else num_lanes
            if num_lanes not in self.ladder:
                raise ValueError(
                    f"num_lanes={num_lanes} must be a ladder width "
                    f"{self.ladder}")
            if shrink_patience < 1:
                raise ValueError(f"shrink_patience must be >= 1, got "
                                 f"{shrink_patience}")
        else:
            if num_lanes is None:
                raise ValueError("num_lanes is required for a fixed budget "
                                 "(pass min_lanes/max_lanes for elastic)")
            self.ladder = (num_lanes,)
        if num_lanes < 1:
            raise ValueError(f"num_lanes must be >= 1, got {num_lanes}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.engine = engine
        self.num_lanes = num_lanes      # CURRENT width (mutates in elastic)
        self.max_dets = max_dets or engine.config.max_detections
        self.chunk = chunk
        self.mesh = mesh
        self.shrink_patience = shrink_patience

        # class/embed operand threading (DESIGN.md §10): required exactly
        # when the engine's cost/partition config consumes them, so the
        # single-class IoU scheduler plans and dispatches byte-identical
        # chunks to the pre-multiclass code.
        self._need_class = engine.config.num_classes > 1
        self._need_embed = engine.config.cost.uses_embed
        self._embed_dim = engine.config.cost.embed_dim
        self._extra_ndims = ((3,) if self._need_class else ()) + \
                            ((4,) if self._need_embed else ())

        self._pending: collections.deque[_Seq] = collections.deque()
        self._occupant: list[Optional[_Seq]] = [None] * num_lanes
        self._cursor = [0] * num_lanes
        self._ready = ReorderBuffer()
        self._num_submitted = 0
        self._shrink_target: Optional[int] = None   # evacuating toward this
        self._shrink_votes = 0                      # hysteresis counter
        self._forced_width: Optional[int] = None    # request_width override

        # serving counters (benchmarks/ragged.py reads these)
        self.frames_processed = 0      # real sequence frames stepped
        # lanes x steps that carried any planned work: steps of a chunk
        # whose `active` mask is all-False (the tail of a draining final
        # chunk) are excluded, so `utilization` measures lane occupancy of
        # working steps rather than being diluted by drain padding.  The
        # lane factor is the width ACTIVE at each chunk, not the
        # construction width (elastic mode resizes between chunks).
        self.lane_steps = 0
        self.chunks_run = 0
        # segments (one sequence's run of steps on one lane) planned:
        # the host's per-chunk Python work, `frames_processed` the frames
        # it covers
        self.segments_planned = 0
        self.bytes_staged = 0          # chunk operands, host -> device
        self.bytes_fetched = 0         # chunk outputs + next_uid, back
        self.admissions: list[tuple[int, int]] = []  # (seq index, step)
        self.resizes: list[tuple[int, int, int]] = []  # (chunk, old, new)
        # one entry (the traced lane width; per-shard width in mesh mode)
        # per chunk-program trace — the recompilation probe: repeated
        # grow/shrink cycles must never retrace a compiled ladder width.
        self.trace_log: list[int] = []

        need_class, need_embed = self._need_class, self._need_embed

        def chunk_fn(state, det, dm, active, reset, *extras):
            self.trace_log.append(det.shape[1])    # runs at trace time only
            # F serving steps in one call: a per-frame jitted scan, or —
            # with SortConfig.chunk_kernel — ONE chunk-resident pallas_call
            # (DESIGN.md §9).  Everything above this line (planning,
            # accounting, trace_log, the elastic ladder, sharding) is
            # identical under both dispatch modes: the granularity change
            # lives entirely inside the engine call.
            it = iter(extras)
            dc = next(it) if need_class else None
            de = next(it) if need_embed else None
            return self.engine.run_chunk_ragged(state, det, dm, active,
                                                reset, det_class=dc,
                                                det_embed=de)

        if mesh is None:
            self._sharding = None
            self._shardings = None
            self._state = engine.init_ragged(num_lanes)
            self._chunk_fn = jax.jit(chunk_fn)
        else:
            # lanes -> mesh (DESIGN.md §7): validate the lane budget splits
            # evenly (every ladder width, so no resize can fail later),
            # shard the resident state, and wrap the identical chunk scan
            # in shard_map — planning above stays host-side and
            # device-count-agnostic.  One jitted chunk program serves all
            # widths: the PartitionSpecs depend on state structure, not
            # lane count, so each width is just one more shape in its
            # cache.
            from repro.sharding.lanes import LaneSharding, shard_count
            n = shard_count(mesh)
            for w in self.ladder:
                if w % n != 0:
                    raise ValueError(
                        f"ladder width {w} (of {self.ladder}) must divide "
                        f"evenly over the {n}-device lane mesh")
            self._shardings: dict[int, LaneSharding] = {}
            self._sharding = self._sharding_for(num_lanes)
            self._state = self._sharding.init()
            self._chunk_fn = jax.jit(self._sharding.shard_chunk(
                chunk_fn, extra_operand_ndims=self._extra_ndims))
        if self.elastic and precompile:
            self._precompile_ladder()

    # --------------------------------------------------------------- intake
    def submit(self, name: str, det_boxes: np.ndarray,
               det_mask: np.ndarray, det_class: Optional[np.ndarray] = None,
               det_embed: Optional[np.ndarray] = None) -> int:
        """Queue one sequence (``det_boxes [F, D_i, 4]``, ``det_mask
        [F, D_i]``); returns its submission index.  ``D_i`` must not exceed
        the scheduler's detection budget.  ``det_class [F, D_i]`` int /
        ``det_embed [F, D_i, E]`` are required exactly when the engine's
        config partitions classes / composes an embedding cost
        (DESIGN.md §10), and ignored otherwise."""
        det_boxes = np.asarray(det_boxes, np.float32)
        det_mask = np.asarray(det_mask, bool)
        f, d_i = det_mask.shape
        if d_i > self.max_dets:
            raise ValueError(
                f"sequence {name!r} has {d_i} detection slots, scheduler "
                f"budget is {self.max_dets}")
        if self._need_class and det_class is None:
            raise ValueError(
                f"sequence {name!r}: det_class is required when "
                f"num_classes={self.engine.config.num_classes} > 1")
        if self._need_embed and det_embed is None:
            raise ValueError(
                f"sequence {name!r}: det_embed is required when the cost "
                f"has an embedding term ({self.engine.config.cost})")
        dc = (np.asarray(det_class, np.int32) if self._need_class else None)
        de = (np.asarray(det_embed, np.float32) if self._need_embed else None)
        if d_i < self.max_dets:
            pad = self.max_dets - d_i
            det_boxes = np.pad(det_boxes, ((0, 0), (0, pad), (0, 0)))
            det_mask = np.pad(det_mask, ((0, 0), (0, pad)))
            if dc is not None:
                dc = np.pad(dc, ((0, 0), (0, pad)))
            if de is not None:
                de = np.pad(de, ((0, 0), (0, pad), (0, 0)))
        seq = _Seq(self._num_submitted, name, det_boxes, det_mask,
                   det_class=dc, det_embed=de)
        self._num_submitted += 1
        if f == 0:  # nothing to step; complete immediately (still in order)
            self._finalize(seq)
        else:
            self._pending.append(seq)
        return seq.index

    @property
    def busy(self) -> bool:
        """True while the scheduler still owes the caller anything: queued
        or in-flight sequences, *or* finished results buffered for
        in-order release.  (The buffered term matters: a zero-frame
        sequence submitted while idle finalizes straight into the reorder
        buffer without ever occupying a lane — ``busy`` ignoring it left
        that result stranded, since drain loops stopped before anything
        popped it.)"""
        return self._has_step_work or len(self._ready) > 0

    @property
    def _has_step_work(self) -> bool:
        """Anything left that requires dispatching a chunk."""
        return bool(self._pending) or any(
            s is not None for s in self._occupant)

    @property
    def utilization(self) -> float:
        """Fraction of dispatched working lane-steps that carried a real
        frame (``frames_processed / lane_steps``).  Fully-idle tail steps
        of a draining chunk are excluded from the denominator — they hold
        no lanes hostage, they only pad the final ``lax.scan``."""
        return self.frames_processed / max(self.lane_steps, 1)

    # ------------------------------------------------------------- elastic
    def _sharding_for(self, width: int):
        """The (cached) :class:`LaneSharding` for one ladder width."""
        from repro.sharding.lanes import LaneSharding
        if width not in self._shardings:
            self._shardings[width] = LaneSharding(self.engine, self.mesh,
                                                  width)
        return self._shardings[width]

    def _precompile_ladder(self) -> None:
        """Compile every ladder width's chunk program up front.

        Each width is traced on a throwaway freshly-init state with
        all-inactive operands — an inactive step is an exact no-op
        (DESIGN.md §3.2), so warm-up never touches serving state, and the
        operands carry exactly the dtypes/shardings real chunks use, so
        the first real chunk at any width is a cache hit.
        """
        c, d = self.chunk, self.max_dets
        for w in self.ladder:
            det = np.zeros((c, w, d, 4), np.float32)
            dm = np.zeros((c, w, d), bool)
            idle = np.zeros((c, w), bool)
            extras = self._zero_extras(c, w, d)
            if self._sharding is not None:
                sh = self._sharding_for(w)
                state = self._state if w == self.num_lanes else sh.init()
                operands = sh.place(det, dm, idle, idle, *extras)
            else:
                state = (self._state if w == self.num_lanes
                         else self.engine.init_ragged(w))
                operands = tuple(jnp.asarray(a)
                                 for a in (det, dm, idle, idle) + extras)
            self._chunk_fn(state, *operands)

    def _zero_extras(self, c: int, l: int, d: int) -> tuple:
        """All-zero class/embed chunk operands in dispatch order (class
        first), matching ``_extra_ndims``."""
        extras = ()
        if self._need_class:
            extras += (np.zeros((c, l, d), np.int32),)
        if self._need_embed:
            extras += (np.zeros((c, l, d, self._embed_dim), np.float32),)
        return extras

    def request_width(self, width: Optional[int]) -> None:
        """Pin the budget to ``width`` (a ladder width), overriding the
        demand policy until released with ``request_width(None)`` or
        superseded by a new pin: grow applies before the next chunk;
        shrink engages the drain protocol immediately (no hysteresis) but
        still waits for the evacuating lanes to empty — queued sequences
        re-queue into the surviving lanes, FIFO order intact.  Tests and
        external autoscalers use this; normal serving relies on the
        built-in policy."""
        if not self.elastic:
            raise ValueError("request_width needs an elastic scheduler "
                             "(min_lanes/max_lanes)")
        if width is not None and width not in self.ladder:
            raise ValueError(f"width {width} not on the ladder {self.ladder}")
        self._forced_width = width

    def _target_width(self) -> int:
        """Smallest ladder width covering current demand (occupied lanes
        plus queue depth) — the width at which the next chunk would run at
        the highest lane utilization without queueing admissible work."""
        occupied = sum(o is not None for o in self._occupant)
        demand = occupied + len(self._pending)
        for w in self.ladder:
            if w >= demand:
                return w
        return self.ladder[-1]

    def _maybe_resize(self) -> None:
        """Resize policy, run once per chunk boundary (before planning).

        Grow is immediate; shrink requires ``shrink_patience`` consecutive
        under-demand boundaries, then marks lanes ``>= target`` as
        evacuating (no further admissions) and applies only once they have
        all drained — so the budget never drops while a live sequence
        occupies a doomed lane, and uids never alias (recycling semantics
        are untouched)."""
        if not self.elastic:
            return
        forced = self._forced_width
        target = forced if forced is not None else self._target_width()
        if target > self.num_lanes:
            self._shrink_target = None           # growth cancels evacuation
            self._shrink_votes = 0
            self._apply_resize(target)
        elif target < self.num_lanes:
            self._shrink_votes = (self.shrink_patience if forced is not None
                                  else self._shrink_votes + 1)
            if self._shrink_votes >= self.shrink_patience:
                self._shrink_target = target
        else:
            self._shrink_votes = 0
            self._shrink_target = None
        if self._shrink_target is not None and all(
                o is None for o in self._occupant[self._shrink_target:]):
            self._apply_resize(self._shrink_target)
            self._shrink_target = None
            self._shrink_votes = 0

    def _apply_resize(self, new_width: int) -> None:
        """Migrate the resident state to ``new_width`` lanes at a chunk
        boundary.  Kept lanes (including lanes mid-sequence) move bit for
        bit; appended lanes are a masked re-init; in mesh mode the
        migrated state is re-placed with the new width's ``NamedSharding``
        here, so the next chunk starts from committed shardings."""
        old = self.num_lanes
        if new_width == old:
            return
        if self._sharding is not None:
            new_sharding = self._sharding_for(new_width)
            self._state = self._sharding.migrate(self._state, new_sharding)
            self._sharding = new_sharding
        else:
            self._state = self.engine.resize_ragged(self._state, old,
                                                    new_width)
        if new_width > old:
            self._occupant += [None] * (new_width - old)
            self._cursor += [0] * (new_width - old)
        else:
            assert all(o is None for o in self._occupant[new_width:]), \
                "shrink applied before the evacuating lanes drained"
            del self._occupant[new_width:]
            del self._cursor[new_width:]
        self.num_lanes = new_width
        self.resizes.append((self.chunks_run, old, new_width))

    # ------------------------------------------------------------- planning
    def _plan_chunk(self):
        """Plan the next ``chunk`` steps of the lane schedule on the host.

        Admission is data-independent (it depends only on queue order and
        sequence lengths), so the whole chunk — including mid-chunk
        recycling — is planned before anything is dispatched.  While a
        shrink is evacuating, lanes at or beyond the target width take no
        new admissions (their occupants run to completion); queued
        sequences keep admitting FIFO into the surviving lanes.

        The unit of the plan is a *segment* ``(t0, n, lane, seq, k0)``:
        ``seq`` runs frames ``k0 .. k0+n-1`` on ``lane`` at chunk steps
        ``t0 .. t0+n-1``, filled with one slice copy per operand.  Each
        queued sequence is admitted at the earliest free ``(step, lane)``,
        step-major and lane-minor, and a lane freed at step ``t`` admits
        at ``t`` — the order of a step-by-step walk over every lane."""
        c, l, d = self.chunk, self.num_lanes, self.max_dets
        admit_limit = (l if self._shrink_target is None
                       else self._shrink_target)
        det = np.zeros((c, l, d, 4), np.float32)
        dm = np.zeros((c, l, d), bool)
        active = np.zeros((c, l), bool)
        reset = np.zeros((c, l), bool)
        extras = self._zero_extras(c, l, d)
        it = iter(extras)
        dc = next(it) if self._need_class else None
        de = next(it) if self._need_embed else None
        segments = []                          # (t0, n, lane, seq, k0)
        free = []                              # heap of (step, lane)

        def place(t0, lane, seq, k0):
            n = min(seq.length - k0, c - t0)
            t1, k1 = t0 + n, k0 + n
            det[t0:t1, lane] = seq.det_boxes[k0:k1]
            dm[t0:t1, lane] = seq.det_mask[k0:k1]
            if dc is not None:
                dc[t0:t1, lane] = seq.det_class[k0:k1]
            if de is not None:
                de[t0:t1, lane] = seq.det_embed[k0:k1]
            active[t0:t1, lane] = True
            segments.append((t0, n, lane, seq, k0))
            self._cursor[lane] = k1
            if k1 == seq.length:               # lane free from step t1
                self._occupant[lane] = None
                if t1 < c and lane < admit_limit:
                    heapq.heappush(free, (t1, lane))

        for lane in range(l):
            seq = self._occupant[lane]
            if seq is not None:
                place(0, lane, seq, self._cursor[lane])
            elif lane < admit_limit:
                heapq.heappush(free, (0, lane))
        while free and self._pending:
            t, lane = heapq.heappop(free)
            seq = self._occupant[lane] = self._pending.popleft()
            reset[t, lane] = True                 # recycle in this step
            self.admissions.append((seq.index, self.chunks_run * c + t))
            place(t, lane, seq, 0)
        return det, dm, active, reset, extras, segments

    # ------------------------------------------------------------ execution
    def _run_chunk(self) -> list[SequenceTracks]:
        if not self._has_step_work:
            # nothing to dispatch — only buffered completions to release
            return self._ready.pop_ready()
        n = self.chunks_run
        shards = 1 if self._sharding is None else self._sharding.shard_count

        def span(name):
            return jax.profiler.TraceAnnotation(name, chunk=n, shards=shards)

        with span("sched.plan"):
            self._maybe_resize()
            det, dm, active, reset, extras, segments = self._plan_chunk()
        staged = (det, dm, active, reset) + extras
        with span("sched.stage"):
            if self._sharding is not None:
                operands = self._sharding.place(*staged)
            else:
                operands = tuple(jnp.asarray(a) for a in staged)
            self._state, outs = self._chunk_fn(self._state, *operands)
        with span("sched.fetch"):
            next_uid = self._check_uid_headroom()
            boxes = np.asarray(outs.boxes)            # [C, L, T, 4]
            uid = np.asarray(outs.uid)
            emit = np.asarray(outs.emit)
            cls = np.asarray(outs.cls) if self._need_class else None
        with span("sched.unpack"):
            fetched = (boxes, uid, emit) + ((cls,) if cls is not None else ())
            finished = []
            for t0, m, lane, seq, k0 in segments:
                if k0 == 0:
                    seq.out = tuple(np.empty((seq.length,) + a.shape[2:],
                                             a.dtype) for a in fetched)
                # into the sequence's own buffers, which pin no chunk array
                for buf, a in zip(seq.out, fetched):
                    buf[k0:k0 + m] = a[t0:t0 + m, lane]
                seq.filled = k0 + m
                if seq.filled == seq.length:
                    finished.append(seq)
            self.frames_processed += sum(seg[1] for seg in segments)
            self.segments_planned += len(segments)
            # denominator from the planned schedule, not the raw chunk
            # size: fully-idle tail steps of a draining chunk carry no
            # lanes' work
            self.lane_steps += int(active.any(axis=1).sum()) * self.num_lanes
            self.chunks_run += 1
            self.bytes_staged += sum(a.nbytes for a in staged)
            self.bytes_fetched += sum(a.nbytes for a in
                                      (next_uid, boxes, uid, emit, cls)
                                      if a is not None)
        with span("sched.release"):
            for seq in finished:
                self._finalize(seq)
            return self._ready.pop_ready()

    def _no_frames(self) -> tuple:
        """The outputs of a sequence with no frame filled, in the order
        of ``_Seq.out``."""
        t = self.engine.config.max_trackers
        out = (np.zeros((0, t, 4), np.float32), np.zeros((0, t), np.int32),
               np.zeros((0, t), bool))
        return out + ((np.zeros((0, t), np.int32),) if self._need_class
                      else ())

    def _finalize(self, seq: _Seq) -> None:
        boxes, uid, emit, *cls = seq.out or self._no_frames()
        self._ready.put(seq.index, SequenceTracks(
            name=seq.name, boxes=boxes, uid=uid, emit=emit,
            cls=cls[0] if cls else None))

    def _check_uid_headroom(self) -> np.ndarray:
        """Guard the per-lane int32 uid counter (``SlotPool.next_uid``).

        ``reset_ragged`` resets the counter to ``uid_start`` on every lane
        recycle, so under normal serving the counter is bounded by one
        sequence's birth count.  A single monster sequence can still run
        it toward int32 overflow; rather than silently wrapping onto uids
        that may *still be alive*, fail loudly with the remediation.  The
        check fetches the ``[L]`` int32 counter row each chunk (a tiny
        cross-device gather in mesh mode) — negligible next to the chunk's
        own output transfer, and the chunk boundary is already a host
        sync point.  Returns the fetched row.
        """
        next_uid = np.asarray(self._state.pool.next_uid)
        if next_uid.size and int(next_uid.max()) > slots.UID_LIMIT:
            lane = int(next_uid.argmax())
            raise RuntimeError(
                f"track uid counter on lane {lane} exceeded "
                f"slots.UID_LIMIT ({slots.UID_LIMIT}): a single sequence "
                f"allocated ~2**31 track ids.  uids are int32 and only "
                f"reset when the lane is recycled (reset_ragged); split "
                f"the sequence or re-admit it to reset its uid namespace.")
        return next_uid

    def pop_ready(self) -> list[SequenceTracks]:
        """Release every finished sequence whose turn has come (submission
        order), **without dispatching anything** — the drain path for
        results that finalized off the chunk path (e.g. zero-frame
        sequences completed at ``submit`` time)."""
        return self._ready.pop_ready()

    def run_chunk(self) -> list[SequenceTracks]:
        """Dispatch (at most) one planned chunk and release whatever
        finished — the service front-end's pump unit (DESIGN.md §11).
        Every return is a chunk boundary: :meth:`export_state` is legal
        immediately after."""
        return self._run_chunk()

    # ------------------------------------------- checkpoint/restore hooks
    # (DESIGN.md §11: the full serving state crosses the checkpoint in a
    # topology-NEUTRAL form — device state in the engine layout via the
    # exact layout inverses, host bookkeeping as numpy arrays + JSON-able
    # meta — so a server restarted on a different execution strategy,
    # stream-block padding, or device mesh resumes bit-exactly.)
    STATE_SCHEMA = 1

    def _engine_signature(self) -> dict:
        """The semantic engine config a checkpoint must agree on.  The
        execution strategy (use_kernels / chunk_kernel / block_b / mesh)
        is deliberately absent: every path computes the same tracker
        (track identities exact, coordinates to float tolerance —
        tests/test_oracle_parity.py), so a checkpoint may resume on any
        of them; resuming on the SAME strategy is bit-exact."""
        cfg = self.engine.config
        return {"max_trackers": cfg.max_trackers,
                "max_detections": cfg.max_detections,
                "iou_threshold": cfg.iou_threshold,
                "max_age": cfg.max_age, "min_hits": cfg.min_hits,
                "assoc": cfg.assoc, "dtype": cfg.dtype,
                "num_classes": cfg.num_classes, "cost": repr(cfg.cost)}

    def _engine_layout_state(self):
        """Resident device state -> engine-layout ``SortState`` on host."""
        if self._sharding is not None:
            state = self._sharding._to_engine(self._state)
        elif self.engine.config.use_kernels:
            state = sort_state_of(self._state, self.num_lanes)
        else:
            state = self._state
        return jax.tree.map(np.asarray, jax.device_get(state))

    _OUT_NAMES = ("out_boxes", "out_uid", "out_emit", "out_cls")

    def _seq_arrays(self, seq: _Seq) -> dict:
        pre = f"seq/{seq.index}"
        arrays = {f"{pre}/det_boxes": seq.det_boxes,
                  f"{pre}/det_mask": seq.det_mask}
        # the filled prefix: frames already written are never rewritten
        for name, buf in zip(self._OUT_NAMES, seq.out or self._no_frames()):
            arrays[f"{pre}/{name}"] = buf[:seq.filled]
        if seq.det_class is not None:
            arrays[f"{pre}/det_class"] = seq.det_class
        if seq.det_embed is not None:
            arrays[f"{pre}/det_embed"] = seq.det_embed
        return arrays

    def export_state(self) -> tuple[dict, dict]:
        """Snapshot the COMPLETE serving state at a chunk boundary.

        Returns ``(meta, arrays)``: ``meta`` is JSON-able (schema,
        engine signature, lane occupancy/cursors, FIFO queue order,
        reorder-buffer watermark, elastic ladder position, counters);
        ``arrays`` is a flat ``{path: np.ndarray}`` dict holding the
        engine-layout device state (``lane/...`` — per-lane Kalman
        means/covariances, lifecycle pools, **uid namespaces**), every
        live sequence's inputs + partially accumulated outputs
        (``seq/<i>/...``), and finished-but-unreleased results
        (``done/<i>/...``).  :meth:`import_state` consumes the pair;
        everything a resumed scheduler needs to continue **bit-exactly**
        is inside (tests/test_scheduler.py, tests/test_serving.py).
        """
        live = [s for s in self._occupant if s is not None] \
            + list(self._pending)
        meta = {
            "schema": self.STATE_SCHEMA,
            "engine": self._engine_signature(),
            "max_dets": self.max_dets,
            "num_lanes": self.num_lanes,
            "occupant": [s.index if s is not None else None
                         for s in self._occupant],
            "cursor": [int(c) for c in self._cursor],
            "pending": [s.index for s in self._pending],
            "num_submitted": self._num_submitted,
            "ready_next": self._ready.next_index,
            "held": [int(i) for i in self._ready.held_indices],
            "shrink_target": self._shrink_target,
            "shrink_votes": self._shrink_votes,
            "forced_width": self._forced_width,
            "counters": {"frames_processed": self.frames_processed,
                         "lane_steps": self.lane_steps,
                         "chunks_run": self.chunks_run,
                         "segments_planned": self.segments_planned,
                         "bytes_staged": self.bytes_staged,
                         "bytes_fetched": self.bytes_fetched},
            "admissions": [list(a) for a in self.admissions],
            "seqs": {str(s.index): {"name": s.name} for s in live},
            "done": {str(i): self._ready.peek(i).name
                     for i in self._ready.held_indices},
        }
        from repro.ckpt.checkpoint import flatten_with_paths
        keys, leaves, _ = flatten_with_paths(self._engine_layout_state())
        arrays = {f"lane/{k}": np.asarray(leaf)
                  for k, leaf in zip(keys, leaves)}
        for seq in live:
            arrays.update(self._seq_arrays(seq))
        for i in self._ready.held_indices:
            tr = self._ready.peek(i)
            arrays[f"done/{i}/boxes"] = tr.boxes
            arrays[f"done/{i}/uid"] = tr.uid
            arrays[f"done/{i}/emit"] = tr.emit
            if tr.cls is not None:
                arrays[f"done/{i}/cls"] = tr.cls
        return meta, arrays

    def _rebuild_seq(self, idx: int, name: str, arrays: dict) -> _Seq:
        pre = f"seq/{idx}"
        missing = [k for k in (f"{pre}/det_boxes", f"{pre}/det_mask",
                               f"{pre}/out_boxes", f"{pre}/out_uid",
                               f"{pre}/out_emit")
                   if k not in arrays]
        if missing:
            raise ValueError(f"checkpoint is missing sequence leaves "
                             f"{missing} for live sequence {name!r}")
        db = np.asarray(arrays[f"{pre}/det_boxes"], np.float32)
        dm = np.asarray(arrays[f"{pre}/det_mask"], bool)
        if dm.ndim != 2 or dm.shape[1] != self.max_dets:
            raise ValueError(
                f"sequence {name!r}: checkpointed detection budget "
                f"{dm.shape} does not match this scheduler's "
                f"max_dets={self.max_dets}")
        dc = arrays.get(f"{pre}/det_class")
        de = arrays.get(f"{pre}/det_embed")
        if self._need_class and dc is None:
            raise ValueError(f"sequence {name!r}: checkpoint carries no "
                             f"det_class but this engine partitions classes")
        if self._need_embed and de is None:
            raise ValueError(f"sequence {name!r}: checkpoint carries no "
                             f"det_embed but this engine's cost needs it")
        seq = _Seq(idx, name, db, dm,
                   det_class=(None if dc is None
                              else np.asarray(dc, np.int32)),
                   det_embed=(None if de is None
                              else np.asarray(de, np.float32)))
        names = self._OUT_NAMES[:4 if self._need_class else 3]
        prefix = [np.asarray(arrays[f"{pre}/{name}"]) for name in names]
        seq.filled = len(prefix[0])
        if seq.filled:
            seq.out = tuple(np.empty((seq.length,) + a.shape[1:], a.dtype)
                            for a in prefix)
            for buf, a in zip(seq.out, prefix):
                buf[:seq.filled] = a
        return seq

    def import_state(self, meta: dict, arrays: dict) -> None:
        """Rebuild the full serving state from :meth:`export_state`'s
        snapshot (typically round-tripped through ``repro.ckpt``).

        Validates before touching anything: schema, the semantic engine
        signature, the detection budget, and that the checkpointed lane
        width is on this scheduler's ladder — so an elastic-restart
        mismatch is a diagnosable ``ValueError``, not corrupted serving.
        The device state re-enters through the exact engine-layout
        inverses (and, in mesh mode, is re-placed with this topology's
        ``NamedSharding``), so a same-strategy resume's per-sequence
        outputs are bit-identical to an uninterrupted run; a resume onto
        a different execution strategy matches it the way the strategies
        match each other — identities exact, coordinates allclose.
        """
        if meta.get("schema") != self.STATE_SCHEMA:
            raise ValueError(f"unsupported scheduler state schema "
                             f"{meta.get('schema')!r} (this build speaks "
                             f"{self.STATE_SCHEMA})")
        sig = self._engine_signature()
        if meta.get("engine") != sig:
            diff = {k: (meta.get("engine", {}).get(k), sig[k])
                    for k in sig if meta.get("engine", {}).get(k) != sig[k]}
            raise ValueError(
                f"checkpointed engine config does not match this "
                f"scheduler's (checkpoint vs here): {diff}")
        if int(meta["max_dets"]) != self.max_dets:
            raise ValueError(f"checkpoint max_dets={meta['max_dets']} vs "
                             f"this scheduler's {self.max_dets}")
        width = int(meta["num_lanes"])
        if width not in self.ladder:
            raise ValueError(
                f"checkpointed lane width {width} is not on this "
                f"scheduler's ladder {self.ladder} — construct the "
                f"scheduler with a ladder covering the checkpoint "
                f"(elastic-restart width mismatch)")

        # device state: engine layout -> this topology's resident layout
        from repro.ckpt.checkpoint import flatten_with_paths
        like = self.engine.init(width)
        keys, leaves, treedef = flatten_with_paths(like)
        missing = [k for k in keys if f"lane/{k}" not in arrays]
        if missing:
            extra = sorted(k for k in arrays if k.startswith("lane/"))
            raise ValueError(f"checkpoint is missing device-state leaves "
                             f"{missing}; it carries {extra}")
        vals = []
        for k, leaf in zip(keys, leaves):
            arr = np.asarray(arrays[f"lane/{k}"])
            want = tuple(np.shape(leaf))
            if tuple(arr.shape) != want:
                raise ValueError(f"device-state leaf {k}: checkpoint shape "
                                 f"{tuple(arr.shape)} != expected {want}")
            vals.append(jnp.asarray(
                arr.astype(np.dtype(leaf.dtype), copy=False)))
        eng_state = jax.tree.unflatten(treedef, vals)
        if self.mesh is not None:
            sharding = self._sharding_for(width)
            self._state = sharding.place_engine_state(eng_state)
            self._sharding = sharding
        elif self.engine.config.use_kernels:
            self._state = lane_state_of(eng_state, self.engine._block_s)
        else:
            self._state = eng_state

        # host bookkeeping: occupancy, FIFO order, reorder buffer, elastic
        seqs = {int(i): self._rebuild_seq(int(i), info["name"], arrays)
                for i, info in meta["seqs"].items()}
        self.num_lanes = width
        self._occupant = [seqs[i] if i is not None else None
                          for i in meta["occupant"]]
        self._cursor = [int(c) for c in meta["cursor"]]
        self._pending = collections.deque(seqs[i] for i in meta["pending"])
        self._num_submitted = int(meta["num_submitted"])
        self._ready = ReorderBuffer(start=int(meta["ready_next"]))
        for i in meta["held"]:
            cls = arrays.get(f"done/{i}/cls")
            self._ready.put(int(i), SequenceTracks(
                name=meta["done"][str(i)],
                boxes=np.asarray(arrays[f"done/{i}/boxes"], np.float32),
                uid=np.asarray(arrays[f"done/{i}/uid"], np.int32),
                emit=np.asarray(arrays[f"done/{i}/emit"], bool),
                cls=(np.asarray(cls, np.int32)
                     if cls is not None else None)))
        self._shrink_target = (None if meta["shrink_target"] is None
                               else int(meta["shrink_target"]))
        self._shrink_votes = int(meta["shrink_votes"])
        self._forced_width = (None if meta["forced_width"] is None
                              else int(meta["forced_width"]))
        c = meta["counters"]
        self.frames_processed = int(c["frames_processed"])
        self.lane_steps = int(c["lane_steps"])
        self.chunks_run = int(c["chunks_run"])
        # snapshots written before these counters existed read 0
        self.segments_planned = int(c.get("segments_planned", 0))
        self.bytes_staged = int(c.get("bytes_staged", 0))
        self.bytes_fetched = int(c.get("bytes_fetched", 0))
        self.admissions = [tuple(a) for a in meta["admissions"]]

    def drain(self) -> list[SequenceTracks]:
        """Run chunks until no step work remains, then release everything
        buffered; returns all newly finished sequences in submission
        order.  Never dispatches an empty chunk."""
        results = []
        while self._has_step_work:
            results.extend(self._run_chunk())
        results.extend(self.pop_ready())
        return results

    def run(self) -> list[SequenceTracks]:
        """Process every submitted sequence to completion (drain), returning
        their track streams **in submission order**."""
        return self.drain()
