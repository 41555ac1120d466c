"""Device-sharded lane serving — the lane axis spread over a JAX mesh.

The paper's throughput result (§VI) is that tiny-matrix SORT scales only
by running *independent* video sequences in parallel — one OpenMP worker
per stream there, one vector lane per stream here (DESIGN.md §2).  A
single device caps the lane budget; this module adds the next rung
(DESIGN.md §7): shard the lane axis over a 1-D ``("lanes",)`` device mesh
so one :class:`~repro.serve.StreamScheduler` drives N devices, each
running the same single-dispatch fused frame step on its own contiguous
lane shard.

Because sequences are independent — no phase of the frame step ever
crosses lanes (DESIGN.md §3.2) — the sharded program needs **zero
cross-device collectives**: ``jax.shard_map``
partitions the state and chunk operands, every device scans its shard
locally, and a sharded run is *bit-identical* to the single-device run
(``tests/test_device_sharding.py`` locks this down for both engine paths
and both association modes).  The chunk-resident megakernel (DESIGN.md
§9) composes unchanged: ``run_chunk_ragged`` replaces the per-frame scan
inside the ``shard_map`` body with one chunk dispatch per device, still
collective-free (same HLO grep lock, ``chunk_kernel=True`` case).

Sharding layouts (the lane axis must be a contiguous array dimension for
``NamedSharding`` to place each device's shard without copies):

* per-phase path — :class:`~repro.core.SortState`: the stream axis is
  dim 0 of every leaf (``x [L, T, 7]``, pool fields ``[L, T]``), so the
  state shards directly.
* fused path — :class:`~repro.core.LaneSortState` flattens lanes
  tracker-slot major (``b = t * S_pad + s``), so a contiguous split of
  ``[7, B]`` would cut the *slot* axis, not the stream axis.  The sharded
  resident state therefore keeps the free 3-D view
  (:class:`MeshLaneState`: ``x [7, T, L]``, ``p [49, T, L]``) whose minor
  axis *is* the lane axis; each device's shard reshapes back to a local
  ``LaneSortState`` at zero cost inside the ``shard_map`` body.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import kalman, slots
from repro.core.sort import (LaneSortState, SortOutput, SortState,
                             lane_state_of, resize_streams, sort_state_of)

from .specs import LANE_AXIS, lane_dim_spec

__all__ = ["LANE_AXIS", "MeshLaneState", "LaneSharding", "lane_mesh",
           "shard_count", "state_pspecs"]

# Profiler spans (``jax.profiler.TraceAnnotation``): ``lanes.place``
# covers :meth:`LaneSharding.place`, inside the scheduler's
# ``sched.stage``; it carries ``shards=<n>``.
SPANS = ("lanes.place",)


def lane_mesh(num_devices: Optional[int] = None, *, devices=None) -> Mesh:
    """A 1-D ``("lanes",)`` mesh over the first ``num_devices`` devices.

    On CPU, simulated devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
    initializes); the error message below points there because it is the
    step everyone forgets.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(
                f"requested {num_devices} devices, only {len(devs)} "
                f"available (on CPU, set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={num_devices} "
                f"before jax initializes)")
        devs = devs[:num_devices]
    return Mesh(np.asarray(devs), (LANE_AXIS,))


def shard_count(mesh: Mesh) -> int:
    if LANE_AXIS not in mesh.shape:
        raise ValueError(
            f"mesh {mesh.axis_names} has no {LANE_AXIS!r} axis; build it "
            f"with repro.sharding.lane_mesh")
    return int(mesh.shape[LANE_AXIS])


class MeshLaneState(NamedTuple):
    """:class:`~repro.core.LaneSortState` in its free 3-D view, lane-minor.

    ``x [7, T, L]`` / ``p [49, T, L]`` are row-major reshapes of the flat
    ``[7, B]`` / ``[49, B]`` lane state (``B = T * L``), so converting
    between the two is free *per shard*; ``pool`` fields are already
    ``[T, L]`` and ``frame_count`` ``[L]``.  Every leaf carries the lane
    axis as its **last** dimension, which is what lets one
    ``PartitionSpec`` family shard the whole pytree contiguously.
    """

    x: jnp.ndarray            # [7, T, L]
    p: jnp.ndarray            # [49, T, L]
    pool: slots.SlotPool      # [T, L] (+ next_uid [L])
    frame_count: jnp.ndarray  # [L]
    # [E, T, L] appearance embeddings (zero-size when the cost has no
    # embed term, DESIGN.md §10); lane axis last like every other leaf
    embed: jnp.ndarray = None


def mesh_view(lane: LaneSortState) -> MeshLaneState:
    """Flat lane state -> 3-D mesh view (free row-major reshape)."""
    t, sp = lane.pool.alive.shape
    e = lane.embed.shape[0]
    return MeshLaneState(
        x=lane.x.reshape(kalman.DIM_X, t, sp),
        p=lane.p.reshape(49, t, sp),
        pool=lane.pool,
        frame_count=lane.frame_count,
        embed=lane.embed.reshape(e, t, sp))


def lane_view(mesh_state: MeshLaneState) -> LaneSortState:
    """3-D mesh view -> flat lane state (the engine's resident layout)."""
    t, sp = mesh_state.pool.alive.shape
    e = mesh_state.embed.shape[0]
    return LaneSortState(
        x=mesh_state.x.reshape(kalman.DIM_X, t * sp),
        p=mesh_state.p.reshape(49, t * sp),
        pool=mesh_state.pool,
        frame_count=mesh_state.frame_count,
        embed=mesh_state.embed.reshape(e, t * sp))


def state_pspecs(state):
    """PartitionSpecs sharding a state pytree's lane axis over ``lanes``.

    :class:`MeshLaneState` carries the lane axis last on every leaf;
    :class:`~repro.core.SortState` carries it first.  Either way one
    uniform rule specs the whole tree.
    """
    if isinstance(state, MeshLaneState):
        return jax.tree.map(lambda a: lane_dim_spec(a.ndim, a.ndim - 1),
                            state)
    if isinstance(state, SortState):
        return jax.tree.map(lambda a: lane_dim_spec(a.ndim, 0), state)
    raise TypeError(f"unshardable state type {type(state).__name__}; "
                    f"expected MeshLaneState or SortState")


# chunk operands are [chunk, L, ...]: the lane axis is dim 1 everywhere
def _chunk_spec(ndim: int) -> P:
    return lane_dim_spec(ndim, 1)


def _placement(state, specs, mesh: Mesh):
    """The ``NamedSharding`` each state leaf is placed with: its spec,
    except a zero-size leaf (``embed`` of a cost without an embedding
    term), which is replicated.  XLA returns every zero-size output of the
    chunk program replicated whatever its out-spec, so placing it so from
    the start gives the program the same input shardings on every chunk:
    it compiles once, not again at the second chunk."""
    return jax.tree.map(
        lambda a, spec: NamedSharding(mesh, P() if a.size == 0 else spec),
        state, specs)


class LaneSharding:
    """``lanes -> mesh`` sharding layer for the stream scheduler.

    Wraps the scheduler's chunked ``lax.scan`` in ``shard_map`` over a
    1-D ``("lanes",)`` mesh: each device owns ``num_lanes / N`` contiguous
    lanes of the resident state and steps them with the engine's own
    ``step_ragged`` — the same single fused dispatch per device per scan
    step, no collectives, host-side planning untouched.

    Usage (what ``StreamScheduler(mesh=...)`` does internally)::

        sharding = LaneSharding(engine, mesh, num_lanes)
        state = sharding.init()                     # device_put, sharded
        chunk = jax.jit(sharding.shard_chunk(body)) # body = reset+step scan
        det, dm, act, rst = sharding.place(det, dm, act, rst)
        state, outs = chunk(state, det, dm, act, rst)
    """

    def __init__(self, engine, mesh: Mesh, num_lanes: int):
        n = shard_count(mesh)
        if num_lanes % n != 0:
            raise ValueError(
                f"num_lanes={num_lanes} must divide evenly over the "
                f"{n}-device lane mesh (got remainder {num_lanes % n})")
        self.engine = engine
        self.mesh = mesh
        self.num_lanes = num_lanes
        self.shard_count = n
        self.lanes_per_shard = num_lanes // n
        self._fused = bool(engine.config.use_kernels)
        self._state_specs = None

    # ----------------------------------------------------------- state init
    def init(self):
        """Sharded initial ragged state, placed with ``NamedSharding``.

        The init state is lane-uniform (zero means, broadcast covariance,
        empty pool), so the global state is ``shard_count`` tiled copies of
        a per-shard ``init_ragged`` — bit-identical to what each device
        would initialize locally, including the fused path's per-shard
        stream padding.
        """
        if self._fused:
            local = mesh_view(self.engine.init_ragged(self.lanes_per_shard))
            state = jax.tree.map(
                lambda a: jnp.tile(
                    a, (1,) * (a.ndim - 1) + (self.shard_count,)), local)
        else:
            state = self.engine.init(self.num_lanes)
        self._state_specs = state_pspecs(state)
        return jax.device_put(
            state, _placement(state, self._state_specs, self.mesh))

    # ------------------------------------------------------------ chunk fn
    def shard_chunk(self, chunk_body, extra_operand_ndims=()):
        """Wrap the scheduler's chunk scan in ``shard_map``.

        ``chunk_body(state, det, dm, active, reset, *extras) -> (state,
        outs)`` is the unsharded scan (masked re-init + ``step_ragged`` per
        step); it runs unchanged on each device's local lane shard.
        ``extra_operand_ndims`` declares the rank of each trailing operand
        (e.g. ``det_class [C, L, D]`` -> 3, ``det_embed [C, L, D, E]`` ->
        4); like every chunk operand they carry the lane axis on dim 1, so
        the class/embed threading stays collective-free (DESIGN.md §10).
        On the fused path the carried state crosses the boundary in its 3-D
        mesh view and reshapes to the flat local lane layout inside — both
        reshapes are free.  No collective appears anywhere in the body, so
        the compiled program is N independent per-device scans.
        """
        if self._state_specs is None:
            raise RuntimeError("call init() before shard_chunk()")
        fused = self._fused

        def local_chunk(state, det, dm, active, reset, *extras):
            st = lane_view(state) if fused else state
            st, outs = chunk_body(st, det, dm, active, reset, *extras)
            return (mesh_view(st) if fused else st), outs

        out_specs = (self._state_specs,
                     SortOutput(boxes=_chunk_spec(4), uid=_chunk_spec(3),
                                emit=_chunk_spec(3), matched_det=_chunk_spec(3),
                                cls=_chunk_spec(3)))
        return jax.shard_map(
            local_chunk, mesh=self.mesh,
            in_specs=(self._state_specs, _chunk_spec(4), _chunk_spec(3),
                      _chunk_spec(2), _chunk_spec(2))
                     + tuple(_chunk_spec(n) for n in extra_operand_ndims),
            out_specs=out_specs,
            check_vma=False)

    # ----------------------------------------------------------- migration
    def _to_engine(self, state):
        """Sharded resident state -> global engine-layout :class:`SortState`
        holding exactly this sharding's real lanes, in global lane order.

        The fused :class:`MeshLaneState` interleaves per-shard stream
        padding with real lanes (each device's block is ``lanes_per_shard``
        real lanes padded to the kernel's stream block), so the lane-minor
        axis is walked shard by shard and each shard's padding dropped via
        the exact :func:`repro.core.sort.sort_state_of` inverse.
        """
        if not self._fused:
            return state
        sp_local = state.frame_count.shape[0] // self.shard_count
        parts = []
        for s in range(self.shard_count):
            local = jax.tree.map(
                lambda a, s=s: a[..., s * sp_local:(s + 1) * sp_local],
                state)
            parts.append(sort_state_of(lane_view(local),
                                       self.lanes_per_shard))
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)

    def _from_engine(self, eng_state):
        """Global engine-layout state -> this sharding's resident layout
        (re-inserting the fused path's per-shard stream padding)."""
        if not self._fused:
            return eng_state
        lps = self.lanes_per_shard
        parts = []
        for s in range(self.shard_count):
            local = jax.tree.map(lambda a, s=s: a[s * lps:(s + 1) * lps],
                                 eng_state)
            parts.append(mesh_view(lane_state_of(
                local, self.engine._block_s)))
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=-1), *parts)

    def migrate(self, state, new_sharding: "LaneSharding"):
        """Move the resident state to ``new_sharding``'s lane budget
        (DESIGN.md §8) — same mesh, different width.

        The state crosses widths through the global engine layout using
        the exact layout inverses, so every kept lane (including lanes
        mid-sequence) is bit-identical after the move; appended lanes are
        freshly re-initialised (``core.sort.resize_streams``).  The result
        is re-placed with the new width's ``NamedSharding`` **here**, at
        the chunk boundary — the jitted chunk scan always starts from
        committed lane shardings and never pays a resharding copy
        mid-chunk (``tests/test_autoscale.py`` asserts the placement).
        """
        if new_sharding.mesh is not self.mesh \
                and new_sharding.mesh != self.mesh:
            raise ValueError("migrate() moves state between widths of the "
                             "same mesh, not between meshes")
        eng_state = resize_streams(self._to_engine(state),
                                   new_sharding.num_lanes)
        return new_sharding.place_engine_state(eng_state)

    def place_engine_state(self, eng_state):
        """Global engine-layout state (``num_lanes`` streams) -> this
        sharding's resident layout, placed with its ``NamedSharding`` —
        the entry point for restoring a topology-neutral checkpoint onto
        this mesh (DESIGN.md §11) and the commit half of :meth:`migrate`."""
        new_state = self._from_engine(eng_state)
        self._state_specs = state_pspecs(new_state)
        return jax.device_put(
            new_state, _placement(new_state, self._state_specs, self.mesh))

    # ----------------------------------------------------------- placement
    def place(self, det, dm, active, reset, *extras):
        """Host chunk operands -> device, already lane-sharded.

        ``device_put`` with the matching ``NamedSharding`` scatters each
        host array straight to its owning devices, so the jitted chunk
        consumes committed shardings and never inserts a resharding copy.
        Trailing ``extras`` (``det_class`` / ``det_embed``) are placed by
        the same lane-on-dim-1 rule.
        """
        arrs = (det, dm, active, reset) + extras
        with jax.profiler.TraceAnnotation("lanes.place",
                                          shards=self.shard_count):
            return tuple(
                jax.device_put(np.asarray(a),
                               NamedSharding(self.mesh, _chunk_spec(a.ndim)))
                for a in arrs)
