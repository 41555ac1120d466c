"""Pure-jnp oracles for the Pallas kernels, in the kernels' *lane layout*.

Layout convention (DESIGN.md §2): the tracker batch axis ``B`` lives on the
TPU lane dimension.  State is ``x [7, B]``, covariance ``p [49, B]`` (row-
major flattened 7x7), observation ``z [4, B]``, mask ``m [1, B]`` (f32 0/1).

These oracles are the ground truth for ``tests/test_kernels.py`` and the
CPU fallback for ``ops.py``.  They are algebraically identical to
``repro.core.kalman`` (which is itself validated against the numpy
reference), just transposed.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# SORT filter constants in lane form -------------------------------------
Q_DIAG = (1.0, 1.0, 1.0, 1.0, 0.01, 0.01, 1e-4)
R_DIAG = (1.0, 1.0, 10.0, 10.0)


def _idx(i: int, j: int) -> int:
    return i * 7 + j


def predict_mean_lane(x: jnp.ndarray) -> jnp.ndarray:
    """The mean half of :func:`predict_lane` — ``x [7, ...]`` only.

    Used standalone by the fused-Hungarian association stage
    (``kernels/ops.py``), which needs the predicted boxes but not the
    covariance: recomputing these 7 rows in plain jnp is free next to
    keeping the 49-row covariance resident in the kernel.
    """
    ds = jnp.where(x[2] + x[6] <= 0.0, 0.0, x[6])
    return jnp.stack([x[0] + x[4], x[1] + x[5], x[2] + ds, x[3],
                      x[4], x[5], ds], axis=0)


def predict_lane(x: jnp.ndarray, p: jnp.ndarray):
    """Constant-velocity predict on lane layout. ``x [7,B]``, ``p [49,B]``."""
    x_new = predict_mean_lane(x)

    def fp(i, j):  # (F P F^T)[i, j] exploiting F = I + shift(0..2 -> 4..6)
        v = p[_idx(i, j)]
        if i < 3:
            v = v + p[_idx(i + 4, j)]
        if j < 3:
            v = v + p[_idx(i, j + 4)]
        if i < 3 and j < 3:
            v = v + p[_idx(i + 4, j + 4)]
        return v

    rows = [fp(i, j) + (Q_DIAG[i] if i == j else 0.0)
            for i in range(7) for j in range(7)]
    return x_new, jnp.stack(rows, axis=0)


def predict_cov4_lane(p: jnp.ndarray):
    """Top-left 4x4 block of the *predicted* covariance, from the
    pre-predict ``p [49, ...]`` — as nested ``[[...]]`` lists of lane
    arrays (the form ``core.cost`` consumes for the Mahalanobis gate).

    This is :func:`predict_lane`'s ``fp`` recurrence restricted to
    ``i, j < 4``, with the identical accumulation order, so each entry is
    bit-identical to row ``_idx(i, j)`` of the predicted covariance.  The
    fused-Hungarian pre-pass (``kernels/ops.py``) uses it to evaluate the
    gate *outside* the kernel on exactly the floats the in-kernel
    ``frame_lane`` path sees post-predict — the dispatch-mode bit-parity
    contract of ``tests/test_oracle_parity.py``.
    """
    def fp(i, j):
        v = p[_idx(i, j)]
        if i < 3:
            v = v + p[_idx(i + 4, j)]
        if j < 3:
            v = v + p[_idx(i, j + 4)]
        if i < 3 and j < 3:
            v = v + p[_idx(i + 4, j + 4)]
        return v

    return [[fp(i, j) + (Q_DIAG[i] if i == j else 0.0) for j in range(4)]
            for i in range(4)]


def _inv2(m00, m01, m10, m11):
    det = m00 * m11 - m01 * m10
    inv = 1.0 / det
    return m11 * inv, -m01 * inv, -m10 * inv, m00 * inv


def update_lane(x: jnp.ndarray, p: jnp.ndarray, z: jnp.ndarray,
                mask: jnp.ndarray):
    """Masked measurement update on lane layout.

    ``x [7,B]``, ``p [49,B]``, ``z [4,B]``, ``mask [1,B]`` (0/1 f32).
    """
    y = [z[i] - x[i] for i in range(4)]
    # S = P[0:4, 0:4] + diag(R)
    s = [[p[_idx(i, j)] + (R_DIAG[i] if i == j else 0.0)
          for j in range(4)] for i in range(4)]
    sinv = _inv4(s)
    # K = P[:, 0:4] @ Sinv  -> [7][4] of (B,) vectors
    k = [[sum(p[_idx(i, kk)] * sinv[kk][j] for kk in range(4))
          for j in range(4)] for i in range(7)]
    x_new = jnp.stack(
        [x[i] + sum(k[i][j] * y[j] for j in range(4)) for i in range(7)], 0)
    # P_new = (I - K H) P ;  (K H)[i, j] = K[i, j] for j < 4 else 0
    p_new = jnp.stack(
        [p[_idx(i, j)] - sum(k[i][kk] * p[_idx(kk, j)] for kk in range(4))
         for i in range(7) for j in range(7)], 0)
    m = mask[0]
    return (m * x_new + (1.0 - m) * x), (m * p_new + (1.0 - m) * p)


def _inv4(s):
    """Blockwise inverse of SPD 4x4 given as [[ (B,) x4 ] x4]."""
    a00, a01, a10, a11 = s[0][0], s[0][1], s[1][0], s[1][1]
    b00, b01, b10, b11 = s[0][2], s[0][3], s[1][2], s[1][3]
    c00, c01, c10, c11 = s[2][0], s[2][1], s[3][0], s[3][1]
    d00, d01, d10, d11 = s[2][2], s[2][3], s[3][2], s[3][3]
    ai00, ai01, ai10, ai11 = _inv2(a00, a01, a10, a11)
    # C A^-1 (2x2)
    ca00 = c00 * ai00 + c01 * ai10
    ca01 = c00 * ai01 + c01 * ai11
    ca10 = c10 * ai00 + c11 * ai10
    ca11 = c10 * ai01 + c11 * ai11
    # A^-1 B (2x2)
    ab00 = ai00 * b00 + ai01 * b10
    ab01 = ai00 * b01 + ai01 * b11
    ab10 = ai10 * b00 + ai11 * b10
    ab11 = ai10 * b01 + ai11 * b11
    # Schur = D - C A^-1 B
    s00 = d00 - (ca00 * b00 + ca01 * b10)
    s01 = d01 - (ca00 * b01 + ca01 * b11)
    s10 = d10 - (ca10 * b00 + ca11 * b10)
    s11 = d11 - (ca10 * b01 + ca11 * b11)
    si00, si01, si10, si11 = _inv2(s00, s01, s10, s11)
    # TL = Ai + AB @ Si @ CA ; TR = -AB @ Si ; BL = -Si @ CA ; BR = Si
    absi00 = ab00 * si00 + ab01 * si10
    absi01 = ab00 * si01 + ab01 * si11
    absi10 = ab10 * si00 + ab11 * si10
    absi11 = ab10 * si01 + ab11 * si11
    tl00 = ai00 + absi00 * ca00 + absi01 * ca10
    tl01 = ai01 + absi00 * ca01 + absi01 * ca11
    tl10 = ai10 + absi10 * ca00 + absi11 * ca10
    tl11 = ai11 + absi10 * ca01 + absi11 * ca11
    tr00, tr01 = -absi00, -absi01
    tr10, tr11 = -absi10, -absi11
    bl00 = -(si00 * ca00 + si01 * ca10)
    bl01 = -(si00 * ca01 + si01 * ca11)
    bl10 = -(si10 * ca00 + si11 * ca10)
    bl11 = -(si10 * ca01 + si11 * ca11)
    return [[tl00, tl01, tr00, tr01],
            [tl10, tl11, tr10, tr11],
            [bl00, bl01, si00, si01],
            [bl10, bl11, si10, si11]]


_EPS = 1e-9


def z_to_xyxy_lane(x: jnp.ndarray) -> jnp.ndarray:
    """Lane-layout ``bbox.z_to_xyxy``: ``x [>=4, ...]`` -> boxes ``[..., 4]``
    stacked on a *new* axis 1 when input is ``[7, T, B]`` -> ``[T, 4, B]``."""
    u, v = x[0], x[1]
    s = jnp.maximum(x[2], 0.0)
    r = jnp.maximum(x[3], _EPS)
    w = jnp.sqrt(s * r)
    h = s / jnp.maximum(w, _EPS)
    half_w, half_h = w / 2.0, h / 2.0
    return jnp.stack([u - half_w, v - half_h, u + half_w, v + half_h],
                     axis=1 if x.ndim == 3 else 0)


def xyxy_to_z_lane(box: jnp.ndarray) -> jnp.ndarray:
    """Lane-layout ``bbox.xyxy_to_z``: ``box [D, 4, B]`` -> ``z [4, D, B]``."""
    x1, y1, x2, y2 = box[:, 0], box[:, 1], box[:, 2], box[:, 3]
    w = x2 - x1
    h = y2 - y1
    u = x1 + w / 2.0
    v = y1 + h / 2.0
    s = w * h
    r = w / jnp.maximum(h, _EPS)
    return jnp.stack([u, v, s, r], axis=0)


def frame_lane(x: jnp.ndarray, p: jnp.ndarray, det: jnp.ndarray,
               det_mask: jnp.ndarray, alive: jnp.ndarray,
               iou_threshold: float = 0.3,
               active: jnp.ndarray | None = None,
               assoc: str = "greedy",
               trk_to_det: jnp.ndarray | None = None,
               det_class: jnp.ndarray | None = None,
               trk_cls: jnp.ndarray | None = None,
               det_embed: jnp.ndarray | None = None,
               trk_embed: jnp.ndarray | None = None,
               cost=None, num_classes: int = 1):
    """One whole SORT frame (predict -> IoU -> assign -> masked update) as
    pure lane-layout vector algebra — the oracle for the single-dispatch
    ``kernels.frame.fused_frame`` Pallas kernel.

    Shapes (DESIGN.md §2; streams on lanes, tracker slots on sublanes):
    ``x [7, T, S]``, ``p [49, T, S]``, ``det [D, 4, S]`` xyxy,
    ``det_mask [D, S]`` (bool or 0/1 float), ``alive [T, S]``.

    ``active [1, S]`` (bool or 0/1 float, optional) is the ragged-stream
    lane mask (DESIGN.md §3): lanes with ``active == 0`` are exact no-ops —
    their detections are masked out (no matches, so ``trk_to_det == -1``
    and ``matched_det == False`` fall out of the association gate) and
    their state is restored after predict/update, bit-identical to never
    having run the frame.

    ``assoc`` selects the association algorithm (DESIGN.md §6):
    ``"greedy"`` (best-first masked argmax rounds) or ``"hungarian"``
    (lane-batched JV solve, ``core.association.associate_lane`` — the
    paper's algorithm).  Alternatively ``trk_to_det [T, S] int32`` supplies
    a *precomputed* assignment and skips the IoU/association phases
    entirely: this is how the Pallas kernel body consumes the fused-
    Hungarian path, whose JV solve runs as a jitted stage **outside** the
    kernel (data-dependent augmenting paths don't vectorize over lanes)
    while predict and update stay resident.

    ``cost`` (a ``core.cost.CostSpec``) + ``num_classes`` activate the
    pluggable association cost (DESIGN.md §10) with its lane-major
    operands: ``det_class [D, S]`` / ``trk_cls [T, S]`` int32 for the
    class partition, ``det_embed [D, E, S]`` / ``trk_embed [E, T, S]``
    for the appearance term.  Score/feasibility are evaluated on the
    *post-predict* state, then feed the same association entry points —
    ``cost=None`` (or the pure-IoU single-class spec) leaves every solver
    argument byte-identical to the pre-cost path.

    Returns ``(x, p, trk_to_det [T, S] int32, matched_det [D, S] bool)``.
    Tracker lifecycle (tick/birth) stays outside: it is integer bookkeeping
    off the covariance hot path.
    """
    from repro.core.greedy import greedy_assign_lane

    x_in, p_in = x, p
    if active is not None:
        det_mask = det_mask * (active > 0)                  # [D,S] & [1,S]
    x, p = predict_lane(x, p)                               # [7,T,S], [49,T,S]
    if trk_to_det is not None:
        # precomputed assignment (already gated): a matching, so matched
        # detections are exactly the assigned values >= 0.  One int32 row
        # per detection, reduced over the slot axis: Mosaic lays out no
        # reshape, stack or cross-sublane reduction of a bool array.
        rows = [jnp.max((trk_to_det == di).astype(jnp.int32), axis=0,
                        keepdims=True) for di in range(det.shape[0])]
        matched_det = jnp.concatenate(rows, axis=0) > 0
    else:
        trk_boxes = z_to_xyxy_lane(x[:4])                   # [T, 4, S]
        iou = iou_lane(det, trk_boxes)                      # [D, T, S]
        score = feasible = None
        if cost is not None:
            from repro.core import cost as cost_mod
            if (cost_mod.needs_score(cost)
                    or cost_mod.needs_feasible(cost, num_classes)):
                p4 = ([[p[_idx(i, j)] for j in range(4)] for i in range(4)]
                      if cost.uses_maha else None)
                score, feasible = cost_mod.score_and_feasible_lane(
                    iou, cost, num_classes=num_classes,
                    det_class=det_class, trk_cls=trk_cls,
                    det_embed=det_embed, trk_embed=trk_embed,
                    z_det=xyxy_to_z_lane(det) if cost.uses_maha else None,
                    x_pred=x, p4_pred=p4)
        if assoc == "hungarian":
            from repro.core.association import associate_lane
            trk_to_det, matched_det = associate_lane(
                iou, det_mask, alive, iou_threshold,
                score=score, feasible=feasible)
        elif assoc == "greedy":
            trk_to_det, matched_det = greedy_assign_lane(
                iou, det_mask, alive, iou_threshold,
                score=score, feasible=feasible)
        else:
            raise ValueError(f"unknown assoc {assoc!r}")
    # gather each matched tracker's observation via one-hot contraction
    # over D (D <= ~16, trace-time unrolled; no per-lane dynamic gather)
    z_all = xyxy_to_z_lane(det)                             # [4, D, S]
    d = det.shape[0]
    z_trk = jnp.zeros_like(x[:4])                           # [4, T, S]
    for di in range(d):
        sel = (trk_to_det == di)[None]                      # [1, T, S]
        z_trk = jnp.where(sel, z_all[:, di][:, None], z_trk)
    mask = (trk_to_det >= 0).astype(x.dtype)[None]          # [1, T, S]
    x, p = update_lane(x, p, z_trk, mask)
    if active is not None:
        keep = (active > 0)[:, None]                        # [1, 1, S]
        x = jnp.where(keep, x, x_in)
        p = jnp.where(keep, p, p_in)
    return x, p, trk_to_det, matched_det


# ------------------------------------------------------------------------
# Chunk-resident execution (DESIGN.md §9): the whole serving step — masked
# lane re-init, fused frame, tracker lifecycle, emit — as kernel-safe
# lane-layout vector algebra, so the megakernel (`kernels.chunk.fused_chunk`)
# can unroll it once per frame of its in-kernel frame loop and stay
# bit-identical to F per-frame dispatches of `core.sort`'s scan.
# ------------------------------------------------------------------------
class ChunkState(NamedTuple):
    """Per-lane SORT state as a flat bundle of numeric arrays — the carried
    state of the chunk-resident megakernel (DESIGN.md §9).

    ``core.sort.LaneSortState`` nests a bool-typed ``SlotPool`` and mixes
    per-stream scalars; a Pallas kernel wants one flat tuple of >=2-D
    numeric operands with a uniform lane axis.  Every lifecycle field is
    int32 (``alive`` included: 0/1), per-stream counters carry a leading
    unit sublane axis: ``x [7, T, S]``, ``p [49, T, S]``, slot fields
    ``[T, S]``, ``next_uid``/``frame_count`` ``[1, S]``.
    ``core.sort.chunk_state_of`` / ``lane_state_of_chunk`` convert exactly.

    ``embed`` is the per-track appearance embedding (DESIGN.md §10),
    ``[E, T, S]`` with ``E = cost.embed_dim`` — a zero-size ``[0, T, S]``
    array when the cost has no appearance term.  It sits *last* so the
    megakernel can drop it from the Pallas operand list when unused
    (``kernels/chunk.py``) without renumbering the other state blocks.
    """

    x: jnp.ndarray                  # [7, T, S]  Kalman means
    p: jnp.ndarray                  # [49, T, S] covariances
    alive: jnp.ndarray              # [T, S] int32 0/1
    age: jnp.ndarray                # [T, S] int32
    hits: jnp.ndarray               # [T, S] int32
    hit_streak: jnp.ndarray         # [T, S] int32
    time_since_update: jnp.ndarray  # [T, S] int32
    uid: jnp.ndarray                # [T, S] int32, -1 when dead
    cls: jnp.ndarray                # [T, S] int32 class, -1 when dead
    next_uid: jnp.ndarray           # [1, S] int32
    frame_count: jnp.ndarray        # [1, S] int32
    embed: jnp.ndarray              # [E, T, S] appearance embeddings


class ChunkOuts(NamedTuple):
    """Per-frame outputs of the chunk body; stacked ``[F, ...]`` by
    :func:`chunk_lane` / the megakernel's frame-indexed output blocks."""

    boxes: jnp.ndarray        # [T, 4, S]
    uid: jnp.ndarray          # [T, S] int32
    emit: jnp.ndarray         # [T, S] bool (int32 across the kernel ABI)
    trk_to_det: jnp.ndarray   # [T, S] int32
    matched_det: jnp.ndarray  # [D, S] bool (int32 across the kernel ABI)
    cls: jnp.ndarray          # [T, S] int32 track class, -1 when dead


def assign_slots_lane_unrolled(free_mask: jnp.ndarray,
                               want_mask: jnp.ndarray) -> jnp.ndarray:
    """Kernel-safe ``slots.assign_slots_lane``: the same rank matching
    (the k-th claimant takes the k-th free slot, -1 when the pool is
    exhausted) as a trace-time-unrolled pass over the claimants, each
    taking the lowest-index slot still free — instead of cumsum +
    scatter + ``take_along_axis``, which don't lower inside a Pallas TPU
    kernel body.  ``free [T, ...]`` bool, ``want [D, ...]`` bool ->
    ``slot_for [D, ...] int32``; integer-exact vs the scatter version
    (``tests/test_lane.py`` locks the equivalence).
    """
    t = free_mask.shape[0]
    ti_iota = jax.lax.broadcasted_iota(jnp.int32, free_mask.shape, 0)
    free = free_mask
    rows = []
    for di in range(want_mask.shape[0]):
        first = jnp.min(jnp.where(free, ti_iota, t), axis=0,
                        keepdims=True)                       # [1, ...]
        slot = jnp.where(want_mask[di:di + 1] & (first < t), first, -1)
        free = free & (ti_iota != slot)
        rows.append(slot)
    return jnp.concatenate(rows, axis=0)


def step_chunk_lane(state: ChunkState, det: jnp.ndarray,
                    det_mask: jnp.ndarray, active: jnp.ndarray,
                    reset: jnp.ndarray,
                    trk_to_det: Optional[jnp.ndarray] = None,
                    det_class: Optional[jnp.ndarray] = None,
                    det_embed: Optional[jnp.ndarray] = None, *,
                    iou_threshold: float = 0.3, max_age: int = 1,
                    min_hits: int = 3, assoc: str = "greedy",
                    cost=None, num_classes: int = 1):
    """One serving step of the chunk-resident body (DESIGN.md §9).

    Replicates, op for op, what the serving scan runs per frame —
    ``core.sort.reset_ragged`` followed by ``SortEngine.lane_step``
    (masked lane re-init, fused predict/IoU/assign/update, tick, births,
    inactive-lane freeze, emit) — restricted to operations that lower
    inside a Pallas TPU kernel body, so the megakernel that runs this
    once per frame of its in-kernel loop is bit-identical to F per-frame
    dispatches.

    ``det [D, 4, S]`` xyxy, ``det_mask [D, S]`` 0/1 in state dtype,
    ``active [1, S]`` 0/1 in state dtype, ``reset [1, S]`` 0/1 numeric;
    ``trk_to_det [T, S] int32`` (optional) is the precomputed association
    for the fused-Hungarian path (see :func:`frame_lane`).
    ``det_class [D, S] int32`` / ``det_embed [D, E, S]`` (optional) are
    the pluggable-cost operands (DESIGN.md §10); with a multi-term
    ``cost`` / ``num_classes`` they feed the in-step score/gate, stamp
    births (class, embedding) and refresh matched tracks' embeddings —
    in the *same unrolled per-detection order* as the per-frame engine
    path (``core.sort.SortEngine.lane_step``), keeping chunk vs frame
    dispatch bit-identical.
    Returns ``(ChunkState, ChunkOuts)``.
    """
    from repro.core import kalman, slots

    dt = state.x.dtype
    t = state.alive.shape[0]
    d = det.shape[0]
    # per-lane flags stay [1, S] and per-detection rows are unit-row
    # slices: Mosaic lays out no bool array that is reshaped, stacked or
    # cut down to a 1-D row, so masks are compared only where a select
    # consumes them
    act = active > 0                                         # [1, S]
    rst = reset > 0                                          # [1, S]

    # masked lane re-init (reset_lanes semantics, uid_start=1): a recycled
    # lane and its admitted sequence's first frame share the step.  The
    # initial covariance enters as 49 scalar selects, not a [49] array —
    # Pallas kernel bodies may not capture non-scalar constants, and the
    # scalar path is bit-identical (every entry is exactly representable).
    p0 = tuple(float(v) for v in
               kalman.initial_covariance_np().astype(dt).reshape(49))
    x = jnp.where(rst[None], jnp.zeros((), dt), state.x)
    p = jnp.stack([jnp.where(rst, v, state.p[i])
                   for i, v in enumerate(p0)], axis=0)
    e = state.embed.shape[0]
    emb = state.embed
    if e > 0:
        emb = jnp.where(rst[None], jnp.zeros((), dt), emb)
    zero = jnp.zeros((), jnp.int32)
    alive0 = (state.alive > 0) & ~rst
    pool0 = slots.SlotPool(
        alive=alive0,
        age=jnp.where(rst, zero, state.age),
        hits=jnp.where(rst, zero, state.hits),
        hit_streak=jnp.where(rst, zero, state.hit_streak),
        time_since_update=jnp.where(rst, zero, state.time_since_update),
        uid=jnp.where(rst, -1, state.uid),
        cls=jnp.where(rst, -1, state.cls),
        next_uid=jnp.where(rst, 1, state.next_uid),          # [1, S]
    )
    fc0 = jnp.where(rst, zero, state.frame_count)            # [1, S]

    # 1-3. fused predict + IoU + assign + masked update — the same body
    # the per-frame kernel runs (inactive lanes restored inside)
    x, p, t2d, matched = frame_lane(
        x, p, det, det_mask, alive0.astype(dt), iou_threshold,
        active=active, assoc=assoc, trk_to_det=trk_to_det,
        det_class=det_class, trk_cls=pool0.cls,
        det_embed=det_embed, trk_embed=emb,
        cost=cost, num_classes=num_classes)

    # 4a. age & kill (elementwise)
    pool = slots.tick(pool0, t2d >= 0, max_age)

    # 4b. births from unmatched detections into free slots (kernel-safe
    # rank matching + an unrolled one-hot select per detection over the
    # [T, S] slot grid; claimed slots are distinct, so at most one
    # detection selects each slot and the order of the selects is moot)
    unmatched = (det_mask > 0) & ~matched & act
    slot_for = assign_slots_lane_unrolled(~pool.alive, unmatched)
    z_det = xyxy_to_z_lane(det)                              # [4, D, S]
    ti_iota = jax.lax.broadcasted_iota(jnp.int32, pool.uid.shape, 0)
    n_born = jnp.zeros_like(pool.next_uid)                   # claimants < di
    born = jnp.zeros_like(pool.uid)                          # [T, S] 0/1
    uid, cls = pool.uid, pool.cls
    zb = jnp.zeros((4,) + pool.uid.shape, dt)
    for di in range(d):
        slot_di = slot_for[di:di + 1]                        # [1, S]
        sel = slot_di == ti_iota                             # [T, S]
        born = jnp.where(sel, 1, born)
        uid = jnp.where(sel, pool.next_uid + n_born, uid)
        cls = jnp.where(sel, zero if det_class is None
                        else det_class[di:di + 1], cls)
        zb = jnp.where(sel[None], z_det[:, di:di + 1], zb)
        n_born = n_born + (slot_di >= 0).astype(jnp.int32)
    is_born = born > 0
    pool = slots.SlotPool(
        alive=pool.alive | is_born,
        age=jnp.where(is_born, zero, pool.age),
        hits=jnp.where(is_born, zero, pool.hits),
        hit_streak=jnp.where(is_born, zero, pool.hit_streak),
        time_since_update=jnp.where(is_born, zero, pool.time_since_update),
        uid=uid,
        cls=cls,
        next_uid=pool.next_uid + n_born,
    )
    x_init = jnp.concatenate([zb, jnp.zeros((3,) + zb.shape[1:], dt)], 0)
    x = jnp.where(is_born[None], x_init, x)
    p = jnp.stack([jnp.where(is_born, v, p[i]) for i, v in enumerate(p0)],
                  axis=0)

    # embedding refresh: matched tracks take their matched detection's
    # embedding (replace), born tracks their claiming detection's — the
    # same unrolled per-detection loop order as the per-frame engine path
    # (`SortEngine.lane_step`), for chunk-vs-frame bit parity.
    if e > 0 and det_embed is not None:
        for di in range(d):
            m_sel = (t2d == di)[None]                        # [1, T, S]
            emb = jnp.where(m_sel, det_embed[di][:, None], emb)
        for di in range(d):
            b_sel = (slot_for[di:di + 1] == ti_iota)[None]   # [1, T, S]
            emb = jnp.where(b_sel, det_embed[di][:, None], emb)

    # inactive lanes: lifecycle freezes (x/p were restored inside
    # frame_lane; births can't fire — `unmatched` was gated by act)
    def sel(new, old):
        if new.dtype == jnp.bool_:    # Mosaic selects no bool operands
            return (act & new) | (~act & old)
        return jnp.where(act, new, old)

    pool = slots.SlotPool(*(sel(new, old) for new, old in zip(pool, pool0)))
    fc = fc0 + act.astype(jnp.int32)                         # [1, S]

    # 5. emit: updated this frame AND (probation passed OR warmup)
    warmup = fc <= min_hits                                  # [1, S]
    emit = (pool.alive & (pool.time_since_update < 1)
            & ((pool.hit_streak >= min_hits) | warmup) & act)
    new_state = ChunkState(
        x=x, p=p, alive=pool.alive.astype(jnp.int32), age=pool.age,
        hits=pool.hits, hit_streak=pool.hit_streak,
        time_since_update=pool.time_since_update, uid=pool.uid,
        cls=pool.cls, next_uid=pool.next_uid, frame_count=fc,
        embed=emb)
    outs = ChunkOuts(boxes=z_to_xyxy_lane(x[:4]), uid=pool.uid, emit=emit,
                     trk_to_det=t2d, matched_det=matched, cls=pool.cls)
    return new_state, outs


def chunk_lane(state: ChunkState, det: jnp.ndarray, det_mask: jnp.ndarray,
               active: jnp.ndarray, reset: jnp.ndarray,
               trk_to_det: Optional[jnp.ndarray] = None,
               det_class: Optional[jnp.ndarray] = None,
               det_embed: Optional[jnp.ndarray] = None, *,
               iou_threshold: float = 0.3, max_age: int = 1,
               min_hits: int = 3, assoc: str = "greedy",
               cost=None, num_classes: int = 1):
    """Chunk-level oracle: scan :func:`step_chunk_lane` over the frame
    axis — the ground truth for ``kernels.chunk.fused_chunk`` and the
    non-TPU execution path of ``kernels.ops.chunk_step``.

    ``det [F, D, 4, S]``, ``det_mask [F, D, S]``, ``active``/``reset``
    ``[F, 1, S]``, optional ``trk_to_det [F, T, S] int32``,
    ``det_class [F, D, S] int32``, ``det_embed [F, D, E, S]``.  Returns
    ``(ChunkState, ChunkOuts stacked over F)``.
    """
    present = [a is not None for a in (trk_to_det, det_class, det_embed)]

    def body(st, inp):
        d_, m_, a_, r_ = inp[:4]
        it = iter(inp[4:])
        t2, dc, de = (next(it) if has else None for has in present)
        return step_chunk_lane(st, d_, m_, a_, r_, t2, dc, de,
                               iou_threshold=iou_threshold, max_age=max_age,
                               min_hits=min_hits, assoc=assoc,
                               cost=cost, num_classes=num_classes)

    xs = (det, det_mask, active, reset) + tuple(
        a for a in (trk_to_det, det_class, det_embed) if a is not None)
    return jax.lax.scan(body, state, xs)


def iou_lane(det: jnp.ndarray, trk: jnp.ndarray) -> jnp.ndarray:
    """IoU on lane layout: ``det [D, 4, B]``, ``trk [T, 4, B]`` -> ``[D, T, B]``."""
    d, t = det.shape[0], trk.shape[0]
    rows = []
    for i in range(d):
        for j in range(t):
            ax1, ay1, ax2, ay2 = det[i, 0], det[i, 1], det[i, 2], det[i, 3]
            bx1, by1, bx2, by2 = trk[j, 0], trk[j, 1], trk[j, 2], trk[j, 3]
            iw = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0.0)
            ih = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0.0)
            inter = iw * ih
            ua = jnp.maximum(ax2 - ax1, 0.0) * jnp.maximum(ay2 - ay1, 0.0)
            ub = jnp.maximum(bx2 - bx1, 0.0) * jnp.maximum(by2 - by1, 0.0)
            rows.append(inter / jnp.maximum(ua + ub - inter, 1e-9))
    return jnp.stack(rows, 0).reshape(d, t, -1)
