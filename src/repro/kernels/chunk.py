"""Chunk-resident SORT megakernel (Pallas TPU) — one dispatch per CHUNK.

``kernels.frame.fused_frame`` already collapsed each frame to a single
``pallas_call``, but the serving scheduler still dispatches it F times per
chunk from a ``lax.scan``: F kernel launches, and 2x F HBM round-trips for
the ``[49, B]`` covariance block that each launch reads and writes.  With
the paper's extremely small matrices (7x7 state, tiny IoU grids) that
per-launch overhead *is* the cost — so this kernel moves the frame loop
itself inside the ``pallas_call`` (DESIGN.md §9).

Structure: the grid is ``(S // block_s, F)`` with the frame axis as the
**minor** (fastest, sequential) dimension, i.e. an in-kernel frame loop
per stream block.

* **Lane-resident state** (``ref.ChunkState``: means, covariances, the
  int32 lifecycle fields) lives in *revisited output blocks* — their index
  maps are constant over ``f``, so Pallas keeps the block in VMEM across
  all F frames and writes it back to HBM once per stream block, not once
  per frame.  ``@pl.when(f == 0)`` seeds them from the input state refs.
* **Per-frame operands** — the chunk's detections ``[F, D, 4, S]``, det
  masks ``[F, D, S]``, ``stream_active``/``reset`` ``[F, 1, S]``, and the
  optional precomputed ``trk_to_det [F, T, S]`` — use frame-indexed
  BlockSpecs (leading ``None`` squeezes the frame axis), so the standard
  Pallas input pipeline double-buffers frame ``f+1``'s slabs in while
  frame ``f`` computes.
* **Per-frame outputs** (boxes/uid/emit/assignment) are frame-indexed the
  same way and stream out as they are produced.

The body is ``ref.step_chunk_lane`` — the exact serving step (masked lane
re-init + fused frame + lifecycle + emit) in kernel-safe vector algebra —
so the megakernel is bit-identical to F per-frame dispatches.

VMEM per grid step at T=D=16, block_s=128: the resident state is ~1,010
words/lane (x 7x16 + p 49x16 + 7 int slot fields + 2 counters) = ~0.5 MiB
per copy, with the input seed and double-buffering a few copies; the
per-frame slabs (det+masks+t2d in, boxes+ids out) are ~113 KiB each.  The
TPU compiler accepts the kernel with a scoped VMEM limit of 3.6 MiB
(``trk_to_det``), 3.9 MiB (greedy) and 4.0 MiB (3 classes + 8-d embedding)
for v5e, against the 16 MiB default — and the budget is **independent of
chunk size F**: frames stream through the minor grid axis, so only HBM
staging grows with F (~100 KiB/frame).  That is why the chunk can be
arbitrarily long without revisiting the §2.3 budget.

Association (DESIGN.md §6): greedy runs fully in-kernel (masked argmax
rounds are vector algebra).  The Hungarian path keeps PR 3's split,
generalized to chunks: its data-dependent JV augmenting paths stay in a
jitted jnp pre-pass (``kernels.ops.chunk_step``) and this kernel consumes
the precomputed per-frame assignment operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .frame import DEFAULT_BLOCK_S

_N_STATE = len(ref.ChunkState._fields)


def _chunk_kernel(*refs, iou_threshold: float, max_age: int, min_hits: int,
                  assoc: str, has_assoc: bool, has_class: bool,
                  has_embed: bool, cost, num_classes: int):
    # `embed` is ChunkState's LAST field; when the cost has no appearance
    # term the zero-size [0, T, S] leaf is dropped from the operand list
    # (Pallas rejects zero-size blocks) and reconstituted as a dummy here.
    n_state = _N_STATE - (0 if has_embed else 1)
    refs = list(refs)
    st_in = refs[:n_state]
    k = n_state
    det_ref, dm_ref, act_ref, rst_ref = refs[k:k + 4]
    k += 4
    t2d_ref = refs[k] if has_assoc else None
    k += int(has_assoc)
    dc_ref = refs[k] if has_class else None
    k += int(has_class)
    de_ref = refs[k] if has_embed else None
    k += int(has_embed)
    st_out = refs[k:k + n_state]
    (boxes_ref, uid_ref, emit_ref, t2d_out_ref, md_ref,
     cls_ref) = refs[k + n_state:]

    f = pl.program_id(1)

    @pl.when(f == 0)
    def _seed_state():  # revisited blocks start as garbage; seed once
        for i_ref, o_ref in zip(st_in, st_out):
            o_ref[...] = i_ref[...]

    leaves = [r[...] for r in st_out]
    if not has_embed:
        t_dim, bs = leaves[2].shape          # alive [T, block_s]
        leaves.append(jnp.zeros((0, t_dim, bs), leaves[0].dtype))
    state = ref.ChunkState(*leaves)
    state, outs = ref.step_chunk_lane(
        state, det_ref[...], dm_ref[...], act_ref[...], rst_ref[...],
        None if t2d_ref is None else t2d_ref[...],
        None if dc_ref is None else dc_ref[...],
        None if de_ref is None else de_ref[...],
        iou_threshold=iou_threshold, max_age=max_age, min_hits=min_hits,
        assoc=assoc, cost=cost, num_classes=num_classes)
    for o_ref, leaf in zip(st_out, state):   # embed leaf skipped if dropped
        o_ref[...] = leaf
    boxes_ref[...] = outs.boxes
    uid_ref[...] = outs.uid
    emit_ref[...] = outs.emit.astype(jnp.int32)
    t2d_out_ref[...] = outs.trk_to_det
    md_ref[...] = outs.matched_det.astype(jnp.int32)
    cls_ref[...] = outs.cls


@functools.partial(jax.jit, static_argnames=("iou_threshold", "max_age",
                                             "min_hits", "assoc", "block_s",
                                             "interpret", "cost",
                                             "num_classes"))
def fused_chunk(state, det, det_mask, active, reset, trk_to_det=None,
                det_class=None, det_embed=None, *,
                iou_threshold: float = 0.3, max_age: int = 1,
                min_hits: int = 3, assoc: str = "greedy",
                cost=None, num_classes: int = 1,
                block_s: int = DEFAULT_BLOCK_S, interpret: bool = False):
    """F serving steps for every stream in a single dispatch.

    ``state`` is a :class:`repro.kernels.ref.ChunkState` (``S % block_s
    == 0``); per-frame operands are ``det [F, D, 4, S]`` xyxy, ``det_mask
    [F, D, S]`` 0/1 float, ``active [F, 1, S]`` 0/1 float, ``reset
    [F, 1, S]`` 0/1 int, optional precomputed ``trk_to_det [F, T, S]``
    int32 (the fused-Hungarian path; with it the in-kernel association is
    skipped — ``assoc`` then only documents intent).

    ``det_class [F, D, S] int32`` / ``det_embed [F, D, E, S]`` (optional)
    are the pluggable-cost operands (DESIGN.md §10), frame-indexed slabs
    exactly like ``det``; ``cost`` (``core.cost.CostSpec``, static) and
    ``num_classes`` configure the in-kernel score/gate.  The per-track
    embedding block rides in the resident state only when the cost has an
    appearance term — a zero-size ``embed`` leaf is dropped from the
    Pallas operand list and passed through unchanged.

    Returns ``(ChunkState, ChunkOuts)`` with outputs stacked ``[F, ...]``
    (``emit``/``matched_det`` as int32 0/1 — the kernel ABI is numeric;
    ``kernels.ops.chunk_step`` restores bool).
    """
    t, s = state.alive.shape
    f, d = det.shape[0], det.shape[1]
    e = state.embed.shape[0]
    has_embed = e > 0
    has_class = det_class is not None
    assert s % block_s == 0, (s, block_s)
    if has_embed and det_embed is None:
        raise ValueError("state carries an embed block but det_embed is "
                         "missing (cost.embed_dim > 0 needs per-frame "
                         "detection embeddings)")
    if assoc == "hungarian" and trk_to_det is None:
        raise ValueError(
            "the Hungarian megakernel path needs the precomputed trk_to_det"
            " operand (kernels.ops.chunk_step builds it); JV augmenting"
            " paths don't run inside the kernel (DESIGN.md §6/§9)")

    def resident(*dims):
        """State block: constant over the frame axis -> VMEM-revisited."""
        return pl.BlockSpec(dims + (block_s,),
                            lambda i, fr: (0,) * len(dims) + (i,))

    def per_frame(*dims):
        """Frame-f slab: leading None squeezes the frame axis; the index
        map walks it, so the pipeline double-buffers frame f+1's DMA."""
        return pl.BlockSpec((None,) + dims + (block_s,),
                            lambda i, fr: (fr,) + (0,) * len(dims) + (i,))

    # zero-size embed leaf: dropped from the kernel operand/output lists
    # (Pallas rejects zero-size blocks) and passed through unchanged
    state_leaves = list(state)[:-1] if not has_embed else list(state)
    n_state = len(state_leaves)
    state_specs = [resident(7, t), resident(49, t)] + [resident(t)] * 7 + \
                  [resident(1), resident(1)]
    if has_embed:
        state_specs.append(resident(e, t))
    operands = state_leaves + [det, det_mask, active, reset]
    in_specs = state_specs + [per_frame(d, 4), per_frame(d),
                              per_frame(1), per_frame(1)]
    if trk_to_det is not None:
        operands.append(trk_to_det)
        in_specs.append(per_frame(t))
    if has_class:
        operands.append(det_class)
        in_specs.append(per_frame(d))
    if has_embed:
        operands.append(det_embed)
        in_specs.append(per_frame(d, e))

    state_shapes = [jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
                    for leaf in state_leaves]
    out_shapes = state_shapes + [
        jax.ShapeDtypeStruct((f, t, 4, s), state.x.dtype),   # boxes
        jax.ShapeDtypeStruct((f, t, s), jnp.int32),          # uid
        jax.ShapeDtypeStruct((f, t, s), jnp.int32),          # emit
        jax.ShapeDtypeStruct((f, t, s), jnp.int32),          # trk_to_det
        jax.ShapeDtypeStruct((f, d, s), jnp.int32),          # matched_det
        jax.ShapeDtypeStruct((f, t, s), jnp.int32),          # cls
    ]
    out_specs = state_specs + [per_frame(t, 4), per_frame(t), per_frame(t),
                               per_frame(t), per_frame(d), per_frame(t)]

    results = pl.pallas_call(
        functools.partial(_chunk_kernel, iou_threshold=iou_threshold,
                          max_age=max_age, min_hits=min_hits, assoc=assoc,
                          has_assoc=trk_to_det is not None,
                          has_class=has_class, has_embed=has_embed,
                          cost=cost, num_classes=num_classes),
        grid=(s // block_s, f),       # frame axis minor: in-kernel loop
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        name="fused_chunk",           # the op name traces are matched on
    )(*operands)
    out_state_leaves = list(results[:n_state])
    if not has_embed:
        out_state_leaves.append(state.embed)     # pass-through [0, T, S]
    return (ref.ChunkState(*out_state_leaves),
            ref.ChunkOuts(*results[n_state:]))
