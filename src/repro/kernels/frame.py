"""Fused whole-frame SORT kernel (Pallas TPU) — one dispatch per frame.

The per-phase kernels in ``kalman_fused``/``iou_cost`` already collapse the
paper's ~15 tiny BLAS calls per tracker (Table IV) into three dispatches,
but the engine still pays launch + HBM round-trip overhead *between* them:
predicted state goes back to HBM, comes back in for the IoU kernel, the
cost matrix goes out, comes back for the update.  This kernel is the
paper's fusion argument taken to its limit: predict -> IoU cost -> greedy
association -> masked update execute in a **single** ``pallas_call`` with
the whole filter block resident in VMEM (DESIGN.md §2.3).

Layout: streams on lanes, tracker slots on sublane-tiled leading axes —
``x [7, T, S]``, ``p [49, T, S]``, ``det [D, 4, S]``, masks ``[*, S]``.
The grid is 1-D over stream blocks of ``block_s`` lanes; every phase is
trace-time-unrolled vector algebra over the block (the greedy rounds are
``min(D, T)`` masked argmaxes), so the MXU is never touched — contraction
dims are 4 and 7, the paper's "extremely small matrices".

VMEM per grid step at T=D=16, block_s=128: the state block is
(7+49)*16*128*4B = 448 KiB per copy, in and out, double-buffered.  The
TPU compiler accepts the kernel with a scoped VMEM limit of 2.9 MiB
(``trk_to_det``), 3.1 MiB (greedy) and 3.3 MiB (3 classes + 8-d
embedding) for v5e — well under the 16 MiB default.

Association (DESIGN.md §6): greedy (``core.greedy.greedy_assign_lane``)
runs *inside* the kernel — ``min(D, T)`` masked argmax rounds are plain
vector algebra.  The Hungarian solver's data-dependent augmenting paths do
not vectorize over lanes, so the paper-exact fused path
(``kernels/ops.py::frame_step(assoc="hungarian")``) instead solves the
lane-batched JV stage in jitted jnp *between* dispatch and kernel — the
precomputed ``trk_to_det`` enters this kernel as one extra ``[T, S]``
int32 operand and the predict/update phases stay resident: the ``[49, B]``
covariance still makes exactly one HBM round-trip per frame.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .kalman_fused import lane_spec

DEFAULT_BLOCK_S = 128


def _frame_kernel(x_ref, p_ref, det_ref, dm_ref, alive_ref, *refs,
                  iou_threshold: float, has_active: bool, has_assoc: bool,
                  has_class: bool, has_embed: bool, cost, num_classes: int):
    refs = list(refs)
    active = refs.pop(0)[...] if has_active else None
    t2d_in = refs.pop(0)[...] if has_assoc else None
    det_class = refs.pop(0)[...] if has_class else None
    trk_cls = refs.pop(0)[...] if has_class else None
    det_embed = refs.pop(0)[...] if has_embed else None
    trk_embed = refs.pop(0)[...] if has_embed else None
    xo_ref, po_ref, t2d_ref, md_ref = refs
    x, p, t2d, md = ref.frame_lane(
        x_ref[...], p_ref[...], det_ref[...], dm_ref[...], alive_ref[...],
        iou_threshold, active=active, trk_to_det=t2d_in,
        det_class=det_class, trk_cls=trk_cls,
        det_embed=det_embed, trk_embed=trk_embed,
        cost=cost, num_classes=num_classes)
    xo_ref[...] = x
    po_ref[...] = p
    t2d_ref[...] = t2d
    md_ref[...] = md.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("iou_threshold", "block_s", "interpret",
                                    "cost", "num_classes"))
def fused_frame(x, p, det, det_mask, alive, stream_active=None,
                trk_to_det=None, det_class=None, trk_cls=None,
                det_embed=None, trk_embed=None, *,
                iou_threshold: float = 0.3, cost=None, num_classes: int = 1,
                block_s: int = DEFAULT_BLOCK_S, interpret: bool = False):
    """One SORT frame for every stream in a single dispatch.

    ``x [7, T, S]``, ``p [49, T, S]``, ``det [D, 4, S]`` xyxy,
    ``det_mask [D, S]`` 0/1 float, ``alive [T, S]`` 0/1 float;
    ``S % block_s == 0``.  ``stream_active [1, S]`` 0/1 float (optional)
    is the ragged-stream lane mask (DESIGN.md §3): inactive lanes pass
    through the kernel as exact no-ops, so finished sequences cost no
    extra dispatch while they wait for a recycled admission.

    ``trk_to_det [T, S] int32`` (optional) is a precomputed, already-gated
    assignment (DESIGN.md §6): the kernel then skips its in-VMEM IoU +
    greedy phases and runs predict -> gather-by-assignment -> masked
    update — the fused-Hungarian path, whose JV solve stage ran outside.

    ``cost`` (``core.cost.CostSpec``, static) + ``num_classes`` activate
    the pluggable association score/gate (DESIGN.md §10) with its
    conditional lane operands — ``det_class [D, S]`` / ``trk_cls [T, S]``
    int32 and ``det_embed [D, E, S]`` / ``trk_embed [E, T, S]`` — each a
    block-sliced VMEM input only when present, exactly like
    ``stream_active``/``trk_to_det``.
    Returns ``(x, p, trk_to_det [T, S] int32, matched_det [D, S] int32)``.
    """
    t, s = x.shape[1], x.shape[2]
    d = det.shape[0]
    assert s % block_s == 0, (s, block_s)
    has_class = det_class is not None
    has_embed = det_embed is not None
    assert has_class == (trk_cls is not None)
    assert has_embed == (trk_embed is not None)

    def spec3(a, b):
        return pl.BlockSpec((a, b, block_s), lambda i: (0, 0, i))

    operands = [x, p, det, det_mask, alive]
    in_specs = [spec3(7, t), spec3(49, t), spec3(d, 4),
                lane_spec(d, block_s), lane_spec(t, block_s)]
    if stream_active is not None:
        operands.append(stream_active)
        in_specs.append(lane_spec(1, block_s))
    if trk_to_det is not None:
        operands.append(trk_to_det)
        in_specs.append(lane_spec(t, block_s))
    if has_class:
        operands += [det_class, trk_cls]
        in_specs += [lane_spec(d, block_s), lane_spec(t, block_s)]
    if has_embed:
        e = det_embed.shape[1]
        operands += [det_embed, trk_embed]
        in_specs += [spec3(d, e), spec3(e, t)]

    return pl.pallas_call(
        functools.partial(_frame_kernel, iou_threshold=iou_threshold,
                          has_active=stream_active is not None,
                          has_assoc=trk_to_det is not None,
                          has_class=has_class, has_embed=has_embed,
                          cost=cost, num_classes=num_classes),
        grid=(s // block_s,),
        in_specs=in_specs,
        out_specs=[spec3(7, t), spec3(49, t),
                   lane_spec(t, block_s), lane_spec(d, block_s)],
        out_shape=[jax.ShapeDtypeStruct((7, t, s), x.dtype),
                   jax.ShapeDtypeStruct((49, t, s), p.dtype),
                   jax.ShapeDtypeStruct((t, s), jnp.int32),
                   jax.ShapeDtypeStruct((d, s), jnp.int32)],
        interpret=interpret,
    )(*operands)
