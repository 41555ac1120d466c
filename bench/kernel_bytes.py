"""Bytes the chunk kernel must move between HBM and the core, from shapes.

``kernels/chunk.py::fused_chunk`` reads the lane-resident state once and
writes it once per chunk (its blocks are revisited across the frame
axis), and streams each frame's operands in and outputs out.  Every
operand is 4 bytes an element (float32 or int32).  ``S`` is the lanes one
chip holds.
"""
from __future__ import annotations

WORD = 4


def fused_chunk(frames: int, lanes: int, trackers: int, dets: int,
                embed_dim: int = 0, assignment_in: bool = True,
                class_in: bool = False) -> int:
    """HBM bytes one ``fused_chunk`` call reads and writes."""
    f, s, t, d, e = frames, lanes, trackers, dets, embed_dim
    state = (7 * t + 49 * t + 7 * t + 2 + e * t) * s
    per_frame_in = (d * 4 + d + 1 + 1) * s          # det, mask, active, reset
    if assignment_in:
        per_frame_in += t * s                        # trk_to_det
    if class_in:
        per_frame_in += d * s                        # det_class
    per_frame_in += d * e * s                        # det_embed
    per_frame_out = (t * 4 + t + t + t + d + t) * s  # boxes uid emit t2d md cls
    return WORD * (2 * state + f * (per_frame_in + per_frame_out))


def fused_chunk_per_chip(config: dict) -> int:
    """The configuration's chunk kernel, at the lanes of one chip."""
    e = config["engine"]
    return fused_chunk(
        frames=config["chunk"], lanes=config["lanes_per_chip"],
        trackers=e["max_trackers"], dets=e["max_detections"],
        embed_dim=e.get("cost", {}).get("embed_dim", 0),
        assignment_in=e["assoc"] == "hungarian",
        class_in=e.get("num_classes", 1) > 1)
