"""The comparison that decides ``correct``.

Delivered tracks are compared, frame by frame, with the plain reference
(``bench/reference.py``) run over the very detections that were
submitted.  Two numbers come out of it:

* ``id_mismatch_frames``: frames whose emitted track ids (and, with a
  class partition, their classes) differ from the reference's;
* ``box_err_px``: the widest gap, in pixels, between an emitted box and
  the reference's box of the same track.

Each configuration states its limits (``limits`` in its file).
"""
from __future__ import annotations

import numpy as np

from bench.reference import Cost, Sort

NOT_FINITE = 1e30       # a box error that is NaN or infinite reads as this


def reference_for(config: dict, rounding: str = "f64") -> Sort:
    e = config["engine"]
    c = e.get("cost", {})
    return Sort(max_age=e["max_age"], min_hits=e["min_hits"],
                iou_threshold=e["iou_threshold"], assoc=e["assoc"],
                cost=Cost(iou_weight=c.get("iou_weight", 1.0),
                          maha_gate=c.get("maha_gate"),
                          embed_weight=c.get("embed_weight", 0.0),
                          embed_dim=c.get("embed_dim", 0)),
                num_classes=e.get("num_classes", 1), rounding=rounding,
                max_trackers=e["max_trackers"])


def reference_frames(config: dict, sub, rounding: str = "f64"):
    """The reference's output for one submission: per frame, ``{uid:
    (box [4], cls)}``."""
    ref = reference_for(config, rounding)
    out = []
    for f in range(sub.frames):
        m = sub.det_mask[f]
        rows = ref.update(
            sub.det_boxes[f][m],
            None if sub.det_class is None else sub.det_class[f][m],
            None if sub.det_embed is None else sub.det_embed[f][m])
        out.append({int(r[4]): (r[:4], int(r[5])) for r in rows})
    return out


def program_frames(tracks):
    """A delivered ``SequenceTracks`` as per-frame ``{uid: (box, cls)}``;
    a track with no class partition has class 0, as in the reference."""
    out = []
    for f in range(tracks.uid.shape[0]):
        em = tracks.emit[f]
        cls = tracks.cls[f] if tracks.cls is not None else None
        out.append({int(u): (tracks.boxes[f, k].astype(np.float64),
                             0 if cls is None else int(cls[k]))
                    for k, u in enumerate(tracks.uid[f]) if em[k]})
    return out


def compare(got_frames, want_frames) -> tuple[int, int, float]:
    """``(frames, id_mismatch_frames, box_err_px)`` of one sequence."""
    bad, worst = 0, 0.0
    if len(got_frames) != len(want_frames):
        n = max(len(got_frames), len(want_frames))
        return n, n, 0.0
    for got, want in zip(got_frames, want_frames):
        if sorted(got) != sorted(want) or any(
                got[u][1] != want[u][1] for u in want):
            bad += 1
            continue
        for u, (box, _) in want.items():
            err = float(np.abs(got[u][0] - box).max())
            worst = max(worst, err if np.isfinite(err) else NOT_FINITE)
    return len(want_frames), bad, worst


def pick_sample(candidates: list[int], frames, mid_chunk: set,
                size: int) -> list[int]:
    """The sample, from the owed submissions a seeded hash kept: the
    ``size`` earliest, the earliest admitted into a recycled lane mid-chunk
    where there is one, and the earliest of the longest.  Release is in
    submission order, so the earliest are the first delivered."""
    idx = sorted(candidates)
    if not idx:
        return []
    chosen = set(idx[:size])
    mids = [i for i in idx if i in mid_chunk]
    if mids:
        chosen.add(mids[0])
    longest = max(frames(i) for i in idx)
    chosen.add(next(i for i in idx if frames(i) == longest))
    return sorted(chosen)


def check_sample(config: dict, traffic, delivered: dict,
                 sample: list[int]) -> dict:
    """Compare each sampled delivery with the reference, run over the very
    detections that were submitted; returns the numbers ``frames``,
    ``id_mismatch_frames``, ``box_err_px``."""
    frames = bad = 0
    worst = 0.0
    for i in sample:
        n, b, w = compare(program_frames(delivered[i]),
                          reference_frames(config, traffic.submission(i)))
        frames, bad, worst = frames + n, bad + b, max(worst, w)
    return {"frames": frames, "id_mismatch_frames": bad,
            "box_err_px": worst}
