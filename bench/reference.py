"""Plain reference SORT: one stream, one numpy/scipy tracker per object.

The yardstick that decides ``correct``.  It is a standalone copy of the
program's numpy oracle (``src/repro/core/ref_numpy.py``: ``KalmanBoxTracker``,
``Sort``, the IoU and box conversions) together with the association cost
it mirrors (``src/repro/core/cost.py``: the IoU + embedding score and the
class partition), so that no change to the program can move it.  It
imports nothing of the program.

``Sort(..., max_trackers=T)`` holds at most ``T`` live trackers a stream,
as a deployment with ``T`` tracker slots does: a detection left unmatched
when every place is taken is not born (``None``: no limit, as published).

``Sort(..., rounding=...)`` is the control: the same tracker with its
state and every detection rounded to a lower precision after each
predict and update (``bf16`` rounds float64 to bfloat16), standing in for
a tracker that keeps its Kalman state in bfloat16.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np
from scipy.optimize import linear_sum_assignment


def round_bf16(a):
    """Round to the nearest bfloat16 (ties to even), returned as float64."""
    f = np.asarray(a, np.float64).astype(np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


ROUNDINGS: dict[str, Optional[Callable]] = {"f64": None, "bf16": round_bf16}


@dataclasses.dataclass(frozen=True)
class Cost:
    """Association score ``iou_weight * IoU + embed_weight * <e_det,
    e_trk>``; the configuration's ``engine.cost`` block."""

    iou_weight: float = 1.0
    maha_gate: Optional[float] = None
    embed_weight: float = 0.0
    embed_dim: int = 0

    @property
    def uses_maha(self) -> bool:
        return self.maha_gate is not None

    @property
    def uses_embed(self) -> bool:
        return self.embed_weight != 0.0 and self.embed_dim > 0

    @property
    def is_iou_only(self) -> bool:
        return (self.iou_weight == 1.0 and not self.uses_maha
                and not self.uses_embed)


def xyxy_to_z(box):
    w = box[2] - box[0]
    h = box[3] - box[1]
    return np.array([box[0] + w / 2.0, box[1] + h / 2.0, w * h,
                     w / max(h, 1e-9)])


def z_to_xyxy(x):
    s = max(x[2], 0.0)
    r = max(x[3], 1e-9)
    w = np.sqrt(s * r)
    h = s / max(w, 1e-9)
    return np.array([x[0] - w / 2, x[1] - h / 2, x[0] + w / 2, x[1] + h / 2])


def iou_matrix(a, b):
    """``[len(a), len(b)]`` IoU of xyxy boxes, pair by pair."""
    a = np.asarray(a, np.float64)[:, None, :]
    b = np.asarray(b, np.float64)[None, :, :]
    iw = np.maximum(np.minimum(a[..., 2], b[..., 2])
                    - np.maximum(a[..., 0], b[..., 0]), 0.0)
    ih = np.maximum(np.minimum(a[..., 3], b[..., 3])
                    - np.maximum(a[..., 1], b[..., 1]), 0.0)
    inter = iw * ih
    ua = np.maximum(a[..., 2] - a[..., 0], 0) * np.maximum(
        a[..., 3] - a[..., 1], 0)
    ub = np.maximum(b[..., 2] - b[..., 0], 0) * np.maximum(
        b[..., 3] - b[..., 1], 0)
    return inter / np.maximum(ua + ub - inter, 1e-9)


class KalmanBoxTracker:
    """One constant-velocity Kalman tracker (filterpy-equivalent)."""

    def __init__(self, box, uid, cls=0, embed=None, rnd=None):
        self.rnd = rnd or (lambda a: a)
        self.F = np.eye(7)
        self.F[0, 4] = self.F[1, 5] = self.F[2, 6] = 1.0
        self.H = np.zeros((4, 7))
        self.H[np.arange(4), np.arange(4)] = 1.0
        self.R = np.diag([1.0, 1.0, 10.0, 10.0])
        self.Q = np.diag([1.0, 1.0, 1.0, 1.0, 0.01, 0.01, 1e-4])
        self.P = np.diag([10.0, 10, 10, 10, 1e4, 1e4, 1e4])
        self.x = np.zeros(7)
        self.x[:4] = self.rnd(xyxy_to_z(box))
        self.uid = uid
        self.cls = cls
        self.embed = embed
        self.time_since_update = 0
        self.hits = 0
        self.hit_streak = 0
        self.age = 0

    def predict(self):
        if self.x[2] + self.x[6] <= 0:
            self.x[6] = 0.0
        self.x = self.rnd(self.F @ self.x)
        self.P = self.rnd(self.F @ self.P @ self.F.T + self.Q)
        self.age += 1
        if self.time_since_update > 0:
            self.hit_streak = 0
        self.time_since_update += 1
        return self.rnd(z_to_xyxy(self.x))

    def update(self, box, embed=None):
        self.time_since_update = 0
        self.hits += 1
        self.hit_streak += 1
        if embed is not None:
            self.embed = embed
        z = self.rnd(xyxy_to_z(box))
        y = z - self.H @ self.x
        s = self.H @ self.P @ self.H.T + self.R
        k = self.P @ self.H.T @ np.linalg.inv(s)
        self.x = self.rnd(self.x + k @ y)
        self.P = self.rnd((np.eye(7) - k @ self.H) @ self.P)

    def maha_d2(self, box):
        """Squared Mahalanobis distance of ``box`` from the post-predict
        observation distribution ``S = P'[:4, :4] + R``."""
        y = xyxy_to_z(box) - self.x[:4]
        s = self.P[:4, :4] + self.R
        return float(y @ np.linalg.inv(s) @ y)


class Sort:
    """Per-stream SORT with Bewley's semantics: Hungarian (scipy) or
    greedy association, an optional composed cost and class partition."""

    def __init__(self, max_age=1, min_hits=3, iou_threshold=0.3,
                 assoc="hungarian", cost: Optional[Cost] = None,
                 num_classes=1, rounding: str = "f64",
                 max_trackers: Optional[int] = None):
        if assoc not in ("hungarian", "greedy"):
            raise ValueError(f"unknown assoc {assoc!r}")
        self.max_age = max_age
        self.min_hits = min_hits
        self.iou_threshold = iou_threshold
        self.assoc = assoc
        self.cost = Cost() if cost is None else cost
        self.num_classes = num_classes
        self.rnd = ROUNDINGS[rounding]
        self.max_trackers = max_trackers
        self.trackers: list[KalmanBoxTracker] = []
        self.frame_count = 0
        self.next_uid = 1

    def update(self, dets: np.ndarray, classes=None, embeds=None):
        """``dets [D, 4]`` xyxy -> list of ``(x1, y1, x2, y2, uid, cls)``."""
        if self.rnd is not None:
            dets = self.rnd(dets)
        self.frame_count += 1
        preds = [t.predict() for t in self.trackers]
        matches, unmatched_dets, _ = self._associate(
            dets, preds, classes, embeds)
        for d, t in matches:
            self.trackers[t].update(
                dets[d], None if embeds is None else embeds[d])
        # trackers missed more than max_age frames die before the births
        # take their places; with a capacity, the unmatched detections claim
        # the free places in detection order, and one that finds none is
        # not born and takes no id
        self.trackers = [t for t in self.trackers
                         if t.time_since_update <= self.max_age]
        if self.max_trackers is not None:
            unmatched_dets = unmatched_dets[
                :max(self.max_trackers - len(self.trackers), 0)]
        for d in unmatched_dets:
            self.trackers.append(KalmanBoxTracker(
                dets[d], self.next_uid,
                cls=0 if classes is None else int(classes[d]),
                embed=None if embeds is None else embeds[d], rnd=self.rnd))
            self.next_uid += 1
        out = []
        for t in self.trackers:
            if t.time_since_update < 1 and (
                    t.hit_streak >= self.min_hits
                    or self.frame_count <= self.min_hits):
                out.append(np.concatenate([z_to_xyxy(t.x), [t.uid, t.cls]]))
        return out

    def _score_and_feasible(self, dets, mat, classes, embeds):
        nd, nt = mat.shape
        cost = self.cost
        score = cost.iou_weight * mat
        if cost.uses_embed:
            for i in range(nd):
                for j in range(nt):
                    score[i, j] += cost.embed_weight * float(
                        np.dot(embeds[i], self.trackers[j].embed))
        feasible = np.ones((nd, nt), bool)
        if self.num_classes > 1:
            for i in range(nd):
                for j in range(nt):
                    feasible[i, j] &= (int(classes[i])
                                       == self.trackers[j].cls)
        if cost.uses_maha:
            for i in range(nd):
                for j in range(nt):
                    feasible[i, j] &= (self.trackers[j].maha_d2(dets[i])
                                       <= cost.maha_gate)
        return score, feasible

    def _associate(self, dets, preds, classes=None, embeds=None):
        nd, nt = len(dets), len(preds)
        if nd == 0 or nt == 0:
            return [], list(range(nd)), list(range(nt))
        mat = iou_matrix(dets, preds)
        plain = self.cost.is_iou_only and self.num_classes == 1
        if not plain:
            score, feasible = self._score_and_feasible(
                dets, mat, classes, embeds)
        matches, md, mt = [], set(), set()
        if self.assoc == "greedy" and plain:
            score = np.where(mat >= self.iou_threshold, mat, -1.0)
            for _ in range(min(nd, nt)):
                i, j = divmod(int(np.argmax(score)), nt)
                if score[i, j] <= 0.0:
                    break
                matches.append((i, j))
                md.add(i)
                mt.add(j)
                score[i, :] = -1.0
                score[:, j] = -1.0
        elif self.assoc == "greedy":
            s = np.where((mat >= self.iou_threshold) & feasible,
                         score, -1.0e30)
            for _ in range(min(nd, nt)):
                i, j = divmod(int(np.argmax(s)), nt)
                if s[i, j] <= -1.0e29:
                    break
                matches.append((i, j))
                md.add(i)
                mt.add(j)
                s[i, :] = -1.0e30
                s[:, j] = -1.0e30
        elif plain:
            ri, ci = linear_sum_assignment(-mat)
            for i, j in zip(ri, ci):
                if mat[i, j] >= self.iou_threshold:
                    matches.append((i, j))
                    md.add(i)
                    mt.add(j)
        else:
            # the feasible pairs in an n x n square whose pad always loses
            # to a real match, so one solve equals the per-class solves
            cost_m = -score
            vals = cost_m[feasible]
            cmax = max(float(vals.max()), 0.0) if vals.size else 0.0
            cmin = min(float(vals.min()), 0.0) if vals.size else 0.0
            n = max(nd, nt)
            pad = cmax + n * (cmax - cmin) + 1.0
            solve = np.full((n, n), pad)
            solve[:nd, :nt] = np.where(feasible, cost_m, pad)
            ri, ci = linear_sum_assignment(solve)
            for i, j in zip(ri, ci):
                if (i < nd and j < nt and feasible[i, j]
                        and mat[i, j] >= self.iou_threshold):
                    matches.append((i, j))
                    md.add(i)
                    mt.add(j)
        return (matches,
                [i for i in range(nd) if i not in md],
                [j for j in range(nt) if j not in mt])
