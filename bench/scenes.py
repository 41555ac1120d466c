"""Seeded synthetic detections, MOT15- and KITTI-shaped.

``generate_scene`` and ``generate_multiclass_scene`` are copies of the
program's generators (``src/repro/data/synthetic.py``), kept here so that
the benchmark's inputs cannot move with the program.  Pure numpy.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class SceneConfig:
    num_frames: int = 200
    max_objects: int = 12           # simultaneous objects cap
    img_w: float = 1920.0
    img_h: float = 1080.0
    mean_size: float = 80.0         # mean box side, px
    speed: float = 8.0              # px/frame
    birth_rate: float = 0.05        # P(new object appears per frame)
    death_rate: float = 0.005       # P(object leaves per frame)
    det_noise: float = 2.0          # detection jitter, px
    miss_rate: float = 0.05         # P(detection dropout)
    fp_rate: float = 0.1            # expected false positives per frame
    seed: int = 0


def generate_scene(cfg: SceneConfig):
    """One sequence: ``(gt_boxes [F, K, 4], gt_mask [F, K], det_boxes
    [F, D, 4], det_mask [F, D])``, xyxy float32, D = max_objects + 2."""
    rng = np.random.default_rng(cfg.seed)
    f = cfg.num_frames
    tracks = []
    active = []
    for _ in range(rng.integers(2, max(3, cfg.max_objects // 2 + 1))):
        active.append(_spawn(rng, cfg, 0))
    for t in range(1, f):
        if len(active) < cfg.max_objects and rng.random() < cfg.birth_rate:
            active.append(_spawn(rng, cfg, t))
        survivors = []
        for tr in active:
            if rng.random() < cfg.death_rate:
                tr["t_death"] = t
                tracks.append(tr)
            else:
                _step(tr, cfg)
                survivors.append(tr)
        active = survivors
    for tr in active:
        tr["t_death"] = f
        tracks.append(tr)

    k = len(tracks)
    gt_boxes = np.zeros((f, k, 4), np.float32)
    gt_mask = np.zeros((f, k), bool)
    for i, tr in enumerate(tracks):
        t0, t1 = tr["t_birth"], tr["t_death"]
        traj = np.asarray(tr["traj"][: t1 - t0], np.float32).reshape(-1, 4)
        gt_boxes[t0:t0 + len(traj), i] = traj
        gt_mask[t0:t0 + len(traj), i] = True

    d_max = cfg.max_objects + max(2, int(3 * cfg.fp_rate))
    det_boxes = np.zeros((f, d_max, 4), np.float32)
    det_mask = np.zeros((f, d_max), bool)
    for t in range(f):
        dets = []
        for i in range(k):
            if gt_mask[t, i] and rng.random() >= cfg.miss_rate:
                dets.append(gt_boxes[t, i] + rng.normal(0, cfg.det_noise, 4))
        n_fp = rng.poisson(cfg.fp_rate)
        for _ in range(n_fp):
            cx = rng.uniform(0, cfg.img_w)
            cy = rng.uniform(0, cfg.img_h)
            s = rng.uniform(0.5, 1.5) * cfg.mean_size
            dets.append([cx - s / 2, cy - s / 2, cx + s / 2, cy + s / 2])
        rng.shuffle(dets)
        dets = dets[:d_max]
        if dets:
            det_boxes[t, : len(dets)] = np.asarray(dets, np.float32)
            det_mask[t, : len(dets)] = True
    return gt_boxes, gt_mask, det_boxes, det_mask


def _spawn(rng, cfg, t):
    w = max(8.0, rng.normal(cfg.mean_size, cfg.mean_size / 4))
    h = max(8.0, rng.normal(cfg.mean_size * 2, cfg.mean_size / 3))
    cx = rng.uniform(w, cfg.img_w - w)
    cy = rng.uniform(h, cfg.img_h - h)
    vx, vy = rng.normal(0, cfg.speed, 2)
    box = [cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2]
    return {"t_birth": t, "t_death": None, "traj": [box],
            "v": (vx, vy), "wh": (w, h), "c": (cx, cy)}


def _step(tr, cfg):
    vx, vy = tr["v"]
    cx, cy = tr["c"]
    w, h = tr["wh"]
    cx = float(np.clip(cx + vx, w / 2, cfg.img_w - w / 2))
    cy = float(np.clip(cy + vy, h / 2, cfg.img_h - h / 2))
    tr["c"] = (cx, cy)
    tr["traj"].append([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2])


def generate_multiclass_scene(cfg: SceneConfig, num_classes: int = 3,
                              embed_dim: int = 4):
    """:func:`generate_scene` with a class per object (fixed at birth) and
    an identity-coded one-hot embedding ``eye[k % embed_dim]``; false
    positives get a random class and embedding.  Returns ``(gt_boxes,
    gt_mask, gt_class [K], det_boxes, det_mask, det_class [F, D] int32,
    det_embed [F, D, E] float32)``."""
    gt_boxes, gt_mask, _, _ = generate_scene(cfg)
    rng = np.random.default_rng(cfg.seed + 7919)
    f, k = gt_mask.shape
    gt_class = rng.integers(0, num_classes, size=k).astype(np.int32)
    eye = np.eye(embed_dim, dtype=np.float32)
    gt_embed = eye[np.arange(k) % embed_dim]
    d_max = cfg.max_objects + max(2, int(3 * cfg.fp_rate))
    det_boxes = np.zeros((f, d_max, 4), np.float32)
    det_mask = np.zeros((f, d_max), bool)
    det_class = np.zeros((f, d_max), np.int32)
    det_embed = np.zeros((f, d_max, embed_dim), np.float32)
    for t in range(f):
        rows = []
        for i in range(k):
            if gt_mask[t, i] and rng.random() >= cfg.miss_rate:
                box = (gt_boxes[t, i]
                       + rng.normal(0, cfg.det_noise, 4)).astype(np.float32)
                rows.append((box, int(gt_class[i]), gt_embed[i]))
        for _ in range(rng.poisson(cfg.fp_rate)):
            cx = rng.uniform(0, cfg.img_w)
            cy = rng.uniform(0, cfg.img_h)
            s = rng.uniform(0.5, 1.5) * cfg.mean_size
            rows.append((np.array([cx - s / 2, cy - s / 2,
                                   cx + s / 2, cy + s / 2], np.float32),
                         int(rng.integers(num_classes)),
                         eye[int(rng.integers(embed_dim))]))
        rng.shuffle(rows)
        for di, (box, c, e) in enumerate(rows[:d_max]):
            det_boxes[t, di] = box
            det_mask[t, di] = True
            det_class[t, di] = c
            det_embed[t, di] = e
    return (gt_boxes, gt_mask, gt_class,
            det_boxes, det_mask, det_class, det_embed)
