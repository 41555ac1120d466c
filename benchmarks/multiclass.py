"""Multi-class association sweep — composed costs vs the single-class baseline.

The class partition (DESIGN.md §10) folds per-class association into ONE
lane-batched solve by masking cross-class pairs infeasible, so K classes
cost the same dispatches as one.  This benchmark quantifies that claim on
the paper's extremely-small-matrix regime: per-frame latency of the
single-class IoU baseline vs the class-partitioned composed costs
({iou, iou+maha, iou+embed} x {1, 3} classes) on the fused lane path,
same synthetic scene geometry throughout.  The derived column carries the
per-run emitted-track count so a cost/partition change that silently
alters tracking behaviour shows up next to its latency.

Run via ``benchmarks.run`` (section ``multiclass``) or standalone.  CI
smokes it with a small ``num_frames`` so the multi-class rows cannot rot.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SortConfig, SortEngine, cost as cost_mod
from repro.data.synthetic import SceneConfig, generate_multiclass_scene

EMBED_DIM = 8

# (row tag, CostSpec, num_classes): the single-class IoU row is the exact
# pre-multiclass engine trace (bit-identity contract, DESIGN.md §10) —
# every other row is measured against it.
CONFIGS = (
    ("iou_1cls", cost_mod.IOU, 1),
    ("iou_3cls", cost_mod.IOU, 3),
    ("iou_maha_3cls", cost_mod.iou_maha(), 3),
    ("iou_embed_3cls", cost_mod.iou_embed(EMBED_DIM), 3),
)


def run(seed: int = 0, num_frames: int = 150):
    scene = SceneConfig(num_frames=num_frames, max_objects=10,
                        miss_rate=0.05, fp_rate=0.2, det_noise=2.0,
                        seed=seed)
    _, _, _, db, dm, dc, de = generate_multiclass_scene(
        scene, num_classes=3, embed_dim=EMBED_DIM)
    d = db.shape[1]
    dbj = jnp.asarray(db[:, None])
    dmj = jnp.asarray(dm[:, None])
    dcj = jnp.asarray(dc[:, None])
    dej = jnp.asarray(de[:, None])

    rows = []
    base_us = None
    for tag, spec, nc in CONFIGS:
        eng = SortEngine(SortConfig(max_trackers=16, max_detections=d,
                                    use_kernels=True, cost=spec,
                                    num_classes=nc))
        kw = {}
        if nc > 1:
            kw["det_class"] = dcj
        if spec.uses_embed:
            kw["det_embed"] = dej
        run_fn = jax.jit(lambda s, b, m, eng=eng, kw=kw:
                         eng.run(s, b, m, **kw))
        jax.block_until_ready(run_fn(eng.init(1), dbj, dmj))
        t0 = time.perf_counter()
        _, out = run_fn(eng.init(1), dbj, dmj)
        jax.block_until_ready(out.boxes)
        us = (time.perf_counter() - t0) / num_frames * 1e6
        if base_us is None:
            base_us = us
        emitted = int(np.asarray(out.emit).sum())
        rows.append((f"multiclass/{tag}_us_per_frame", us,
                     f"x{us / base_us:.2f} vs 1-class iou, "
                     f"emitted={emitted}, one lane-batched solve "
                     f"(block-diagonal via feasibility mask)"))
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=150)
    args = ap.parse_args()
    for name, value, derived in run(num_frames=args.frames):
        print(f"{name},{value:.4f},{derived}")
