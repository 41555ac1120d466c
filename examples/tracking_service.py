"""End-to-end tracking service — the paper's workload as a deployable driver.

Ingests MOT15-format detection files (or synthesizes Table-I-shaped ones)
and serves them through the online multi-stream scheduler
(``repro.serve.StreamScheduler``): ragged-length sequences are multiplexed
onto a fixed lane budget, lanes are recycled the moment a sequence ends
(masked re-init + next admission in the same fused step, DESIGN.md §3),
and results drain in submission order as MOT15 submission files.

    PYTHONPATH=src python examples/tracking_service.py --replicate 4 \
        --lanes 8 --out /tmp/sort_out

``--devices N`` shards the lane budget over an N-device ``("lanes",)``
mesh (DESIGN.md §7) — each device scans its own lane shard, bit-identical
to the single-device run.  On CPU, export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first.

``--serve`` routes everything through the crash-exact service front-end
(``repro.serve.TrackingService``, DESIGN.md §11): results are written as
they finish, and with ``--ckpt-dir`` the full service state checkpoints
at every ``--ckpt-every``-th chunk boundary, so a SIGKILL'd run resumed
with ``--resume`` produces byte-identical output files::

    PYTHONPATH=src python examples/tracking_service.py --serve \
        --ckpt-dir /tmp/trk_ckpt --out /tmp/sort_out            # killed...
    PYTHONPATH=src python examples/tracking_service.py --serve \
        --ckpt-dir /tmp/trk_ckpt --out /tmp/sort_out --resume   # ...resumed

``--kill-after-chunks N`` SIGKILLs the process after N chunks (exit 137)
— the CI soak's deterministic crash injection.
"""
import argparse
import asyncio
import os
import signal
import time

import numpy as np

from repro.core import SortConfig, SortEngine, cost as cost_mod
from repro.data import mot
from repro.data.synthetic import (SceneConfig, generate_multiclass_scene,
                                  generate_scene)
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import StreamScheduler
from repro.sharding import lane_mesh


def load_or_synthesize(det_dir, num_classes=1, embed_dim=0):
    """``[(name, det_boxes, det_mask, det_class|None, det_embed|None)]``.

    Multi-class / embed configs read the class column from real det files
    (clamped into ``[0, num_classes)``; MOT15 files carry ``-1`` = no
    class) and code up identity embeddings from it; synthetic sequences
    come from the multi-class generator directly.
    """
    multi = num_classes > 1 or embed_dim > 0
    seqs = []
    if det_dir and os.path.isdir(det_dir):
        for name in sorted(os.listdir(det_dir)):
            if not name.endswith(".txt"):
                continue
            db, dm, dc, _ = mot.read_det_file(
                os.path.join(det_dir, name), with_extras=True)
            dc = np.clip(dc, 0, max(num_classes - 1, 0)).astype(np.int32)
            de = None
            if embed_dim > 0:
                de = np.eye(embed_dim, dtype=np.float32)[dc % embed_dim]
            seqs.append((name[:-4], db, dm,
                         dc if num_classes > 1 else None, de))
    if not seqs:  # synthesize the 11 paper sequences
        for i, (name, (frames, max_obj)) in enumerate(mot.TABLE_I.items()):
            cfg = SceneConfig(num_frames=frames, max_objects=max_obj, seed=i)
            if multi:
                _, _, _, db, dm, dc, de = generate_multiclass_scene(
                    cfg, num_classes=max(num_classes, 1),
                    embed_dim=max(embed_dim, 1))
                seqs.append((name, db, dm,
                             dc if num_classes > 1 else None,
                             de if embed_dim > 0 else None))
            else:
                _, _, db, dm = generate_scene(cfg)
                seqs.append((name, db, dm, None, None))
    return seqs


async def _serve(sched, seqs, args) -> int:
    """The --serve path: pump the service chunk by chunk, writing each
    finished sequence's MOT file the moment it is delivered (BEFORE the
    covering checkpoint commits — at-least-once; a resumed run may
    re-write identical files, never miss one)."""
    from repro.serve import TrackingService

    frames = [0]

    def on_result(_idx, tracks):
        mot.write_results(os.path.join(args.out, f"{tracks.name}.txt"),
                          tracks.boxes, tracks.uid, tracks.emit)
        frames[0] += tracks.num_frames

    # the whole replay is one client's admitted work: bound admission by
    # it, so every lane can fill instead of shedding past the defaults
    knobs = dict(ckpt_every=args.ckpt_every, on_result=on_result,
                 max_pending=max(len(seqs), 1),
                 per_client_pending=max(len(seqs), 1))
    if args.resume:
        svc = TrackingService.resume(sched, args.ckpt_dir, **knobs)
    else:
        svc = TrackingService(sched, ckpt_dir=args.ckpt_dir, **knobs)
        for name, db, dm, dc, de in seqs:
            await svc.submit(name, db, dm, det_class=dc, det_embed=de)
        if svc.ckpt is not None:
            svc.checkpoint(wait=True)   # pre-flight: resume always has a step
    chunks = 0
    while svc.busy:
        await svc.step()
        chunks += 1
        if args.kill_after_chunks is not None and \
                chunks >= args.kill_after_chunks:
            svc.close()                 # flush the in-flight write, then die
            os.kill(os.getpid(), signal.SIGKILL)
    svc.close()
    return frames[0]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--det-dir", default=None,
                    help="directory of MOT15 det.txt files")
    ap.add_argument("--out", default="/tmp/sort_out")
    ap.add_argument("--replicate", type=int, default=1,
                    help="paper §VI: replicate inputs k times")
    ap.add_argument("--lanes", type=int, default=4,
                    help="fixed lane budget the ragged sequences are "
                         "multiplexed onto (recycled as sequences end)")
    ap.add_argument("--chunk", type=int, default=32,
                    help="frames planned/dispatched per host round-trip")
    ap.add_argument("--autoscale", action="store_true",
                    help="elastic lane budget (DESIGN.md §8): autoscale "
                         "between --min-lanes and --lanes over a "
                         "pre-compiled power-of-two width ladder — grow "
                         "on queue pressure, shrink once evacuating "
                         "lanes drain; outputs stay bit-identical to the "
                         "fixed --lanes run")
    ap.add_argument("--min-lanes", type=int, default=None,
                    help="ladder floor for --autoscale (default: "
                         "--lanes // 4 when that forms a power-of-two "
                         "ladder, raised until it divides --devices, "
                         "else --lanes); --lanes must be min * 2**k")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the lane budget over this many devices "
                         "(1-D 'lanes' mesh, DESIGN.md §7; --lanes must "
                         "divide evenly; on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first)")
    ap.add_argument("--fused", action="store_true",
                    help="lane-persistent fused frame path "
                         "(SortConfig.use_kernels=True): one kernel "
                         "dispatch per frame")
    ap.add_argument("--chunk-kernel", action="store_true",
                    help="chunk-resident megakernel (DESIGN.md §9, "
                         "SortConfig.chunk_kernel=True; implies --fused): "
                         "each planned --chunk-frame serving chunk runs "
                         "as ONE kernel dispatch with lane state resident "
                         "across the in-kernel frame loop — bit-identical "
                         "outputs, F-to-1 dispatch reduction "
                         "(configs/sort_mot.py::MEGAKERNEL)")
    ap.add_argument("--assoc", choices=("hungarian", "greedy"),
                    default="hungarian",
                    help="association algorithm (DESIGN.md §6): "
                         "'hungarian' is the paper's optimal assignment "
                         "(on the fused path its JV solve runs as a "
                         "jitted lane-batched stage); 'greedy' is the "
                         "cheaper in-kernel best-first matcher")
    ap.add_argument("--cost", choices=("iou", "iou+maha", "iou+embed"),
                    default="iou",
                    help="association cost (DESIGN.md §10): pure IoU "
                         "(the paper's, default), IoU with a chi-square "
                         "Mahalanobis gate, or IoU composed with an "
                         "appearance-embedding dot product")
    ap.add_argument("--classes", type=int, default=1,
                    help="class-partitioned association (DESIGN.md §10): "
                         "cross-class det/track pairs are masked "
                         "infeasible, so the single lane-batched "
                         "assignment solves the per-class block-diagonal "
                         "problem — no per-class loop, no extra "
                         "dispatches; 1 = single-class (default)")
    ap.add_argument("--embed-dim", type=int, default=8,
                    help="appearance embedding width for --cost iou+embed")
    ap.add_argument("--serve", action="store_true",
                    help="run through the TrackingService front-end "
                         "(DESIGN.md §11): async bounded admission, "
                         "circuit-broken dispatch, and — with "
                         "--ckpt-dir — crash-exact checkpoint/restore")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory for --serve; full service "
                         "state snapshots at chunk boundaries")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="checkpoint every N chunk boundaries")
    ap.add_argument("--resume", action="store_true",
                    help="resume --serve from the latest committed "
                         "checkpoint in --ckpt-dir instead of submitting "
                         "fresh work; resumed outputs are bit-identical "
                         "to an uninterrupted run")
    ap.add_argument("--kill-after-chunks", type=int, default=None,
                    help="SIGKILL this process after N dispatched chunks "
                         "(crash injection for the kill-and-resume soak; "
                         "exits 137)")
    args = ap.parse_args()
    if args.min_lanes is not None and not args.autoscale:
        ap.error("--min-lanes only applies with --autoscale "
                 "(a fixed budget is just --lanes)")
    if (args.resume or args.kill_after_chunks is not None) and \
            not (args.serve and args.ckpt_dir):
        ap.error("--resume/--kill-after-chunks need --serve and --ckpt-dir")

    enable_compile_cache()
    spec = cost_mod.parse_cost(args.cost, embed_dim=args.embed_dim)
    seqs = load_or_synthesize(args.det_dir, num_classes=args.classes,
                              embed_dim=spec.embed_dim)
    if args.replicate > 1:
        reps = []
        for r in range(args.replicate):
            reps += [(f"{name}#{r}",) + rest
                     for name, *rest in (tuple(s) for s in seqs)]
        seqs = reps
    os.makedirs(args.out, exist_ok=True)

    d = max(db.shape[1] for _, db, *_ in seqs)
    eng = SortEngine(SortConfig(max_trackers=16, max_detections=d,
                                use_kernels=args.fused or args.chunk_kernel,
                                chunk_kernel=args.chunk_kernel,
                                assoc=args.assoc, cost=spec,
                                num_classes=args.classes))
    mesh = lane_mesh(args.devices) if args.devices > 1 else None
    min_lanes = max_lanes = None
    if args.autoscale:
        max_lanes = args.lanes
        min_lanes = args.min_lanes
        if min_lanes is None:       # largest 4x headroom that stays a ladder
            min_lanes = args.lanes // 4 if args.lanes % 4 == 0 else args.lanes
            while min_lanes % args.devices and min_lanes < args.lanes:
                min_lanes *= 2  # every width must divide the mesh;
                # doubling stays on-ladder and stops at --lanes (an
                # indivisible --lanes fails scheduler validation anyway)
    sched = StreamScheduler(eng, num_lanes=min_lanes or args.lanes,
                            max_dets=d, chunk=args.chunk, mesh=mesh,
                            min_lanes=min_lanes, max_lanes=max_lanes)

    t_start = time.perf_counter()
    if args.serve:
        total_frames = asyncio.run(_serve(sched, seqs, args))
    else:
        for name, db, dm, dc, de in seqs:
            sched.submit(name, db, dm, det_class=dc, det_embed=de)
        total_frames = 0
        for tracks in sched.run():              # drains in submission order
            mot.write_results(os.path.join(args.out, f"{tracks.name}.txt"),
                              tracks.boxes, tracks.uid, tracks.emit)
            total_frames += tracks.num_frames
    dt = time.perf_counter() - t_start
    mode = ("chunk-resident megakernel" if args.chunk_kernel
            else "fused lane-persistent" if args.fused
            else "per-phase") + f" / {args.assoc} / {args.cost}"
    if args.classes > 1:
        mode += f" / {args.classes} classes"
    if args.devices > 1:
        mode += f" / {args.devices}-device lane mesh"
    lanes_str = f"{args.lanes} lanes"
    if args.autoscale:
        lanes_str = (f"elastic {sched.ladder[0]}-{sched.ladder[-1]} lanes, "
                     f"{len(sched.resizes)} resizes")
    print(f"{len(seqs)} sequences, {total_frames} frames in {dt:.2f}s "
          f"-> {total_frames / dt:,.0f} FPS (incl. compile, {mode}, "
          f"{lanes_str} at {sched.utilization:.0%} utilization)  "
          f"results in {args.out}")


if __name__ == "__main__":
    main()
