"""Benchmark driver — one section per paper table.

Prints ``name,us_per_call,derived`` CSV.  Table mapping:

* Table I   -> benchmarks.datasets   (11 MOT15-shaped sequences, FPS+MOTA)
* Table IV  -> benchmarks.kernel_ai  (per-phase time share + AI)
* Table V   -> benchmarks.speedup    (per-op Python vs fused batched JAX)
* Table VI  -> benchmarks.scaling    (strong vs weak vs throughput)

Roofline (§Roofline, from the dry-run) lives in ``benchmarks.roofline`` —
run it separately after ``repro.launch.dryrun``.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main(argv=None) -> None:
    from benchmarks import (association_ablation, autoscale, datasets,
                            device_scaling, dispatch_overhead, kernel_ai,
                            multiclass, ragged, scaling, service_soak,
                            speedup)

    argparse.ArgumentParser(
        prog="benchmarks.run",
        description="Run every benchmark section; prints CSV to stdout."
    ).parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    sections = [
        ("tableI", datasets.run),
        ("tableIV", kernel_ai.run),
        ("tableV", speedup.run),
        ("tableVI", scaling.run),
        ("ragged", ragged.run),
        ("ablation", association_ablation.run),
        # elastic vs fixed lane budgets on a bursty 4-phase arrival trace
        # (DESIGN.md §8)
        ("autoscale", autoscale.run),
        # reports per-device rows only up to jax.device_count(); export
        # XLA_FLAGS=--xla_force_host_platform_device_count=8 for the full
        # {1,2,4,8} sweep on CPU (DESIGN.md §7)
        ("devices", device_scaling.run),
        # per-frame scan vs chunk-resident megakernel dispatch accounting
        # (DESIGN.md §9)
        ("dispatch", dispatch_overhead.run),
        # composed costs x class partition vs the single-class IoU
        # baseline — one block-diagonal lane-batched solve (DESIGN.md §10)
        ("multiclass", multiclass.run),
        # TrackingService front-end: admission/delivery overhead,
        # chunk-boundary checkpoint tax, resume latency, shed behaviour
        # (DESIGN.md §11)
        ("service", service_soak.run),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in sections:
        try:
            for row_name, value, derived in fn():
                print(f"{row_name},{value:.4f},{derived}")
                sys.stdout.flush()
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"{name}/ERROR,-1,see stderr")
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
