"""The benchmark's entry point: one run of one cell on the chip(s).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration (``bench/configs/<config>.json``) and
a traffic mix (``bench/traffic/<mix>.json``); each metric is read by
``bench/metrics/<metric>.py``.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, each
number compared with its limit; the same numbers end standard error.

There is no fallback: off a TPU, with fewer chips than the cell asks for,
or with a chunk program that holds no Pallas kernel, the run exits 1 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class NoChip(Exception):
    """The run cannot measure what the cell asks for."""


class Chip:
    """What only the chip can give: its devices, and a chunk program that
    holds the Pallas kernel."""

    def devices(self, chips: int):
        import jax
        backend = jax.default_backend()
        if backend != "tpu":
            raise NoChip(f"needs a TPU; JAX's backend is {backend!r}")
        devs = jax.devices()
        if len(devs) < chips:
            raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devs)}")
        return devs[:chips]

    def check_program(self, hlo: str) -> None:
        if "tpu_custom_call" not in hlo:
            raise NoChip("the compiled chunk program holds no Pallas kernel "
                         "(tpu_custom_call)")


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at one fixed path: the one
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: Path = ROOT, chip: Chip = None,
         t_start: float = T_START) -> int:
    args = parse(argv)
    sys.path[:0] = [str(root), str(root / "src")]
    chip = chip or Chip()
    try:
        from bench import harness
        spec = harness.Spec(root)
        cell = spec.cell(args.workload)
        devices = chip.devices(cell["chips"])
        enable_compile_cache(root)
        result = harness.run_cell(spec, args.workload, args.seed,
                                  args.seconds, bool(args.trace), devices,
                                  t_start, chip.check_program)
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    for name, (value, op, limit) in result["checks"].items():
        print(f"check {name} = {value!r} (limit {op} {limit!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
