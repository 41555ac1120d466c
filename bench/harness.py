"""One run of one cell: build the served path, warm it, drive the mix,
check the outputs, and hand the record to the metric readers.

The timed path is the program's own: ``TrackingService.submit`` and
``TrackingService.step`` over a ``StreamScheduler`` with one lane width,
whose chunk program is compiled and warmed before the window opens.
"""
from __future__ import annotations

import asyncio
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench import check, kernel_bytes, trace
from bench.traffic.generator import Traffic

TRACE_CHUNKS = 3         # chunks in the traced part of a --trace 1 window
KEEP_ONE_IN = 16         # owed answers the seeded hash keeps for the check
SAMPLE = 12              # sequences compared with the reference per run


class Spec:
    """``BENCHMARK.json`` and the files it names, found by name."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.paths = self.root / self.doc["paths"][0]

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        return json.loads((self.paths / "traffic" / f"{name}.json")
                          .read_text())

    def metrics_for(self, cell: str, traced: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``traced`` the per-layer
        ones that list it."""
        if traced:
            return [m for m in self.doc["per_layer"]
                    if cell in m["workloads"]]
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """``metrics/<metric>.py``; a metric split by cells
        (``<base>.<part>``) without a file of its own reads as its base."""
        path = self.paths / "metrics" / f"{metric}.py"
        if not path.exists():
            path = self.paths / "metrics" / f"{metric.split('.')[0]}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


class Run:
    """State of one run: the service, the traffic, and what was seen."""

    def __init__(self, svc, traffic: Traffic, mix: dict, seed: int,
                 tracer: Optional["Tracer"]):
        self.svc, self.sched = svc, svc.sched
        self.traffic, self.mix, self.seed = traffic, mix, seed
        self.tracer = tracer
        self.submitted = 0
        self.delivered = 0
        self.sheds = 0
        self.dispatch_errors = 0
        self.delivered_at: list[float] = []
        self.step_start: dict[int, float] = {}     # chunk number -> start
        self.window: Optional[list] = None
        self.window_frames = [0, 0]
        self.window_chunks = [0, 0]
        self.kept: dict = {}
        self.sample: Optional[list[int]] = None
        self.mid_chunk: set = set()
        self.due_origin: Optional[float] = None
        self.measured: Optional[tuple[int, int]] = None
        self._compiles = 0
        self.compiles_in_window = 0
        self.traces_in_window = 0

    def clock(self) -> float:
        """The run's clock: the host's, stopped while the profiler starts
        and stops, so that its stall delays no arrival, latency or window
        of a traced run."""
        paused = self.tracer.paused if self.tracer is not None else 0.0
        return time.perf_counter() - paused

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def queued(self) -> int:
        return self.submitted - len(self.sched.admissions)

    def submit(self, i: int) -> None:
        from repro.serve import Overloaded
        sub = self.traffic.submission(i)
        try:
            coro = self.svc.submit(sub.name, sub.det_boxes, sub.det_mask,
                                   client=sub.client,
                                   det_class=sub.det_class,
                                   det_embed=sub.det_embed)
            _run_now(coro)
        except Overloaded:
            self.sheds += 1
        self.submitted = max(self.submitted, i + 1)
        self._release()

    async def step(self) -> None:
        chunk = self.sched.chunks_run
        t0 = self.clock()
        self.step_start.setdefault(chunk, t0)
        try:
            with self.span("bench.step"):
                await self.svc.step()
        except Exception as exc:          # a failed dispatch: count, go on
            self.dispatch_errors += 1
            print(f"bench: dispatch failed: {exc!r}", file=sys.stderr)
        with self.span("bench.deliver"):
            self._release()
        if self.tracer is not None:
            self.tracer.after_step(self)

    def _release(self) -> None:
        """Take every delivered result out of the service: keep one in
        ``KEEP_ONE_IN`` (by a seeded hash) for the check, drop the rest."""
        done = self.svc.completed
        if not done:
            return
        now = self.clock()
        for idx in sorted(done):
            tracks = done.pop(idx)
            self.delivered_at.append(now)
            self.delivered = idx + 1
            if (idx in self.sample if self.sample is not None
                    else _keep(self.seed, idx)):
                self.kept[idx] = tracks

    def open_window(self) -> None:
        self.window = [self.clock(), None]
        self.window_frames[0] = self.sched.frames_processed
        self.window_chunks[0] = self.sched.chunks_run
        self._compiles_at_open = (self._compiles, len(self.sched.trace_log))
        if self.tracer is not None:
            self.tracer.start()

    def close_window(self) -> None:
        self.window[1] = self.clock()
        self.window_frames[1] = self.sched.frames_processed
        self.window_chunks[1] = self.sched.chunks_run
        self.compiles_in_window = self._compiles - self._compiles_at_open[0]
        self.traces_in_window = (len(self.sched.trace_log)
                                 - self._compiles_at_open[1])
        if self.tracer is not None:
            self.tracer.stop()

    def measure(self, first: int, end: int) -> None:
        """Open loop: the arrivals ``first .. end - 1`` were due in the
        window; the window owes each of them."""
        self.measured = (first, end)
        self.owe(range(first, end))

    def finished_in_window(self) -> list[int]:
        """Closed loop: the sequences whose last frame a chunk of the
        window stepped."""
        c = self.sched.chunk
        lo, hi = (n * c for n in self.window_chunks)
        return sorted(i for i, step in self.sched.admissions
                      if lo <= step + self.traffic.frames(i) - 1 < hi)

    def owe(self, indices) -> None:
        """The answers the window owes: draw the check's sample from them
        (:func:`bench.check.pick_sample` over those the seeded hash keeps)
        and keep only its deliveries from now on."""
        c = self.sched.chunk
        self.mid_chunk = {i for i, step in self.sched.admissions
                          if step % c != 0}
        self.sample = check.pick_sample(
            [i for i in indices if _keep(self.seed, i)], self.traffic.frames,
            self.mid_chunk, SAMPLE)
        self.kept = {i: t for i, t in self.kept.items() if i in self.sample}

    def owed(self) -> bool:
        """True while a sampled answer is still to come (release is in
        submission order)."""
        return bool(self.sample) and self.delivered <= self.sample[-1]

    def on_compile(self, event: str, *_args, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compiles += 1


def _keep(seed: int, idx: int) -> bool:
    h = (idx * 0x9E3779B1 + (seed & 0xFFFFFFFF) * 0x85EBCA77) & 0xFFFFFFFF
    return (h >> 7) % KEEP_ONE_IN == 0


def _run_now(coro):
    """Run a coroutine that never awaits (``TrackingService.submit``)."""
    try:
        coro.send(None)
    except StopIteration as stop:
        return stop.value
    raise RuntimeError("submit awaited; expected it to complete at once")


class Tracer:
    """Profiles the first ``TRACE_CHUNKS`` chunks of the window."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.on = False
        self.steps = 0
        self.paused = 0.0        # seconds spent starting and stopping

    def start(self) -> None:
        import jax
        t0 = time.perf_counter()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.on = True
        self.paused += time.perf_counter() - t0

    def after_step(self, run: Run) -> None:
        if self.on:
            self.steps += 1
            if self.steps >= TRACE_CHUNKS:
                self.stop()

    def stop(self) -> None:
        if self.on:
            import jax
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.on = False
            self.paused += time.perf_counter() - t0


def build(config: dict, devices):
    """Engine, scheduler and service of a configuration, on ``devices``."""
    from repro.core import SortConfig, SortEngine, cost
    from repro.serve import StreamScheduler, TrackingService
    from repro.sharding import lane_mesh

    e = dict(config["engine"])
    e["cost"] = cost.CostSpec(**e.get("cost", {}))
    engine = SortEngine(SortConfig(**e))
    chips = len(devices)
    mesh = lane_mesh(chips, devices=devices) if chips > 1 else None
    sched = StreamScheduler(engine, num_lanes=config["lanes_per_chip"] * chips,
                            max_dets=e["max_detections"],
                            chunk=config["chunk"], mesh=mesh)
    svc = TrackingService(sched, **config["service"])
    return svc


def compile_and_warm(sched) -> str:
    """Compile the scheduler's chunk program at its serving shapes, run it
    once on an all-idle chunk (an exact no-op), and return its HLO text."""
    import jax
    import jax.numpy as jnp

    c, l, d = sched.chunk, sched.num_lanes, sched.max_dets
    zeros = (np.zeros((c, l, d, 4), np.float32), np.zeros((c, l, d), bool),
             np.zeros((c, l), bool), np.zeros((c, l), bool)) + \
        sched._zero_extras(c, l, d)
    operands = (sched._sharding.place(*zeros) if sched._sharding is not None
                else tuple(jnp.asarray(a) for a in zeros))
    hlo = sched._chunk_fn.lower(sched._state, *operands).compile().as_text()
    jax.block_until_ready(sched._chunk_fn(sched._state, *operands))
    return hlo


def run_cell(spec: Spec, cell_name: str, seed: int, seconds: float,
             traced: bool, devices, t_start: float, check_program) -> dict:
    """One run; returns the result line's object."""
    import jax

    cell = spec.cell(cell_name)
    config = spec.config(cell["config"])
    mix = spec.mix(cell["traffic"])
    traffic = Traffic(mix, config, seed,
                      lanes=config["lanes_per_chip"] * len(devices))
    svc = build(config, devices)
    sched = svc.sched
    check_program(compile_and_warm(sched))

    logdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    run = Run(svc, traffic, mix, seed, Tracer(logdir) if traced else None)
    jax.monitoring.register_event_duration_secs_listener(run.on_compile)
    kind = importlib.import_module(f"bench.traffic.{mix['kind']}")
    asyncio.run(kind.drive(run, seconds))
    if run.tracer is not None:
        run.tracer.stop()
    setup_s = run.window[0] - t_start

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    record = Record(cell, config, run, setup_s)
    if traced:
        path = trace.find_xplane(logdir)
        ids = {d.id for d in devices}
        record.events = trace.collect(path, ids) if path else None
        shutil.rmtree(logdir, ignore_errors=True)
        record.reduced = (trace.reduce(record.events)
                          if record.events and record.events.device
                          else None)
        record.kernel_bytes = kernel_bytes.fused_chunk_per_chip(config)
        record.peaks = load_peaks(spec.paths, devices[0].device_kind)

    sample = run.sample or []
    missing = [i for i in sample if i not in run.kept]
    del svc, sched, run.svc, run.sched
    gc.collect()
    numbers = check.check_sample(config, traffic, run.kept,
                                 [i for i in sample if i in run.kept])
    # a mix may set a coverage limit its loop can meet: an open loop below
    # capacity admits every segment at a chunk boundary, none mid-chunk
    limits = {**config["limits"], **mix.get("limits", {})}
    checks = {
        "id_mismatch_frames": [numbers["id_mismatch_frames"], "<=",
                               limits["id_mismatch_frames"]],
        "box_err_px": [numbers["box_err_px"], "<=", limits["box_err_px"]],
        "checked_seqs": [len(sample) - len(missing), ">=",
                         limits["checked_seqs"]],
        "mid_chunk_seqs": [len(set(sample) & run.mid_chunk), ">=",
                           limits["mid_chunk_seqs"]],
        "sampled_never_delivered": [len(missing), "<=", 0],
        "compiles_in_window": [run.compiles_in_window
                               + run.traces_in_window, "<=", 0],
        "dispatch_errors": [run.dispatch_errors, "<=", 0],
    }
    correct = all(_holds(*c) for c in checks.values())

    metrics = {}
    for m in spec.metrics_for(cell_name, traced):
        value = spec.reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted, failed = _attempts(run, len(missing))
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if traced and record.reduced is not None:
        red = record.reduced
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        device["busy_s_by_chip"] = red.busy_by_chip
        out["breakdown"] = {"device_ops": red.top_ops,
                            "idle_gaps": red.idle_gaps}
    out["checks"] = checks
    return out


def _holds(value, op, limit) -> bool:
    return value <= limit if op == "<=" else value >= limit


def _attempts(run: Run, missing: int) -> tuple[int, int]:
    if run.measured is not None:
        first, end = run.measured
        undelivered = max(0, end - max(run.delivered, first))
        return end - first, run.sheds + run.dispatch_errors + undelivered
    return run.submitted, run.sheds + run.dispatch_errors + missing


class Record:
    """What a metric reader may read."""

    def __init__(self, cell, config, run: Run, setup_s: float):
        self.cell = cell
        self.config = config
        self.setup_s = setup_s
        w0, w1 = run.window
        self.window_s = w1 - w0
        self.frames = run.window_frames[1] - run.window_frames[0]
        self.latencies = None
        self.admission_waits = None
        if run.measured is not None:
            first, end = run.measured
            origin = run.due_origin
            due = [origin + run.traffic.due(i) for i in range(first, end)]
            self.latencies = [run.delivered_at[i] - d
                              for i, d in zip(range(first, end), due)
                              if i < len(run.delivered_at)]
            admitted = {i: step for i, step in run.sched.admissions
                        if first <= i < end}
            chunk = run.sched.chunk
            self.admission_waits = [
                run.step_start[admitted[i] // chunk] - d
                for i, d in zip(range(first, end), due) if i in admitted]
        self.events = None
        self.reduced = None
        self.kernel_bytes = None
        self.peaks = None


def load_peaks(paths: Path, kind: str) -> dict:
    table = json.loads((paths / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between order statistics)."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
