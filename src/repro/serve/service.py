"""Tracking service front-end — admission, backpressure, crash recovery.

:class:`~repro.serve.scheduler.StreamScheduler` answers *how* ragged
sequences share the engine's lanes; this module answers what stands
between that scheduler and the network (DESIGN.md §11):

* **Admission control** — ``submit`` is async and *bounded*: a global
  in-flight cap plus a per-client cap, with an optional per-client token
  bucket.  Over-budget submissions are shed **explicitly** with
  :class:`Overloaded` carrying a ``retry_after`` hint — the queue never
  grows without bound, and a client is told when to come back instead of
  being silently stalled.
* **Circuit breaker** — device dispatch is wrapped in a
  CLOSED / OPEN / HALF_OPEN breaker: repeated chunk failures open it
  (submissions and steps shed fast instead of hammering a sick
  accelerator), a timed half-open probe retries one chunk, and success
  closes it again.  A failed chunk's host planning is rolled back from
  the latest checkpoint so the probe retries the *same* work.
* **Crash-exact checkpoint/restore** — at chunk boundaries the service
  snapshots the scheduler's complete state (``export_state``) plus its
  own delivery/accounting state through :mod:`repro.ckpt`.  Results are
  delivered **before** the covering checkpoint commits (at-least-once:
  a crash between delivery and commit re-delivers, never loses), so a
  SIGKILL'd server resumed with :meth:`TrackingService.resume` produces
  per-sequence outputs **bit-identical** to an uninterrupted run — the
  lane-recycling invariant (DESIGN.md §3) makes both equal the solo run.

Time is injectable (``clock=``) so rate limiting and breaker timeouts
are deterministic under test (tests/test_serving.py).
"""
from __future__ import annotations

import asyncio
import json
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.ckpt import CheckpointManager, committed_steps, restore_flat
from repro.data.stream import SequenceTracks

SERVICE_META_KEY = "__service_meta__"

# Profiler spans (``jax.profiler.TraceAnnotation``): ``svc.submit`` carries
# ``seq=<submission index>`` (the index the submission takes, or would
# have taken if shed), ``svc.checkpoint`` ``chunk=<step committed>``.
SPANS = ("svc.submit", "svc.checkpoint")


class Overloaded(Exception):
    """Explicit load shed: the service cannot take this work *right now*.

    ``retry_after`` (seconds) is the backpressure signal — an HTTP
    front-end maps it straight onto a 429/503 ``Retry-After`` header.
    ``reason`` says which limit tripped (``"rate"``, ``"queue"``,
    ``"client_queue"``, ``"breaker_open"``).
    """

    def __init__(self, reason: str, retry_after: float):
        super().__init__(f"overloaded ({reason}); retry after "
                         f"{retry_after:.3f}s")
        self.reason = reason
        self.retry_after = float(retry_after)


class TokenBucket:
    """Per-client admission rate limiter.

    ``rate`` tokens/second refill toward a ``burst`` cap; ``try_take``
    returns ``0.0`` on success or the seconds until a token would be
    available (the ``Retry-After`` hint) — it never sleeps, shedding is
    the caller's policy.
    """

    def __init__(self, rate: float, burst: float,
                 clock: Callable[[], float] = time.monotonic):
        if rate <= 0 or burst <= 0:
            raise ValueError(f"rate and burst must be > 0, got "
                             f"rate={rate}, burst={burst}")
        self.rate = float(rate)
        self.burst = float(burst)
        self._clock = clock
        self._tokens = float(burst)
        self._last = clock()

    def try_take(self, n: float = 1.0) -> float:
        now = self._clock()
        self._tokens = min(self.burst,
                           self._tokens + (now - self._last) * self.rate)
        self._last = now
        if self._tokens >= n:
            self._tokens -= n
            return 0.0
        return (n - self._tokens) / self.rate


class CircuitBreaker:
    """CLOSED / OPEN / HALF_OPEN breaker around device dispatch.

    ``failure_threshold`` consecutive failures open it; after
    ``reset_timeout`` seconds ``allow()`` grants exactly one half-open
    probe; the probe's success closes the breaker, its failure re-opens
    it (and restarts the timeout).  ``retry_after()`` is the shed hint
    while open.
    """

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half-open"

    def __init__(self, failure_threshold: int = 3, reset_timeout: float = 5.0,
                 clock: Callable[[], float] = time.monotonic):
        if failure_threshold < 1:
            raise ValueError(f"failure_threshold must be >= 1, got "
                             f"{failure_threshold}")
        self.failure_threshold = failure_threshold
        self.reset_timeout = float(reset_timeout)
        self._clock = clock
        self.state = self.CLOSED
        self.failures = 0
        self._opened_at = 0.0

    def allow(self) -> bool:
        """May a dispatch proceed right now?  Grants the half-open probe
        as a side effect once the timeout has elapsed."""
        if self.state == self.CLOSED:
            return True
        if self.state == self.OPEN and \
                self._clock() - self._opened_at >= self.reset_timeout:
            self.state = self.HALF_OPEN
            return True
        return self.state == self.HALF_OPEN

    def record_success(self) -> None:
        self.state = self.CLOSED
        self.failures = 0

    def record_failure(self) -> None:
        self.failures += 1
        if self.state == self.HALF_OPEN or \
                self.failures >= self.failure_threshold:
            self.state = self.OPEN
            self._opened_at = self._clock()

    def retry_after(self) -> float:
        if self.state != self.OPEN:
            return 0.0
        return max(0.0, self.reset_timeout -
                   (self._clock() - self._opened_at))


class TrackingService:
    """Async serving front-end over a :class:`StreamScheduler`.

    Usage::

        svc = TrackingService(sched, ckpt_dir="ckpts", rate=100, burst=20)
        idx = await svc.submit("seq-7", det_boxes, det_mask, client="cam7")
        tracks = await svc.result(idx)          # or: await svc.drain()

    ``submit`` resolves immediately (admission is host-side planning);
    the engine advances only through :meth:`step` / :meth:`drain`, which
    dispatch one scheduler chunk at a time, deliver finished sequences
    (futures + ``on_result``), and then checkpoint — every knob of the
    recovery story (delivery order, breaker rollback, resume) lives at
    this chunk granularity.
    """

    def __init__(self, scheduler, *, max_pending: int = 64,
                 per_client_pending: int = 16,
                 rate: Optional[float] = None, burst: Optional[float] = None,
                 breaker_threshold: int = 3, breaker_reset: float = 5.0,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 1,
                 keep: int = 3, retry_after_hint: float = 0.05,
                 on_result: Optional[Callable[[int, SequenceTracks],
                                              None]] = None,
                 clock: Callable[[], float] = time.monotonic):
        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        if per_client_pending < 1:
            raise ValueError(f"per_client_pending must be >= 1, got "
                             f"{per_client_pending}")
        if ckpt_every < 1:
            raise ValueError(f"ckpt_every must be >= 1, got {ckpt_every}")
        self.sched = scheduler
        self.max_pending = max_pending
        self.per_client_pending = per_client_pending
        self.retry_after_hint = retry_after_hint
        self.on_result = on_result
        self._clock = clock
        self._rate = rate
        self._burst = burst if burst is not None else rate
        self._buckets: dict[str, TokenBucket] = {}
        self.breaker = CircuitBreaker(breaker_threshold, breaker_reset,
                                      clock=clock)
        self.ckpt_every = ckpt_every
        self.ckpt = (CheckpointManager(ckpt_dir, keep=keep)
                     if ckpt_dir is not None else None)

        # delivery/accounting state (all of it crosses the checkpoint)
        self._client_of: dict[int, str] = {}     # live submission -> client
        self._inflight: dict[str, int] = {}      # client -> live count
        self._next_result = scheduler._ready.next_index
        self.completed: dict[int, SequenceTracks] = {}
        self._futures: dict[int, asyncio.Future] = {}
        self.sheds: list[tuple[str, str, float]] = []  # (client, reason, hint)

    # -------------------------------------------------------------- intake
    @property
    def pending(self) -> int:
        """Live (admitted, unfinished-or-undelivered) submissions."""
        return sum(self._inflight.values())

    def _bucket(self, client: str) -> Optional[TokenBucket]:
        if self._rate is None:
            return None
        if client not in self._buckets:
            self._buckets[client] = TokenBucket(self._rate, self._burst,
                                                clock=self._clock)
        return self._buckets[client]

    async def submit(self, name: str, det_boxes: np.ndarray,
                     det_mask: np.ndarray, *, client: str = "default",
                     det_class: Optional[np.ndarray] = None,
                     det_embed: Optional[np.ndarray] = None) -> int:
        """Admit one sequence or shed it with :class:`Overloaded`.

        Checks run cheapest-first: breaker state, the client's token
        bucket, then the queue bounds — a shed consumes no bucket token
        beyond the rate check itself and leaves no state behind."""
        with jax.profiler.TraceAnnotation("svc.submit",
                                          seq=self.sched._num_submitted):
            if self.breaker.state == CircuitBreaker.OPEN:
                self._shed(client, "breaker_open",
                           max(self.breaker.retry_after(),
                               self.retry_after_hint))
            bucket = self._bucket(client)
            if bucket is not None:
                wait = bucket.try_take()
                if wait > 0.0:
                    self._shed(client, "rate", wait)
            if self.pending >= self.max_pending:
                self._shed(client, "queue", self.retry_after_hint)
            if self._inflight.get(client, 0) >= self.per_client_pending:
                self._shed(client, "client_queue", self.retry_after_hint)
            idx = self.sched.submit(name, det_boxes, det_mask,
                                    det_class=det_class, det_embed=det_embed)
            self._client_of[idx] = client
            self._inflight[client] = self._inflight.get(client, 0) + 1
            # zero-frame sequences finalize inside submit(); release them
            # (and anything they unblocked) without waiting for a chunk
            # dispatch.
            self._deliver(self.sched.pop_ready())
            return idx

    def _shed(self, client: str, reason: str, retry_after: float):
        self.sheds.append((client, reason, retry_after))
        raise Overloaded(reason, retry_after)

    # ------------------------------------------------------------- pumping
    @property
    def busy(self) -> bool:
        return self.sched.busy

    async def step(self) -> list[SequenceTracks]:
        """Dispatch one scheduler chunk through the breaker, deliver what
        finished, then checkpoint the boundary.

        Failure path: the exception is recorded with the breaker and the
        scheduler is rolled back to the latest committed checkpoint (a
        failed dispatch leaves host planning advanced past device state
        — rollback realigns them so the half-open probe retries the same
        chunk).  The original exception propagates.
        """
        if not self.breaker.allow():
            raise Overloaded("breaker_open",
                             max(self.breaker.retry_after(),
                                 self.retry_after_hint))
        try:
            results = self.sched.run_chunk()
        except Exception:
            self.breaker.record_failure()
            self._rollback()
            raise
        self.breaker.record_success()
        self._deliver(results)
        if self.ckpt is not None and \
                self.sched.chunks_run % self.ckpt_every == 0:
            self.checkpoint()
        return results

    async def drain(self, max_failures: Optional[int] = None
                    ) -> list[SequenceTracks]:
        """Step until the scheduler owes nothing, pacing around an open
        breaker.  ``max_failures`` bounds dispatch failures (then the
        last one re-raises); ``None`` retries forever."""
        out: list[SequenceTracks] = []
        failures = 0
        while self.busy:
            if not self.breaker.allow():
                await asyncio.sleep(min(self.breaker.retry_after(), 0.05))
                continue
            try:
                out.extend(await self.step())
            except Overloaded:
                continue
            except Exception:
                failures += 1
                if max_failures is not None and failures > max_failures:
                    raise
        if self.ckpt is not None:
            self.ckpt.wait()            # surface any async write failure
        return out

    async def result(self, index: int) -> SequenceTracks:
        """Await one submission's finished tracks (submission index from
        :meth:`submit`).  Already-delivered results resolve immediately —
        including after :meth:`resume`, where re-delivered duplicates
        land in ``completed`` before any future exists."""
        if index in self.completed:
            return self.completed[index]
        fut = self._futures.get(index)
        if fut is None:
            fut = self._futures[index] = \
                asyncio.get_running_loop().create_future()
        return await fut

    def _deliver(self, results: list[SequenceTracks]) -> None:
        """Hand finished sequences to their consumers — BEFORE the
        covering checkpoint commits (at-least-once, DESIGN.md §11).
        Tolerates re-delivery after a rollback or resume: futures may
        already be resolved, files already written (idempotent)."""
        for tracks in results:
            idx = self._next_result
            self._next_result += 1
            self.completed[idx] = tracks
            client = self._client_of.pop(idx, None)
            if client is not None:
                left = self._inflight.get(client, 0) - 1
                if left > 0:
                    self._inflight[client] = left
                else:
                    self._inflight.pop(client, None)
            fut = self._futures.get(idx)
            if fut is not None and not fut.done():
                fut.set_result(tracks)
            if self.on_result is not None:
                self.on_result(idx, tracks)

    # -------------------------------------------------- checkpoint/restore
    def checkpoint(self, wait: bool = False) -> int:
        """Snapshot the FULL service state at the current chunk boundary;
        returns the step number.  The write is async (double-buffered);
        any failure surfaces on the next call or :meth:`close` — never
        silently (repro.ckpt contract)."""
        if self.ckpt is None:
            raise ValueError("service was constructed without ckpt_dir")
        with jax.profiler.TraceAnnotation("svc.checkpoint",
                                          chunk=self.sched.chunks_run):
            meta, arrays = self.sched.export_state()
            smeta = {
                "schema": 1,
                "sched": meta,
                "service": {
                    "next_result": self._next_result,
                    "client_of": {str(i): c
                                  for i, c in self._client_of.items()},
                },
            }
            blob = np.frombuffer(json.dumps(smeta).encode(), np.uint8).copy()
            tree = dict(arrays)
            tree[SERVICE_META_KEY] = blob
            step = self.sched.chunks_run
            self.ckpt.save_async(step, tree)
            if wait:
                self.ckpt.wait()
            return step

    def _rollback(self) -> None:
        """Re-import the latest committed checkpoint after a dispatch
        failure, realigning host planning with device state.  Without a
        checkpoint directory (or before the first commit) this is a
        no-op: the failed chunk's planned frames are lost to this
        process, exactly the gap checkpoints exist to close."""
        if self.ckpt is None:
            return
        self.ckpt.wait()
        steps = committed_steps(self.ckpt.ckpt_dir)
        if not steps:
            return
        flat, _ = restore_flat(self.ckpt.ckpt_dir, step=steps[-1])
        smeta = json.loads(bytes(flat.pop(SERVICE_META_KEY).tobytes())
                           .decode())
        self.sched.import_state(smeta["sched"], flat)
        self._next_result = self.sched._ready.next_index

    @classmethod
    def resume(cls, scheduler, ckpt_dir: str, *, step: Optional[int] = None,
               **knobs) -> "TrackingService":
        """Rebuild a service from its latest (or ``step``-th) committed
        checkpoint.  ``scheduler`` must be freshly constructed with a
        semantically identical engine; the execution strategy may differ
        (the state contract is topology-neutral, DESIGN.md §11) — a
        same-strategy resume is bit-exact, a cross-strategy one exact in
        track identities and allclose in coordinates.  The scheduler's
        pre-resume contents are discarded by ``import_state``.  Accepts
        the same ``**knobs`` as the constructor (``ckpt_dir`` is implied).
        """
        flat, _ = restore_flat(ckpt_dir, step=step)
        if SERVICE_META_KEY not in flat:
            raise ValueError(
                f"checkpoint in {ckpt_dir!r} carries no service metadata "
                f"({SERVICE_META_KEY}) — it is a bare-scheduler or model "
                f"checkpoint, not a TrackingService snapshot")
        smeta = json.loads(bytes(flat.pop(SERVICE_META_KEY).tobytes())
                           .decode())
        if smeta.get("schema") != 1:
            raise ValueError(f"unsupported service checkpoint schema "
                             f"{smeta.get('schema')!r}")
        scheduler.import_state(smeta["sched"], flat)
        svc = cls(scheduler, ckpt_dir=ckpt_dir, **knobs)
        svc._next_result = int(smeta["service"]["next_result"])
        for i, client in smeta["service"]["client_of"].items():
            svc._client_of[int(i)] = client
            svc._inflight[client] = svc._inflight.get(client, 0) + 1
        return svc

    def close(self) -> None:
        """Flush the async checkpoint writer; raises any deferred write
        failure (the no-silent-loss contract)."""
        if self.ckpt is not None:
            self.ckpt.wait()
