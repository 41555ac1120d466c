"""Open loop: arrivals on the generator's schedule, whatever the service
does.

Mix parameters: ``rate_per_s`` and ``cameras`` (read by the generator),
``warm_s`` (seconds of arrivals before the window opens) and
``drain_s`` (how long past the window to wait for its last deliveries).
Every arrival due before a chunk boundary is submitted at that boundary,
so a late submission is timed from when it was due.  The window opens at
the first chunk boundary after ``warm_s`` and closes at the first after
``seconds`` more; the segments due in it are the measured ones, and the
service keeps serving, with arrivals going on, until each is delivered.
"""
from __future__ import annotations

import asyncio


async def drive(run, seconds: float) -> None:
    traffic = run.traffic
    warm_s = float(run.mix.get("warm_s", 3.0))
    drain_s = float(run.mix.get("drain_s", 60.0))
    t0 = run.clock()
    run.due_origin = t0
    nxt = 0
    window_due = None            # (first, end) arrival index of the window

    def arrivals():
        nonlocal nxt
        now = run.clock()
        while t0 + traffic.due(nxt) <= now:
            run.submit(nxt)
            nxt += 1

    while True:
        with run.span("bench.submit"):
            arrivals()
        if run.sched.busy:
            await run.step()
        else:
            await asyncio.sleep(max(0.0, t0 + traffic.due(nxt)
                                    - run.clock()))
            continue
        now = run.clock()
        if run.window is None and now - t0 >= warm_s:
            run.open_window()
        elif run.window is not None and run.window[1] is None and \
                now - run.window[0] >= seconds:
            run.close_window()
            w0, w1 = (w - t0 for w in run.window)
            first = 0
            while traffic.due(first) < w0:
                first += 1
            end = first
            while traffic.due(end) < w1:
                end += 1
            window_due = (first, end)
            run.measure(first, end)
        if window_due is not None and (
                run.delivered >= window_due[1]
                or now - run.window[1] > drain_s):
            break
