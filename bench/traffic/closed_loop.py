"""Closed loop: one client keeps the queue deep, so no lane idles.

Mix parameters: ``queue_lanes`` (submissions queued beyond the lanes,
as a multiple of the lane count), ``warm_chunks`` (chunks served before
the window opens) and ``drain_s`` (how long past the window to wait for
the sampled answers).  The window opens at a chunk boundary and closes at
the first boundary after ``seconds``.  It owes the sequences whose last
frame it stepped; past it the service keeps serving, untimed and with no
more submissions, until each sampled one is delivered in its turn.
"""
from __future__ import annotations


async def drive(run, seconds: float) -> None:
    lanes = run.sched.num_lanes
    depth = int(round(run.mix.get("queue_lanes", 1.0) * lanes))
    drain_s = float(run.mix.get("drain_s", 60.0))

    def top_up():
        while run.queued() < depth:
            run.submit(run.submitted)

    with run.span("bench.submit"):
        while run.submitted < lanes + depth:
            run.submit(run.submitted)
    for _ in range(int(run.mix.get("warm_chunks", 2))):
        await run.step()
        with run.span("bench.submit"):
            top_up()
    run.open_window()
    while True:
        await run.step()
        with run.span("bench.submit"):
            top_up()
        if run.clock() - run.window[0] >= seconds:
            break
    run.close_window()
    run.owe(run.finished_in_window())
    while run.owed() and run.sched.busy and \
            run.clock() - run.window[1] <= drain_s:
        await run.step()
