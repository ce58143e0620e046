"""Closed loop: a fixed number of clients, each submitting its next
request when its last one completes.

The loop of tools/loadgen.py::run_load (submit what is due, then
`engine.step()`), with the sizes and the clock of the benchmark: every
token is stamped by the benchmark through `on_token`, not by the
engine's submit clock.
"""

from __future__ import annotations

import time

import jax


class Request:
    __slots__ = ("client", "prompt_len", "submit", "stamps", "done_at",
                 "handle")

    def __init__(self, client, prompt_len):
        self.client, self.prompt_len = client, prompt_len
        self.submit = self.done_at = self.handle = None
        self.stamps = []          # host clock at each delivered token


def drive(eng, streams, seconds: float, warmup_rounds: int, on_step):
    """Run `streams` (one iterator of (prompt, max_new_tokens) per
    client) against `eng`.  Warm-up lasts until every client has
    completed `warmup_rounds` requests; the window opens then and lasts
    `seconds`.  `on_step(elapsed)` is called after each engine step of
    the window.  Returns (requests, window start, window end, the
    engine's active slots after each step of the window, the engine's
    counters at the window's start)."""
    clock = time.perf_counter
    requests, live = [], {}
    completed = [0] * len(streams)

    def submit(c):
        prompt, new = next(streams[c])
        r = Request(c, int(prompt.size))
        with jax.profiler.TraceAnnotation("bench.submit"):
            r.submit = clock()
            r.handle = eng.submit(
                prompt, max_new_tokens=new,
                on_token=lambda tok, h, r=r: r.stamps.append(clock()))
        live[c] = r
        requests.append(r)

    for c in range(len(streams)):
        submit(c)
    w0, active, step_ends, snap0 = None, [], [], None
    while True:
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        now = clock()
        with jax.profiler.TraceAnnotation("bench.bookkeeping"):
            for c, r in list(live.items()):
                if r.handle.done:
                    r.done_at = now
                    completed[c] += 1
                    submit(c)
            if w0 is None:
                if min(completed) >= warmup_rounds:
                    w0, snap0 = clock(), eng.metrics.snapshot()
                continue
            active.append(eng.pool.active_count)
            step_ends.append(now)
            on_step(now - w0)
        if now - w0 >= seconds:
            return requests, w0, now, active, step_ends, snap0
