"""Milliseconds a minute of the step loop that the host's stalls take:
over the turns whose Python time (wall outside runtime calls) passed
50 ms, the excess over 50 ms, summed, per minute of summed turns.  0.0
where turns were counted and none was slow.  A stall inside a runtime
call is not told from device time on the host's clock and is not here.

Read from the engine's own account of its host time
(`singa_tpu/serve/metrics.py::HostAccount`), which is always on and is
published through `singa_tpu.obs.events.histogram`: the sum of
`serve.slow_turn_ms` over the sum of `serve.turn_ms`.  The histograms
aggregate in the process and nothing resets them, so the reading is over
the process's whole serving life less the turns in which a program
compiled: one warm-up round a client, on the window's own traffic, then
the window, then the drain of the requests still running (the older
runners hand a metric file no counter of the window: PERF.md section 7
(l); one serve runner could pass the window's delta).  The metric prints
in a `--trace 1` run only, and there the harness's own start of the
profiler, made between two steps of the window, is a slow turn of the
caller's (`phase` `return>step`, about 50 ms of Python: 1-2 ms of excess)
that no plain run has.  A program without the account has nothing to
read: `None`."""

from singa_tpu.obs.events import histogram_summary

META = {"layer": "serve scheduler", "unit": "ms/min",
        "moves": "serve_tokens_per_s",
        "cells": ["serve-chat-closed", "serve-code-closed",
                  "serve-reason-closed", "serve-rag-closed"]}


def compute(run):
    turns = histogram_summary("serve.turn_ms")
    if turns is None:
        return None
    slow = histogram_summary("serve.slow_turn_ms")
    return (slow["sum"] if slow else 0.0) / (turns["sum"] / 60e3)
