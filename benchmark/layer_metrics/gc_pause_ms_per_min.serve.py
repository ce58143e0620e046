"""Milliseconds a minute of the step loop that Python's collector paused
the stepping thread for, in pauses of a millisecond or more
(`singa_tpu.obs.events.watch_gc`), per minute of summed turns.  0.0
where turns were counted and no such pause fell in one.  Beside
`slow_turn_ms_per_min.serve` it says whether the host's stalls are the
collector.

Read from the engine's own account of its host time
(`singa_tpu/serve/metrics.py::HostAccount`), which is always on and is
published through `singa_tpu.obs.events.histogram`: the sum of
`serve.turn_gc_ms` over the sum of `serve.turn_ms`.  The histograms
aggregate in the process and nothing resets them, so the reading is over
the process's whole serving life less the turns in which a program
compiled: one warm-up round a client, on the window's own traffic, then
the window, then the drain of the requests still running (the older
runners hand a metric file no counter of the window: PERF.md section 7
(l); one serve runner could pass the window's delta).  A program without
the account has nothing to read: `None`."""

from singa_tpu.obs.events import histogram_summary

META = {"layer": "serve scheduler", "unit": "ms/min",
        "moves": "serve_tokens_per_s",
        "cells": ["serve-chat-closed", "serve-code-closed",
                  "serve-reason-closed", "serve-rag-closed"]}


def compute(run):
    turns = histogram_summary("serve.turn_ms")
    if turns is None:
        return None
    paused = histogram_summary("serve.turn_gc_ms")
    return (paused["sum"] if paused else 0.0) / (turns["sum"] / 60e3)
