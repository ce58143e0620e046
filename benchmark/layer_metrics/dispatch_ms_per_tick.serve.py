"""Median time a turn of the step loop spends inside the engine's
dispatch calls (the decode tick's, and a prefill chunk's where the turn
admitted), over the turns whose decode tick was dispatched ahead of the
one before.  It is the runtime's host work to launch a program, not the
engine's Python: beside `host_ms_per_tick.serve` it is the other part
of what the host needs between a landing and the next launch, and the
part that fewer or smaller arguments to a program would shorten and
faster Python would not.

Read from the engine's own account of its host time
(`singa_tpu/serve/metrics.py::HostAccount`), which is always on and is
published through `singa_tpu.obs.events.histogram`: the median of
`serve.dispatch_ms` (over its last 4,096 observations).  The histograms
aggregate in the process and nothing resets them, so the reading is over
the process's whole serving life less the turns in which a program
compiled: one warm-up round a client, on the window's own traffic, then
the window, then the drain of the requests still running (the older
runners hand a metric file no counter of the window: PERF.md section 7
(l); one serve runner could pass the window's delta).  A program without
the account has nothing to read: `None`."""

from singa_tpu.obs.events import histogram_summary

META = {"layer": "serve scheduler", "unit": "ms",
        "moves": "serve_tokens_per_s",
        "cells": ["serve-chat-closed", "serve-code-closed",
                  "serve-reason-closed", "serve-rag-closed"]}


def compute(run):
    h = histogram_summary("serve.dispatch_ms")
    return h["p50"] if h else None
