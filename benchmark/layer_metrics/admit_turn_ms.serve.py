"""Median wall of a turn of the step loop (one entry of `step()` to the
next) in which a request was admitted: its prefill chunks, their fetch
and the tick beside them.  Every running stream waits such a turn out,
so it is the long gap of the inter-token tail.

Read from the engine's own account of its host time
(`singa_tpu/serve/metrics.py::HostAccount`), which is always on and is
published through `singa_tpu.obs.events.histogram`: the median of
`serve.turn_ms.admitting` (over its last 4,096 observations).  The
histograms aggregate in the process and nothing resets them, so the
reading is over the process's whole serving life less the turns in which
a program compiled: one warm-up round a client, on the window's own
traffic, then the window, then the drain of the requests still running
(the older runners hand a metric file no counter of the window: PERF.md
section 7 (l); one serve runner could pass the window's delta).  A
program without the account has nothing to read: `None`."""

from singa_tpu.obs.events import histogram_summary

META = {"layer": "serve scheduler", "unit": "ms",
        "moves": "itl_p95_ms",
        "cells": ["serve-chat-closed", "serve-code-closed",
                  "serve-reason-closed", "serve-rag-closed"]}


def compute(run):
    h = histogram_summary("serve.turn_ms.admitting")
    return h["p50"] if h else None
