"""Median Python stretch under an empty device queue after the landing
of a tick that ended a request by length: delivery, the step's tail,
the caller's bookkeeping and `submit`, the next step's expiry, probe,
claim and staging, up to the entry of the successor's first prefill
dispatch (or of the next tick's).  The first of the two waits at a
request's end (PERF.md section 5), engine's and caller's parts together.

Read from the engine's own account of its host time
(`singa_tpu/serve/metrics.py::HostAccount`), which is always on and is
published through `singa_tpu.obs.events.histogram`: the median of
`serve.exposed_ms.finish` (over its last 4,096 observations).  The
histograms aggregate in the process and nothing resets them, so the
reading is over the process's whole serving life less the turns in which
a program compiled: one warm-up round a client, on the window's own
traffic, then the window, then the drain of the requests still running
(the older runners hand a metric file no counter of the window: PERF.md
section 7 (l); one serve runner could pass the window's delta).  A
program without the account has nothing to read: `None`."""

from singa_tpu.obs.events import histogram_summary

META = {"layer": "serve scheduler", "unit": "ms",
        "moves": "serve_tokens_per_s",
        "cells": ["serve-chat-closed", "serve-code-closed",
                  "serve-reason-closed", "serve-rag-closed"]}


def compute(run):
    h = histogram_summary("serve.exposed_ms.finish")
    return h["p50"] if h else None
