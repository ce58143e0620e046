"""Share of decode ticks dispatched while the tick before had not
landed, so that their launch and the other's landing ran beside a busy
chip: `decode_ticks_ahead / decode_ticks` of the engine's counters,
through the one observation a tick (1.0 or 0.0) that reaches a metric
file.

Read from the engine's own account of its host time
(`singa_tpu/serve/metrics.py::HostAccount`), which is always on and is
published through `singa_tpu.obs.events.histogram`: the mean of
`serve.tick_ahead`.  The histograms aggregate in the process and nothing
resets them, so the reading is over the process's whole serving life
less the turns in which a program compiled: one warm-up round a client,
on the window's own traffic, then the window, then the drain of the
requests still running (the older runners hand a metric file no counter
of the window: PERF.md section 7 (l); one serve runner could pass the
window's delta).  A program without the account has nothing to read:
`None`."""

from singa_tpu.obs.events import histogram_summary

META = {"layer": "serve scheduler", "unit": "%",
        "moves": "serve_tokens_per_s",
        "cells": ["serve-chat-closed", "serve-code-closed",
                  "serve-reason-closed", "serve-rag-closed"]}


def compute(run):
    h = histogram_summary("serve.tick_ahead")
    return 100.0 * h["mean"] if h else None
