"""Median wall of a turn of the step loop in which a request was
admitted, in the KDA serve cell: its snapshot entry, its prefill chunks,
their fetch and the tick beside them.  Every running stream waits such a
turn out, so it is the long gap of the inter-token tail.

Read from the engine's own account of its host time
(`singa_tpu/serve/metrics.py::HostAccount`), published through
`singa_tpu.obs.events.histogram`: the median of
`serve.turn_ms.admitting` over its last 4,096 observations, which span
the warm-up's last turns, the window and the check's one step.  A
program without the account has nothing to read: `None`."""

from singa_tpu.obs.events import histogram_summary

META = {"layer": "serve scheduler", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    h = histogram_summary("serve.turn_ms.admitting")
    return h["p50"] if h else None
