"""Share of the step loop's wall in which the device queue stood empty
under the host's Python: every stretch between two of the engine's
runtime calls that began with nothing dispatched left unfetched (after
the landing of a tick that ended a request, after an admission's token
fetch), summed, over the summed turns of the loop.  The host's own
clock, no second one: it is the floor under `device_idle.*`, and
`device_idle.*` less this is the runtime's wake-up and launch latency,
which only work kept in flight can hide and faster Python cannot.

Read from the engine's own account of its host time
(`singa_tpu/serve/metrics.py::HostAccount`), which is always on and is
published through `singa_tpu.obs.events.histogram`: the sums of
`serve.exposed_ms.finish`, `.admit` and `.other` over the sum of
`serve.turn_ms`.  The histograms aggregate in the process and nothing
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
    turns = histogram_summary("serve.turn_ms")
    if turns is None:
        return None
    exposed = [histogram_summary("serve.exposed_ms." + cause)
               for cause in ("finish", "admit", "other")]
    return 100.0 * sum(h["sum"] for h in exposed if h) / turns["sum"]
