"""Share of the device's busy time spent in the Kimi delta attention
layers: every op traced under the `kda` scope (projections, convolution,
the chunked form in prefill, the decode kernel, the gated norm and the
output projection), read from the raw profile's scopes
(`kda_count.scope_seconds`).  A program without the scopes, or a trace
whose ops carry none, has nothing to read: `None`."""

META = {"layer": "linear attention", "unit": "%", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    trace, scoped = run.get("trace"), run.get("kda_scoped_s") or {}
    if not trace or not scoped.get("kda"):
        return None
    return 100.0 * scoped["kda"] / trace["busy_s"]
