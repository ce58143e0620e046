"""Share of the traced span in which device 0 was idle and no phase of
the engine's step claims the gap: idle seconds whose innermost host
event is the benchmark's `bench.step`, the engine's outer `serve.step`,
or nothing at all, over the traced span.  It measures what the spans
inside `ServeEngine.step()` fail to cover, and rises when engine code
is added outside a span.  With `idle_engine_python.serve` and the gaps
under the runtime's own events it sums to `device_idle.serve`."""

META = {"layer": "serve scheduler", "unit": "%",
        "moves": "serve_tokens_per_s", "cells": ["serve-chat-closed"]}

UNATTRIBUTED = ("bench.step", "serve.step", "outside the benchmark's spans")


def compute(run):
    trace = run.get("trace")
    if not trace:
        return None
    idle = sum(sec for name, sec in trace["idle_by_span"].items()
               if name in UNATTRIBUTED)
    return 100.0 * idle / trace["window_s"]
