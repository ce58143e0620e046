"""Share of the traced span in which device 0 waited on the engine's
own Python: idle seconds whose innermost host event is a phase span of
`ServeEngine.step()` (`serve.expire`, `serve.admit.claim`,
`serve.prefill.stage`, `serve.deliver`, ...: any `serve.*` name but the
outer `serve.step`), over the traced span.  `breakdown.idle_gaps` has
the same seconds phase by phase.  A gap under one of the runtime's own
events (`DevicePut`, `PjitFunction(...)`, `np.asarray(jax.Array)`)
keeps that event's name and is counted in neither this metric nor
`idle_unattributed.serve`.  A program without the phase spans (no
`serve.*` name under any gap) has nothing to read here: `None`.
The names come from each gap's midpoint on the host's line, so a
profiler session whose device clock sits a millisecond off the host's
moves seconds between this metric and the runtime's names (PERF.md
section 5)."""

META = {"layer": "serve scheduler", "unit": "%",
        "moves": "serve_tokens_per_s", "cells": ["serve-chat-closed"]}


def compute(run):
    trace = run.get("trace")
    if not trace:
        return None
    idle = [sec for name, sec in trace["idle_by_span"].items()
            if name.startswith("serve.") and name != "serve.step"]
    return 100.0 * sum(idle) / trace["window_s"] if idle else None
