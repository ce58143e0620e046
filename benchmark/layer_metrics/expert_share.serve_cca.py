"""Share of the device's busy time spent in ops that stream the stacked
expert weights (`moe_count.streams_expert_weights`) in the CCA serve
cell: 16 experts of width 2048 at top-1, every stack streamed by every
tick and every chunk.  A run without the configuration's keys has
nothing to read: `None`."""

import moe_count

META = {"layer": "expert layer", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-reason-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("moe_config")
    if not trace or not c:
        return None
    seconds = moe_count.expert_op_seconds(trace["ops"], c)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
