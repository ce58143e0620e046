"""Share of the device's busy time spent in ops that stream the stacked
expert weights (`moe_count.streams_expert_weights`: the expert matmuls
and the copies and prefetches of the stacks), from the trace's op line.
The proof that the mechanism the cell exists for does most of its
work.  A run without the configuration's keys (a dense cell) has
nothing to read: `None`."""

import moe_count

META = {"layer": "expert layer", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-code-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("moe_config")
    if not trace or not c:
        return None
    seconds = moe_count.expert_op_seconds(trace["ops"], c)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
