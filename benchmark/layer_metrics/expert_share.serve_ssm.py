"""Share of the device's busy time spent in ops that stream the stacked
expert weights (`moe_count.streams_expert_weights`) in the state-space
serve cell: the 9 experts of width 768 this chip holds of each layer's
72, every held stack streamed by every tick and every chunk.  A run
without the configuration's keys has nothing to read: `None`."""

import moe_count
import ssm_count

META = {"layer": "expert layer", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-rag-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("ssm_config")
    if not trace or not c:
        return None
    seconds = moe_count.expert_op_seconds(trace["ops"],
                                          ssm_count.expert_keys(c))
    return 100.0 * seconds / trace["busy_s"] if seconds else None
