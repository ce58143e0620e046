"""Expert weights the traced programs had to stream, over what the HBM
peak moves in the time of the ops that streamed them:

    executions of `prefill_chunk` and `decode_paged` in the trace
    x layers x bytes of one layer's stacks (`moe_count`)
    / (peak bytes/s x seconds of those ops), in %.

Bound by bandwidth, not by FLOPs: at ~4 rows an expert the matmuls are
weight streaming.  Ops that overlap (an async prefetch under a matmul)
count their seconds twice, so the share reads low, never high; a value
over 100 means the matcher misses ops that stream the weights."""

import moe_count

META = {"layer": "expert layer", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-code-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("moe_config")
    if not trace or not c or not run.get("peak"):
        return None
    executions = sum(len(v) for k, v in trace["module_ms"].items()
                     if "prefill_chunk" in k or "decode_paged" in k)
    return moe_count.expert_roofline(
        executions, moe_count.expert_op_seconds(trace["ops"], c), c,
        run["peak"]["hbm_bytes_per_s"])
