"""Expert weights the traced programs of the CCA serve cell had to
stream, over what the HBM peak moves in the time of the ops that
streamed them (`moe_count.expert_roofline`: executions of
`prefill_chunk` and `decode_paged` x layers x bytes of one layer's
stacks / (peak bytes/s x seconds of those ops), in %).  A share of the
bandwidth roof: a tick's 64 rows stream; a chunk's 256 rows through
every one of 16 experts are 16 times the routed FLOPs and sit under the
MXU's roof instead, so chunks pull the reading down.  Overlapping ops
count their seconds twice: it reads low, never high."""

import moe_count

META = {"layer": "expert layer", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-reason-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("moe_config")
    if not trace or not c or not run.get("peak"):
        return None
    executions = sum(len(v) for k, v in trace["module_ms"].items()
                     if "prefill_chunk" in k or "decode_paged" in k)
    return moe_count.expert_roofline(
        executions, moe_count.expert_op_seconds(trace["ops"], c), c,
        run["peak"]["hbm_bytes_per_s"])
