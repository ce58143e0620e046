"""Expert weights the traced programs of the state-space serve cell had
to stream, over what the HBM peak moves in the time of the ops that
streamed them (`moe_count.expert_roofline`: executions of
`prefill_chunk` and `decode_paged` x 10 layers x the 9 held stacks'
169,869,312 B a layer / (peak bytes/s x seconds of those ops), in %).
A share of the bandwidth roof: a tick's 24 rows stream; a chunk's 256
rows through every held expert are compute instead and pull the
reading down.  Overlapping ops count their seconds twice: it reads low,
never high."""

import moe_count
import ssm_count

META = {"layer": "expert layer", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-rag-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("ssm_config")
    if not trace or not c or not run.get("peak"):
        return None
    keys = ssm_count.expert_keys(c)
    executions = sum(len(v) for k, v in trace["module_ms"].items()
                     if "prefill_chunk" in k or "decode_paged" in k)
    return moe_count.expert_roofline(
        executions, moe_count.expert_op_seconds(trace["ops"], keys), keys,
        run["peak"]["hbm_bytes_per_s"])
