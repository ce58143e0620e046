"""The recurrent state the traced decode ticks had to read and write,
over what the HBM peak moves in the time of the ops that name the
per-slot state (`ssm_count.state_roofline`, in %).  The bytes are the
program's own count: the window's delta of `snapshot()
["ssm_state_bytes"]` (each running slot's state once each way a tick)
a tick of the window, times the `decode_paged` executions in the trace.
A state update that passes over the state more often than once each
way, or that waits on something else, reads low.  A program without
the counter has nothing to read: `None`."""

import ssm_count

META = {"layer": "state-space layer", "unit": "%",
        "moves": "serve_tokens_per_s", "cells": ["serve-rag-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("ssm_config")
    if not trace or not c or not run.get("peak") \
            or not run.get("decode_ticks") or not run.get("ssm_state_bytes"):
        return None
    ticks = sum(len(v) for k, v in trace["module_ms"].items()
                if "decode_paged" in k)
    nbytes = run["ssm_state_bytes"] / run["decode_ticks"] * ticks
    seconds = ssm_count.state_update_seconds(
        trace["ops"], c, c["engine"]["num_slots"])
    return ssm_count.state_roofline(nbytes, seconds,
                                    run["peak"]["hbm_bytes_per_s"])
