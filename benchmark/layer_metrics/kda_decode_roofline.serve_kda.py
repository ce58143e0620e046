"""The KDA state and vectors the traced decode ticks had to move, over
what the HBM peak moves in the decode kernel's time (in %).  Each
running slot's f32 state, every KDA layer's, read and written once a
tick (`kda_count.state_bytes_per_slot`, from the configuration), times
the running slots a tick (the window's (slot, tick) pairs, which the
program counts in `snapshot()["ssm_state_bytes"]`, over its ticks),
times the `decode_paged` executions in the trace, plus the rows'
vectors (`kda_count.decode_bytes`); the seconds are the ops named
`kda_decode`.  A program without the kernel has nothing to read:
`None`."""

import kda_count

META = {"layer": "linear attention", "unit": "%", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("kda_config")
    if not trace or not c or not run.get("peak") \
            or not run.get("decode_ticks") or not run.get("slot_ticks"):
        return None
    ticks = sum(len(v) for k, v in trace["module_ms"].items()
                if "decode_paged" in k)
    nbytes = kda_count.decode_bytes(
        c, 2 * kda_count.state_bytes_per_slot(c)
        * run["slot_ticks"] / run["decode_ticks"] * ticks)
    return kda_count.roofline(nbytes, kda_count.decode_seconds(trace["ops"]),
                              run["peak"]["hbm_bytes_per_s"])
