"""The least time the traced prefill chunks' KDA chunked form could take,
over the time its ops took (in %): the larger of its FLOPs over the bf16
peak and its bytes over the HBM peak (`kda_count.prefill_flops`,
`prefill_bytes`), for the rows the form runs, the chunk's whole
`chunk_rows` (its pad rows too, which it computes like any other) times
the `prefill_chunk` executions in the trace, against the device seconds
of the ops traced under `kda.prefill` (`kda_count.scope_seconds`).  A
program without the scope has nothing to read: `None`."""

import kda_count

META = {"layer": "linear attention", "unit": "%", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("kda_config")
    seconds = (run.get("kda_scoped_s") or {}).get("kda.prefill")
    if not trace or not c or not run.get("peak") or not seconds \
            or not run.get("chunk_rows"):
        return None
    chunks = sum(len(v) for k, v in trace["module_ms"].items()
                 if "prefill_chunk" in k)
    rows = run["chunk_rows"] * chunks
    least = max(kda_count.prefill_flops(c, rows) / run["peak"]["flops"],
                kda_count.prefill_bytes(c, rows, chunks)
                / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
