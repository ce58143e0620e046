"""Device microseconds a prompt token costs in the state-space serve
cell: the median duration of one `prefill_chunk` execution (the
trace's module line) over the rows a chunk filled on average in the
window (`snapshot()["prefill_chunk_rows"]` / `["prefill_chunks"]`,
window's end minus start; rows recomputed after a partial snapshot hit
are not among them).  A prefill blocks every decode, and the cell's
`itl_p95_ms` is a tick plus one admission's chunk.  A program without
the counters has nothing to read: `None`."""

import trace_reduce

META = {"layer": "serve programs", "unit": "us/row", "moves": "itl_p95_ms",
        "cells": ["serve-rag-closed"]}


def compute(run):
    ms = trace_reduce.median_module_ms(run["trace"], "prefill_chunk")
    if ms is None or not run.get("prefill_chunks") \
            or not run.get("prefill_chunk_rows"):
        return None
    return 1e3 * ms * run["prefill_chunks"] / run["prefill_chunk_rows"]
