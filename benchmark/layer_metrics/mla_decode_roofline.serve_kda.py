"""The cached latent rows the traced decode ticks of the KDA serve cell
had to read, over what the HBM peak moves in the latent kernel's time
(in %): the window's delta of `snapshot()["mla_latent_bytes"]` a tick,
times the `decode_paged` executions in the trace, over the seconds of
the ops named `mla_decode_attention` (`mla_count`).  One MLA layer of
eight reads ~4k rows a slot here.  A program without the counter or
the kernel has nothing to read: `None`."""

import mla_count

META = {"layer": "latent attention", "unit": "%", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    trace = run.get("trace")
    if not trace or not run.get("peak") or not run.get("decode_ticks") \
            or not run.get("mla_latent_bytes"):
        return None
    ticks = sum(len(v) for k, v in trace["module_ms"].items()
                if "decode_paged" in k)
    nbytes = run["mla_latent_bytes"] / run["decode_ticks"] * ticks
    return mla_count.roofline(nbytes, mla_count.decode_seconds(trace["ops"]),
                              run["peak"]["hbm_bytes_per_s"])
