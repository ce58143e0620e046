"""Rows an expert is routed per dispatch, on average, in the CCA serve
cell: the window's delta of `metrics.snapshot()["moe_assignments"]`
(valid tokens x top-1 of every prefill chunk and decode tick) over 16
experts x the delta of `moe_dispatches`: 64 slots / 16 = 4 a tick.  A
program without the counters has nothing to read: `None`."""

META = {"layer": "expert layer", "unit": "rows",
        "moves": "serve_tokens_per_s", "cells": ["serve-reason-closed"]}


def compute(run):
    if not run.get("moe_dispatches"):
        return None
    return run["moe_assignments"] / (run["moe_config"]["num_experts"]
                                     * run["moe_dispatches"])
