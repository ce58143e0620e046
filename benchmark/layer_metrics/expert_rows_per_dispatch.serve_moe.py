"""Rows an expert multiplies per dispatch, on average: the window's
delta of `metrics.snapshot()["moe_assignments"]` (valid tokens x top-k
of every prefill chunk and decode tick) over experts x the delta of
`moe_dispatches`.  32 tokens x 8 of 64 experts = 4 while a dispatch is
one 32-token chunk or one token a slot; it is what a larger prefill
chunk raises.  A program without the counters has nothing to read:
`None`."""

META = {"layer": "expert layer", "unit": "rows",
        "moves": "serve_tokens_per_s", "cells": ["serve-code-closed"]}


def compute(run):
    if not run.get("moe_dispatches"):
        return None
    return run["moe_assignments"] / (run["moe_config"]["num_experts"]
                                     * run["moe_dispatches"])
