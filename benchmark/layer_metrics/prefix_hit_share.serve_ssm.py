"""Prompt tokens whose prefill the prefix cache skipped
(`metrics.snapshot()["prefix_hit_tokens"]`, window's end minus start)
over the prompt tokens of the requests admitted in the window, in the
state-space serve cell.  A hit here needs more than resident KV blocks:
the prefill starts after the deepest shared block that has a state
snapshot, and rows between it and the end of the shared blocks are
prefilled again (`prefix_tokens_recomputed`) and not counted.  A share
above zero is the proof that sharing survives a state of 38 MB a slot.
A hit is prefill the device does not do, and a prefill blocks every
decode: the share moves the cell's tokens per second."""

META = {"layer": "serve scheduler", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-rag-closed"]}


def compute(run):
    if not run.get("prompt_tokens") or "prefix_hit_tokens" not in run:
        return None
    return 100.0 * run["prefix_hit_tokens"] / run["prompt_tokens"]
