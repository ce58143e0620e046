"""Prompt tokens whose prefill the prefix cache skipped
(`metrics.snapshot()["prefix_hit_tokens"]`, window's end minus start)
over the prompt tokens of the requests admitted in the window, in the
CCA serve cell: every hit resumes the CCA side state from the tail
kept with the last shared block, so a share above zero is the proof
that sharing survives the state.  A hit is prefill the device does not
do, and a prefill blocks every decode: the share moves the cell's
tokens per second (the cell reports no `ttft_p95_ms`: PERF.md
section 7 h)."""

META = {"layer": "serve scheduler", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-reason-closed"]}


def compute(run):
    if not run.get("prompt_tokens"):
        return None
    return 100.0 * run["prefix_hit_tokens"] / run["prompt_tokens"]
