"""Prompt tokens whose prefill the prefix cache skipped
(`metrics.snapshot()["prefix_hit_tokens"]`, window's end minus start)
over the prompt tokens of the requests admitted in the window."""

META = {"layer": "serve scheduler", "unit": "%", "moves": "ttft_p95_ms",
        "cells": ["serve-chat-closed"]}


def compute(run):
    if not run.get("prompt_tokens"):
        return None
    return 100.0 * run["prefix_hit_tokens"] / run["prompt_tokens"]
