"""Mean share of the engine's slots that hold a running request, from
`eng.pool.active_count` sampled by the runner after each `step()`."""

META = {"layer": "serve scheduler", "unit": "%",
        "moves": "serve_tokens_per_s", "cells": ["serve-chat-closed"]}


def compute(run):
    if not run.get("active"):
        return None
    return 100.0 * sum(run["active"]) / len(run["active"]) / run["num_slots"]
