"""Output tokens stamped inside the window over its seconds, in the KDA
serve cell, on the benchmark's own clock: what `serve_tokens_per_s` is
in other serve cells, kept here as a reading without a bound.  Five
seeds spread 1.1% of it against half a bound of 1% (PERF.md section 6):
how many ~12 ms chunks share the window with ~4,350 ticks moves it.  A
tick is every running request's next gap, hence `itl_p95_ms`."""

META = {"layer": "serve scheduler", "unit": "tokens/s", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    return run.get("end_to_end", {}).get("serve_tokens_per_s")
