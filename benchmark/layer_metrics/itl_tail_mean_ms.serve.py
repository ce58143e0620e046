"""Mean of the slowest 5% of the window's gaps between consecutive
tokens of one request (the gaps `itl_p95_ms` is the 95th percentile
of), on the benchmark's own clock.  It stands beside `itl_p95_ms`,
which rests on whichever single engine step holds the p95's rank and so
steps by 2% when the window's end moves (PERF.md, PR 24): a mean over
the ~48 slowest steps moves a fiftieth as far."""

import math

META = {"layer": "serve scheduler", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-chat-closed"]}


def compute(run):
    gaps = sorted(run.get("itl_ms") or [])
    if not gaps:
        return None
    tail = gaps[math.ceil(0.95 * len(gaps)):] or gaps[-1:]
    return sum(tail) / len(tail)
