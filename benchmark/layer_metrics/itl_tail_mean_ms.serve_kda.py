"""Mean of the slowest 5% of the window's gaps between consecutive
tokens of one request in the KDA serve cell (the gaps `itl_p95_ms` is
the 95th percentile of), on the benchmark's own clock: a mean over the
slowest steps, the turns in which admissions prefill beside the tick,
moves less than the p95's single rank does."""

import math

META = {"layer": "serve scheduler", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    gaps = sorted(run.get("itl_ms") or [])
    if not gaps:
        return None
    tail = gaps[math.ceil(0.95 * len(gaps)):] or gaps[-1:]
    return sum(tail) / len(tail)
