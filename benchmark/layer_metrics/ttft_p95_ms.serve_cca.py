"""The 95th percentile of submit-to-first-token over the requests whose
first token fell in the window, in the CCA serve cell, on the
benchmark's own clock: what `ttft_p95_ms` is in the other serve cells,
kept here as a reading without a bound.  Of ~200 admissions a window,
seven wait behind another's prefill and 22 prefill three chunks alone;
the rank falls on the third or fourth slowest of those 22, so the
number is three chunks on the device (22 ms) plus ~3 ms of the host,
read at the upper tail of 22 samples: sets of runs of one tree spread
by 0.6-1.8% of it against a bound of 1% (PERF.md section 7 h).  An
admission's time to its first token is also the extra gap every
running request sees in that step, hence `itl_p95_ms`."""

import yardstick

META = {"layer": "serve scheduler", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-reason-closed"]}


def compute(run):
    if not run.get("ttft_ms"):
        return None
    return yardstick.percentile(run["ttft_ms"], 95)
