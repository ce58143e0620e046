"""The 95th percentile of submit-to-first-token over the requests whose
first token fell in the window, in the KDA serve cell, on the
benchmark's own clock: what `ttft_p95_ms` is in other serve cells, kept
here as a reading without a bound.  ~107 first tokens fall in a window,
so the rank is the sixth slowest: an admission of one to three chunks
that waited behind another's, and five seeds spread 3.2% of it against
a bound of 1% (PERF.md section 6).  An admission's chunks are also the
extra gap every running request sees in that step, hence `itl_p95_ms`."""

META = {"layer": "serve scheduler", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    return run.get("end_to_end", {}).get("ttft_p95_ms")
