"""Median device duration of one execution of the `decode_paged`
program of the CCA serve cell (one token for every one of 64 slots:
seven CCA layers with their side state, all 16 expert stacks of each
streamed once, the 1.07 GB tied head), from the trace's module line."""

import trace_reduce

META = {"layer": "serve programs", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-reason-closed"]}


def compute(run):
    return trace_reduce.median_module_ms(run["trace"], "decode_paged")
