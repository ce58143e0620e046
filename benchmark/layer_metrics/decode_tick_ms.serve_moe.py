"""Median device duration of one execution of the `decode_paged`
program of the mixture-of-experts serve cell (one token for every
slot: four attention layers, all 64 experts of each streamed once),
from the trace's module line."""

import trace_reduce

META = {"layer": "serve programs", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-code-closed"]}


def compute(run):
    return trace_reduce.median_module_ms(run["trace"], "decode_paged")
