"""Median device duration of one execution of the `prefill_chunk`
program of the mixture-of-experts serve cell (32 prompt tokens of one
request: every layer's experts streamed once for them), from the
trace's module line."""

import trace_reduce

META = {"layer": "serve programs", "unit": "ms", "moves": "ttft_p95_ms",
        "cells": ["serve-code-closed"]}


def compute(run):
    return trace_reduce.median_module_ms(run["trace"], "prefill_chunk")
