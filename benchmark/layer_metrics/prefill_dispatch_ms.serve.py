"""Median device duration of one execution of the `prefill_chunk`
program (one block of one prompt), from the trace's module line."""

import trace_reduce

META = {"layer": "serve programs", "unit": "ms", "moves": "ttft_p95_ms",
        "cells": ["serve-chat-closed"]}


def compute(run):
    return trace_reduce.median_module_ms(run["trace"], "prefill_chunk")
