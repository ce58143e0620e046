"""Median device duration of one execution of the `decode_paged`
program (one token for every slot), from the trace's module line."""

import trace_reduce

META = {"layer": "serve programs", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-chat-closed"]}


def compute(run):
    return trace_reduce.median_module_ms(run["trace"], "decode_paged")
