"""Median device duration of one execution of the `decode_paged`
program of the state-space serve cell (one token for every one of 24
slots: nine Mamba-2 layers, each reading and writing 4 MB of f32 state
a slot, one attention layer over its KV blocks, 9 held expert stacks
and a shared MLP a layer, the tied head over 12,544 rows), from the
trace's module line."""

import trace_reduce

META = {"layer": "serve programs", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-rag-closed"]}


def compute(run):
    return trace_reduce.median_module_ms(run["trace"], "decode_paged")
