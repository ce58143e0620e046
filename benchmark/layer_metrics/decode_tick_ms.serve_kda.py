"""Median device duration of one execution of the `decode_paged`
program of the KDA serve cell (one token for every one of 64 slots:
seven KDA layers, each one call of the state kernel over every slot's
2 MiB of f32 state, one MLA layer through the latent kernel, two dense
layers, 16 held expert stacks and a shared expert in six layers, the
untied head over 19,648 rows), from the trace's module line."""

import trace_reduce

META = {"layer": "serve programs", "unit": "ms", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    return trace_reduce.median_module_ms(run["trace"], "decode_paged")
