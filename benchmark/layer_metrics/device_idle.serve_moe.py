"""1 - (union of device 0's op intervals) / (span of the traced
window) in the mixture-of-experts serve cell, from the profiler trace:
the share of a step in which the host's work between two dispatches
(~6 ms against an 8 ms tick or a 5 ms chunk) keeps the chip waiting."""

import trace_reduce

META = {"layer": "device", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-code-closed"]}


def compute(run):
    return trace_reduce.idle_share(run["trace"])
