"""1 - (union of device 0's op intervals) / (span of the traced
window) in the CCA serve cell, from the profiler trace: the share of a
step in which the host's work between two dispatches keeps the chip
waiting; 64 slots make a tick's delivery twice as long as in the
32-slot cells."""

import trace_reduce

META = {"layer": "device", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-reason-closed"]}


def compute(run):
    return trace_reduce.idle_share(run["trace"])
