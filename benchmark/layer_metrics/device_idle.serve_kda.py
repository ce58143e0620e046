"""1 - (union of device 0's op intervals) / (span of the traced
window) in the KDA serve cell, from the profiler trace: the share of a
step in which the host's work between two dispatches keeps the chip
waiting.  Eight of 42 layers make it a larger share than at the
deployment's depth."""

import trace_reduce

META = {"layer": "device", "unit": "%", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    return trace_reduce.idle_share(run["trace"])
