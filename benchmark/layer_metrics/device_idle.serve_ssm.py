"""1 - (union of device 0's op intervals) / (span of the traced
window) in the state-space serve cell, from the profiler trace: the
share of a step in which the host's work between two dispatches keeps
the chip waiting.  Ten of forty layers make it a larger share than at
the deployment's depth."""

import trace_reduce

META = {"layer": "device", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-rag-closed"]}


def compute(run):
    return trace_reduce.idle_share(run["trace"])
