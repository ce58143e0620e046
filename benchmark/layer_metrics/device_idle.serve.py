"""1 - (union of device 0's op intervals) / (span of the traced
window), from the profiler trace."""

import trace_reduce

META = {"layer": "device", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-chat-closed"]}


def compute(run):
    return trace_reduce.idle_share(run["trace"])
