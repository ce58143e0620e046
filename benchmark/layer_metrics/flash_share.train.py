"""Time in the Pallas flash-attention custom calls (forward, dQ, dK/dV)
over the device's busy time, from the trace's op line."""

import trace_reduce

META = {"layer": "kernels", "unit": "%", "moves": "train_tokens_per_s",
        "cells": ["train-seq2k"]}


def compute(run):
    return trace_reduce.op_share(run["trace"], trace_reduce.FLASH_OPS)
