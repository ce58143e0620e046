"""Model FLOP/s utilization: the benchmark's own FLOPs per token
(yardstick.train_flops_per_token: no recompute, causal keys only) times
the window's tokens per second, over the peak of the device's kind."""

import yardstick

META = {"layer": "compiled step", "unit": "%",
        "moves": "train_tokens_per_s", "cells": ["train-seq2k"]}


def compute(run):
    if not run.get("tokens_per_s") or not run.get("peak"):
        return None
    flops = yardstick.train_flops_per_token(run["shape"], run["seq"])
    return 100.0 * flops * run["tokens_per_s"] / run["peak"]["flops"]
