"""`memory_stats()["peak_bytes_in_use"]` of the fullest chip after the
window, in GB (1e9).  It counts live buffers; a program's scratch is
printed beside it on an earlier line where the runner has it."""

META = {"layer": "device", "unit": "GB", "moves": "train_tokens_per_s",
        "cells": ["train-seq2k"]}


def compute(run):
    return run["memory_peak_bytes"] / 1e9 or None
