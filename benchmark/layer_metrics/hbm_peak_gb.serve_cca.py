"""`memory_stats()["peak_bytes_in_use"]` of the chip after the window
of the CCA serve cell, in GB (1e9): the f32 masters, the engine's bf16
cast of all 16 experts of every layer and of the whole tied vocabulary,
the KV arena and the CCA state per block and per slot.  It counts live
buffers, not a program's scratch; the configuration's size is judged
on it."""

META = {"layer": "device", "unit": "GB", "moves": "serve_tokens_per_s",
        "cells": ["serve-reason-closed"]}


def compute(run):
    return run["memory_peak_bytes"] / 1e9 or None
