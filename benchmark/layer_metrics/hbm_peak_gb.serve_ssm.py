"""`memory_stats()["peak_bytes_in_use"]` of the chip after the window
of the state-space serve cell, in GB (1e9): the f32 masters, the
engine's bf16 cast (9 held experts a layer, an eighth of the tied
vocabulary), the recurrent state of 24 slots in f32 with six snapshots,
and one attention layer's KV arena.  It counts live buffers, not a
program's scratch; the configuration's size is judged on it."""

META = {"layer": "device", "unit": "GB", "moves": "serve_tokens_per_s",
        "cells": ["serve-rag-closed"]}


def compute(run):
    return run["memory_peak_bytes"] / 1e9 or None
