"""`memory_stats()["peak_bytes_in_use"]` of the chip after the window
of the mixture-of-experts serve cell, in GB (1e9): the f32 masters, the
engine's bf16 cast of all 64 experts of every layer and of the whole
vocabulary, and the KV arena.  It counts live buffers, not a program's
scratch; the configuration's size is judged on it."""

META = {"layer": "device", "unit": "GB", "moves": "serve_tokens_per_s",
        "cells": ["serve-code-closed"]}


def compute(run):
    return run["memory_peak_bytes"] / 1e9 or None
