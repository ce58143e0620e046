"""`memory_stats()["peak_bytes_in_use"]` of the chip after the window
of the KDA serve cell, in GB (1e9): the f32 masters, the engine's bf16
cast (16 held experts a layer, an eighth of the vocabulary twice), the
KDA state of 64 slots and 16 snapshots, and one layer's latent arena.
It counts live buffers, not a program's scratch; the configuration's
size is judged on it."""

META = {"layer": "device", "unit": "GB", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    return run["memory_peak_bytes"] / 1e9 or None
