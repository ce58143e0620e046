"""Admissions of the window that entered from a state snapshot, over
those that entered from one or from zeros (`snapshot()
["state_snapshot_hits"]` / (`["state_snapshot_hits"]` +
`["state_snapshot_misses"]`), window's end minus start), in the KDA
serve cell: with 8 tenants and 16 snapshot entries every admission after
warm-up should enter from its tenant's snapshot at position 1,024.  A
miss prefills the tenant's 1,024 shared rows again.  A program without
the miss counter has nothing to read: `None`."""

META = {"layer": "serve scheduler", "unit": "%", "moves": "itl_p95_ms",
        "cells": ["serve-think-closed"]}


def compute(run):
    hits, misses = run.get("state_snapshot_hits"), \
        run.get("state_snapshot_misses")
    if hits is None or misses is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
