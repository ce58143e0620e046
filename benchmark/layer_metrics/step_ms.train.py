"""Median over the fenced windows of window seconds / steps in it, on
the host's clock (a window spans seconds, so the clock's half
millisecond does not show)."""

META = {"layer": "compiled step", "unit": "ms",
        "moves": "train_tokens_per_s", "cells": ["train-seq2k"]}


def compute(run):
    return run.get("step_ms")
