"""CPU-only tests of the nine per-layer metrics that read the engine's
account of its own host time (`singa_tpu/serve/metrics.py::HostAccount`)
through `singa_tpu.obs.events.histogram_summary`: each file is found
under its name, its `META` is its `BENCHMARK.json` entry and lists the
four serve cells, `compute` on hand-made histograms gives the hand-made
number, and a program that observed nothing gives `None`.

    python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

from singa_tpu.obs import events  # noqa: E402  (run.py put the root on the path)

SPEC = bench_run.read_json("BENCHMARK.json")
CELLS = ["serve-chat-closed", "serve-code-closed", "serve-reason-closed",
         "serve-rag-closed"]
#: name -> (unit, better, the end-to-end metric it moves)
NAMES = {
    "queue_empty_share.serve": ("%", "lower", "serve_tokens_per_s"),
    "exposed_ms_per_finish.serve": ("ms", "lower", "serve_tokens_per_s"),
    "exposed_ms_per_admit.serve": ("ms", "lower", "serve_tokens_per_s"),
    "host_ms_per_tick.serve": ("ms", "lower", "serve_tokens_per_s"),
    "ticks_ahead_share.serve": ("%", "higher", "serve_tokens_per_s"),
    "admit_turn_ms.serve": ("ms", "lower", "itl_p95_ms"),
    "slow_turn_ms_per_min.serve": ("ms/min", "lower", "serve_tokens_per_s"),
    "gc_pause_ms_per_min.serve": ("ms/min", "lower", "serve_tokens_per_s"),
    "dispatch_ms_per_tick.serve": ("ms", "lower", "serve_tokens_per_s")}
HISTS = ("serve.exposed_ms.finish", "serve.exposed_ms.admit",
         "serve.exposed_ms.other", "serve.covered_ms", "serve.dispatch_ms",
         "serve.turn_ms",
         "serve.turn_ms.admitting", "serve.tick_ahead", "serve.turn_gc_ms",
         "serve.slow_turn_ms")


def _compute(name):
    return bench_run.load_module("layer_metrics", name).compute({})


@pytest.fixture
def observed():
    """Hand-made histograms in place of whatever the process holds;
    the process's own are put back after."""
    with events._hist_lock:
        kept = {n: events._hists.pop(n) for n in HISTS if n in events._hists}

    def observe(name, values):
        for v in values:
            events.histogram(name, v)

    yield observe
    with events._hist_lock:
        for n in HISTS:
            events._hists.pop(n, None)
        events._hists.update(kept)


def test_the_nine_are_the_last_entries_and_nothing_moved():
    tail = SPEC["per_layer"][-len(NAMES):]
    assert [m["name"] for m in tail] == list(NAMES)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in tail:
        unit, better, moves = NAMES[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_counter",
                     "layer": "serve scheduler", "moves": moves,
                     "workloads": CELLS}
        # every one of its cells reports the metric it moves
        assert all(bench_run.applies(e2e[moves], c) for c in CELLS)


@pytest.mark.parametrize("name", NAMES)
def test_the_file_is_found_and_its_meta_is_its_entry(name):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    mod = bench_run.load_module("layer_metrics", name)
    assert mod.META == {"layer": entry["layer"], "unit": entry["unit"],
                        "moves": entry["moves"], "cells": CELLS}
    assert "whole serving life" in mod.__doc__     # what it reads, and why
    assert "section 7" in mod.__doc__


@pytest.mark.parametrize("name", NAMES)
def test_nothing_observed_is_none(observed, name):
    assert _compute(name) is None


def test_queue_empty_share_is_the_exposed_sums_over_the_turns(observed):
    observed("serve.turn_ms", [10.0] * 99 + [110.0])           # 1,100 ms
    observed("serve.exposed_ms.finish", [1.5, 2.5])
    observed("serve.exposed_ms.admit", [0.5, 0.5, 1.0])
    assert _compute("queue_empty_share.serve") == \
        pytest.approx(100 * 6.0 / 1100.0)
    observed("serve.exposed_ms.other", [5.0])
    assert _compute("queue_empty_share.serve") == pytest.approx(1.0)


def test_turns_and_no_empty_queue_is_zero(observed):
    observed("serve.turn_ms", [9.0, 11.0])
    assert _compute("queue_empty_share.serve") == 0.0


@pytest.mark.parametrize("name,hist", [
    ("exposed_ms_per_finish.serve", "serve.exposed_ms.finish"),
    ("exposed_ms_per_admit.serve", "serve.exposed_ms.admit"),
    ("host_ms_per_tick.serve", "serve.covered_ms"),
    ("dispatch_ms_per_tick.serve", "serve.dispatch_ms"),
    ("admit_turn_ms.serve", "serve.turn_ms.admitting")])
def test_the_medians(observed, name, hist):
    observed(hist, [1.0, 1.4, 1.6, 2.0, 140.0])    # a stall moves no median
    assert _compute(name) == 1.6
    others = [n for n in NAMES if n != name and n != "queue_empty_share.serve"]
    assert all(_compute(n) is None for n in others)


def test_ticks_ahead_share_is_the_mean_of_the_flags(observed):
    observed("serve.tick_ahead", [1.0] * 3 + [0.0])
    assert _compute("ticks_ahead_share.serve") == 75.0


@pytest.mark.parametrize("name,hist", [
    ("slow_turn_ms_per_min.serve", "serve.slow_turn_ms"),
    ("gc_pause_ms_per_min.serve", "serve.turn_gc_ms")])
def test_per_minute_of_turns_and_zero_where_none(observed, name, hist):
    observed("serve.turn_ms", [10.0] * 3000)            # half a minute
    assert _compute(name) == 0.0                        # counted, none slow
    observed(hist, [70.0, 95.0])
    assert _compute(name) == pytest.approx(330.0)
