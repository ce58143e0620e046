"""CPU-only tests of the per-layer metric that the paged decode kernel
brings: `paged_attn_share.serve` is found under its name, lists the
cells that `BENCHMARK.json` gives it, and reads the custom calls' share
of the busy time — 0 where a serve program holds none, as on a program
whose decode still walks a gathered view.

    python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

NAME = "paged_attn_share.serve"
SPEC = bench_run.read_json("BENCHMARK.json")

#: a tick of the view path: gather, token write, attention's read
VIEW_OPS = {
    "%fusion.1 = bf16[2048,32,8,128] fusion(bf16[2049,32,8,128] %arena)":
        (0.4, 10),
    "%while.2 = (s32[], bf16[32,2048,8,128]) while(%tuple)": (0.1, 10),
    "%fusion.3 = bf16[32,1,32,128] fusion(bf16[32,2048,8,128] %view)":
        (0.2, 10),
    "%fusion.4 = bf16[32,14336] fusion(bf16[4096,14336] %w)": (0.3, 10)}
#: the same tick with one kernel a layer in the view passes' place
PAGED_OPS = {
    "%paged_attention.1 = bf16[32,32,128] custom-call(s32[32,64] %tables, "
    "s32[32] %lengths), custom_call_target=\"tpu_custom_call\"": (0.1, 10),
    "%scatter.2 = bf16[2049,32,8,128] scatter(bf16[2049,32,8,128] %arena)":
        (0.02, 10),
    "%fusion.4 = bf16[32,14336] fusion(bf16[4096,14336] %w)": (0.3, 10)}


def _metric():
    return bench_run.load_module("layer_metrics", NAME)


def test_the_metric_is_found_and_lists_its_cells():
    entry = next(m for m in SPEC["per_layer"] if m["name"] == NAME)
    assert entry == SPEC["per_layer"][-1]       # appended, nothing moved
    meta = _metric().META
    assert meta["cells"] == entry["workloads"] == [
        "serve-chat-closed", "serve-code-closed", "serve-reason-closed"]
    assert (meta["layer"], meta["unit"], meta["moves"]) == \
        (entry["layer"], entry["unit"], entry["moves"]) == \
        ("kernels", "%", "serve_tokens_per_s")
    assert (entry["better"], entry["source"]) == ("lower", "device_trace")
    # every one of its cells reports the metric it moves
    e2e = next(m for m in SPEC["end_to_end"] if m["name"] == entry["moves"])
    assert all(bench_run.applies(e2e, c) for c in entry["workloads"])


@pytest.mark.parametrize("ops,busy,want", [
    (VIEW_OPS, 1.0, 0.0),            # no custom call: the view path
    (PAGED_OPS, 0.42, 100 * 0.1 / 0.42),
    (PAGED_OPS, 0.5, 20.0),          # over busy time, not over the ops' sum
])
def test_share_of_busy_time_in_custom_calls(ops, busy, want):
    run = {"trace": {"ops": ops, "busy_s": busy, "window_s": 1.0,
                     "module_ms": {}}}
    assert _metric().compute(run) == pytest.approx(want)


def test_a_run_without_a_trace_reads_nothing():
    assert _metric().compute({"trace": {}}) is None
