"""CPU-only tests of the benchmark harness (not of the program):

    python -m pytest benchmark/tests -q

They live under `benchmark/` because the benchmark's PR may add files
only there; the repo's tier-1 command does not collect them.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import yardstick  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(*args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_dry_run_ends_in_the_contracts_line(cell):
    r = _run("--workload", cell, "--seed", "3000000019", "--dry-run")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.splitlines()[-1])
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}      # never a time or a rate from a CPU


def test_no_tpu_is_a_nonzero_exit_and_no_result_line():
    r = _run("--workload", SPEC["workloads"][0]["name"], "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert not any(l.startswith("{") for l in r.stdout.splitlines())


def test_interval_union_and_gaps():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4), (6.0, 6.5)]
    assert trace_reduce.union_seconds(spans) == pytest.approx(3.5)
    assert trace_reduce.gaps(spans) == [(2.0, 3.0), (4.0, 6.0)]
    assert trace_reduce.union_seconds([]) == 0.0


def test_percentile_and_flops_on_hand_made_inputs():
    assert yardstick.percentile(range(1, 101), 95) == pytest.approx(95.05)
    assert yardstick.percentile([7.0], 95) == 7.0
    c = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10,
         "sliding_window": 0}
    # per layer 8*(16+8) + 3*8*16 = 576; head 80; keys mean(1..4) = 2.5
    assert yardstick.train_flops_per_token(c, 4) == \
        6 * (2 * 576 + 80) + 12 * 2 * 8 * 2.5
    with pytest.raises(SystemExit):
        yardstick.peaks("TPU v99")


def test_traffic_is_a_function_of_the_seed_and_honours_its_clips():
    with open(os.path.join(BENCH, "workloads", "serve-chat-closed.json")) as f:
        t = json.load(f)["traffic"]

    def take(seed, client, n=5):
        s = traffic.client_stream(t, 32000, seed, client)
        return [next(s) for _ in range(n)]

    a, b = take(3000000019, 5), take(3000000019, 5)
    assert all(np.array_equal(p, q) and o == r
               for (p, o), (q, r) in zip(a, b))
    assert not np.array_equal(a[0][0], take(11, 5)[0][0])
    # one round deals every client a different quantile of the same set
    for seed in (11, 3000000019):
        firsts = [take(seed, c, 1)[0] for c in range(t["clients"])]
        lens = sorted(p.size - t["prefix_len"] for p, _ in firsts)
        assert lens == sorted(traffic.quantile_lengths(
            t["clients"], t["prompt"]).tolist())
        assert t["prompt"]["lo"] <= lens[0] and lens[-1] <= t["prompt"]["hi"]
        outs = [o for _, o in firsts]
        assert t["output"]["lo"] <= min(outs) <= max(outs) <= t["output"]["hi"]
    # a client keeps its tenant: same system prompt in every request
    assert np.array_equal(a[0][0][:t["prefix_len"]], a[3][0][:t["prefix_len"]])


def test_every_file_is_found_and_every_moves_is_reported():
    import run as bench_run
    cells = {w["name"]: w for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for c in SPEC["configs"]:
        conf = bench_run.read_json(c["file"])
        assert conf["source"] == c["source"]
        assert set(conf["reduced"]) == set(c["reduced"])
    for name, w in cells.items():
        cell = bench_run.read_json("benchmark", "workloads", name + ".json")
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert os.path.exists(os.path.join(
            BENCH, "runners", cell["runner"] + ".py"))
    on_disk = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
               if f.endswith(".py")}
    assert on_disk == {m["name"] for m in SPEC["per_layer"]}
    for m in SPEC["per_layer"]:
        meta = bench_run.load_module("layer_metrics", m["name"]).META
        assert (meta["layer"], meta["unit"], meta["moves"]) == \
            (m["layer"], m["unit"], m["moves"])
        assert meta["cells"] == m["workloads"]
        for cell in m["workloads"]:
            assert bench_run.applies(e2e[m["moves"]], cell), (m["name"], cell)
