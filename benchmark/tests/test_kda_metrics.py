"""CPU-only tests of what the KDA cell adds to the harness: the counts of
`kda_count.py` at the published widths against hand counts, the ops it
finds by name and by scope in a hand-made profile, the per-layer metrics
the cell brings on a hand-made trace and counters (and their silence
where the program lacks the counters or the scopes), the runner's
refusal of a program without the model, the configuration file against
the catalog's numbers, and the cell's dry run.

    python -m pytest benchmark/tests -q
"""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import kda_count  # noqa: E402
import run as bench_run  # noqa: E402

LING = bench_run.read_json("benchmark", "configs", "ling-3.0-flash-serve.json")
SPEC = bench_run.read_json("BENCHMARK.json")
# the model catalog's entry for the source model; MODEL_CATALOG may name the
# whole catalog (one JSON object a line) instead
CATALOG = os.environ.get("MODEL_CATALOG",
                         os.path.join(HERE, "catalog_ling.jsonl"))
CELL = "serve-think-closed"
NAMES = ("kda_share", "kda_decode_roofline", "kda_prefill_roofline",
         "decode_tick_ms", "prefill_us_per_row", "snapshot_hit_share",
         "mla_decode_roofline", "device_idle", "hbm_peak_gb", "ttft_p95_ms",
         "serve_tokens_per_s", "itl_tail_mean_ms", "admit_turn_ms")


def test_the_counts_by_hand_at_the_published_widths():
    # layers 0-7, the sixth (5) MLA: seven KDA layers of 32 heads of 128
    assert kda_count.kda_layers(LING) == 7
    assert kda_count.state_bytes_per_slot(LING) == 7 * 32 * 128 * 128 * 4
    # a row, a head: 8 x 64 x 128 (products and solve) + 6 x 128^2
    assert kda_count.prefill_flops(LING, 1) == 7 * 32 * (65536 + 98304)
    # a row: q, k, v, g, o (5 x 4,096 f32) and beta (32 f32); a call:
    # four states of 32 x 128 x 128 f32
    assert kda_count.prefill_bytes(LING, 1, 0) == 7 * (5 * 4096 + 32) * 4
    assert kda_count.prefill_bytes(LING, 0, 1) == 7 * 4 * 32 * 128 * 128 * 4
    assert kda_count.decode_bytes(LING, 256) == 256 * (1 + 3 / 128)


class _Event:
    def __init__(self, name, start_ns, ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, ns


def _profile(modules, ops):
    line = lambda name, events: types.SimpleNamespace(name=name,
                                                      events=events)
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Modules", modules), line("XLA Ops", ops)]),
        types.SimpleNamespace(name="/host:CPU", lines=[line("XLA Ops", ops)])])


PREFILL_HLO = """
  %fusion.1 = f32[1,32,4,64,64]{4,3,2,1,0} fusion(f32[1] %p), kind=kLoop, calls=%c.1, metadata={op_type="dot_general" op_name="jit(prefill_chunk)/jit(main)/kda/kda.prefill/dot_general"}
  %fusion.2 = bf16[1,256,12288]{2,1,0} fusion(bf16[1] %p), kind=kOutput, calls=%c.2, metadata={op_name="jit(prefill_chunk)/jit(main)/kda/kda.proj/dot_general"}
  ROOT %fusion.4 = f32[1,32,4,64,64]{4,3,2,1,0} fusion(f32[1] %p), metadata={op_name="jit(prefill_chunk)/jit(main)/attn.mla/attn.mla.prefill/dot_general"}
"""
DECODE_HLO = """
  %fusion.1 = bf16[64,2560]{1,0} fusion(bf16[1] %p), metadata={op_name="jit(decode_paged)/jit(main)/moe.experts/dot_general"}
  %kda_decode.3 = (f32[64,32,128]) custom-call(f32[1] %p), metadata={op_name="jit(decode_paged)/jit(main)/kda/kda.decode/pallas_call"}
"""


def test_ops_are_found_by_scope_and_by_name_not_by_shape():
    scopes = {"jit_prefill_chunk": kda_count.op_scopes(PREFILL_HLO),
              "jit_decode_paged": kda_count.op_scopes(DECODE_HLO)}
    assert scopes["jit_prefill_chunk"]["fusion.4"].endswith(
        "attn.mla.prefill/dot_general")
    modules = [_Event("jit_prefill_chunk(7)", 0, 10_000_000),
               _Event("jit_decode_paged(8)", 20_000_000, 10_000_000)]
    ops = [
        _Event("%fusion.1 = f32[1,32,4,64,64] fusion(...)", 1_000_000,
               2_000_000),
        _Event("%fusion.2 = bf16[1,256,12288] fusion(...)", 3_000_000,
               1_000_000),
        # the same shape under another scope is not the layer's
        _Event("%fusion.4 = f32[1,32,4,64,64] fusion(...)", 5_000_000,
               4_000_000),
        # fusion.1 of the decode program is an expert op there
        _Event("%fusion.1 = bf16[64,2560] fusion(...)", 21_000_000,
               5_000_000),
        _Event("%kda_decode.3 = (f32[64,32,128]) custom-call(...)",
               26_000_000, 3_000_000)]
    got = kda_count.scope_seconds(_profile(modules, ops), scopes)
    assert got == pytest.approx({"kda": 6e-3, "kda.prefill": 2e-3,
                                 "kda.decode": 3e-3})
    assert kda_count.scope_seconds(_profile(modules, ops[2:4]), scopes) == {}
    reduced = {e.name: (e.duration_ns * 1e-9, 1) for e in ops}
    assert kda_count.decode_seconds(reduced) == pytest.approx(3e-3)


def _run(**over):
    trace = {"busy_s": 10.0, "window_s": 11.0,
             "ops": {"%kda_decode.1 = custom-call": (2.0, 700),
                     "%mla_decode_attention.2 = custom-call": (0.5, 100)},
             "module_ms": {"jit_decode_paged": [10.0] * 100,
                           "jit_prefill_chunk": [20.0] * 10}}
    run = {"trace": trace, "kda_config": LING, "decode_ticks": 1000,
           "kda_scoped_s": {"kda": 6.0, "kda.prefill": 0.5,
                            "kda.decode": 2.0},
           "slot_ticks": 1000 * 62.5, "chunk_rows": 256,
           "itl_ms": [8.0] * 95 + [20.0] * 5,
           "mla_latent_bytes": 1000 * 3e8, "prefill_chunks": 20,
           "prefill_chunk_rows": 20 * 180, "state_snapshot_hits": 90,
           "state_snapshot_misses": 10,
           "peak": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
           "memory_peak_bytes": 10e9, "workload": CELL,
           "end_to_end": {"ttft_p95_ms": 143.9, "serve_tokens_per_s": 6961.8,
                          "itl_p95_ms": 8.0, "setup_s": 118.2}}
    run.update(over)
    return run


def _metric(name):
    return bench_run.load_module("layer_metrics", f"{name}.serve_kda")


def test_metrics_on_a_hand_made_run():
    run = _run()
    assert _metric("kda_share").compute(run) == pytest.approx(60.0)
    # 62.5 running slots a tick, each one's 7 x 2 MiB of f32 state read
    # and written, over the 100 traced ticks
    state = 2 * 62.5 * 7 * 32 * 128 * 128 * 4
    assert _metric("kda_decode_roofline").compute(run) == pytest.approx(
        100 * 100 * state * (1 + 3 / 128) / (819e9 * 2.0))
    rows, chunks = 256 * 10, 10
    least = max(kda_count.prefill_flops(LING, rows) / 197e12,
                kda_count.prefill_bytes(LING, rows, chunks) / 819e9)
    assert _metric("kda_prefill_roofline").compute(run) == pytest.approx(
        100 * least / 0.5)
    assert _metric("decode_tick_ms").compute(run) == pytest.approx(10.0)
    assert _metric("prefill_us_per_row").compute(run) == pytest.approx(
        1e3 * 20.0 / 180)
    assert _metric("snapshot_hit_share").compute(run) == pytest.approx(90.0)
    assert _metric("mla_decode_roofline").compute(run) == pytest.approx(
        100 * 100 * 3e8 / (819e9 * 0.5))
    assert _metric("device_idle").compute(run) == pytest.approx(
        100 * (1 - 10 / 11))
    assert _metric("hbm_peak_gb").compute(run) == pytest.approx(10.0)
    assert _metric("ttft_p95_ms").compute(run) == pytest.approx(143.9)
    assert _metric("serve_tokens_per_s").compute(run) == pytest.approx(6961.8)
    assert _metric("itl_tail_mean_ms").compute(run) == pytest.approx(20.0)


def test_the_admitting_turns_median():
    """`admit_turn_ms.serve_kda` is the median of the process's
    `serve.turn_ms.admitting` histogram, and nothing where it is empty."""
    from singa_tpu.obs import events
    name = "serve.turn_ms.admitting"
    with events._hist_lock:
        kept = events._hists.pop(name, None)
    try:
        assert _metric("admit_turn_ms").compute(_run()) is None
        for v in (12.0, 30.0, 31.0, 33.0, 90.0):
            events.histogram(name, v)
        assert _metric("admit_turn_ms").compute(_run()) == pytest.approx(
            events.histogram_summary(name)["p50"])
        assert 30.0 <= _metric("admit_turn_ms").compute(_run()) <= 33.0
    finally:
        with events._hist_lock:
            events._hists.pop(name, None)
            if kept is not None:
                events._hists[name] = kept


def test_a_program_without_the_counters_reads_nothing():
    bare = _run(slot_ticks=0, mla_latent_bytes=0, kda_scoped_s={},
                state_snapshot_misses=None, itl_ms=[])
    for name in ("kda_share", "kda_decode_roofline", "kda_prefill_roofline",
                 "snapshot_hit_share", "mla_decode_roofline",
                 "itl_tail_mean_ms"):
        assert _metric(name).compute(bare) is None, name


def test_metas_match_the_entries_and_are_appended_at_the_end():
    names = [m["name"] for m in SPEC["per_layer"]]
    assert names[-len(NAMES):] == [f"{n}.serve_kda" for n in NAMES]
    for n in NAMES:
        entry = SPEC["per_layer"][names.index(f"{n}.serve_kda")]
        meta = _metric(n).META
        assert entry["workloads"] == meta["cells"] == [CELL]
        assert (entry["layer"], entry["unit"], entry["moves"]) == (
            meta["layer"], meta["unit"], meta["moves"])
    assert SPEC["workloads"][-1]["name"] == CELL
    assert SPEC["configs"][-1]["name"] == "ling-3.0-flash-serve"


def test_the_runner_refuses_a_program_without_the_model():
    runner = bench_run.load_module("runners", "serve_kda")
    with pytest.raises(SystemExit, match="no models.Ling"):
        runner.ling_config(LING, types.SimpleNamespace(), 12288)


def test_the_configuration_keeps_the_catalogs_numbers():
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if json.loads(line)["name"] == "Ling-3.0-flash-VL")
    assert LING["source"] == row["source_url"]
    entry = next(c for c in SPEC["configs"]
                 if c["name"] == "ling-3.0-flash-serve")
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            continue
        assert LING[key] == value, key
    assert sorted(LING["reduced"]) == sorted(entry["reduced"])
    dep = LING["deployment"]
    assert dep["num_experts"] == row["config"]["num_experts"] == 512
    assert dep["vocab_size"] == row["config"]["vocab_size"]
    assert dep["num_hidden_layers"] == row["config"]["num_hidden_layers"]
    assert LING["num_experts"] == len(dep["experts_held"]) == 16
    assert LING["vocab_size"] * 8 == dep["vocab_size"]
    assert LING["num_hidden_layers"] == 8


def test_the_dry_run_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--dry-run"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"]
    assert line["metrics"] == {}        # a CPU rehearsal prints none
