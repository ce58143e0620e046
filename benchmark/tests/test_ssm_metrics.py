"""CPU-only tests of what the state-space cell adds to the harness: the
shape rules and counts of `ssm_count.py` on hand-made inputs and at the
published widths, the per-layer metrics that the cell brings on a
hand-made trace and counters, its runner's refusals, and its
configuration file against the catalog's numbers.

    python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import moe_count  # noqa: E402
import run as bench_run  # noqa: E402
import ssm_count  # noqa: E402

#: a hand-made configuration: 2 heads of 3 over a state of 5, so d_inner
#: = 6, conv_dim = 16 and in_proj has 24 columns; two mamba layers of 3
C = {"mamba_n_heads": 2, "mamba_d_head": 3, "mamba_d_state": 5,
     "mamba_n_groups": 1, "mamba_d_conv": 4, "hidden_size": 10,
     "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba",
                                             "mamba"],
     "num_local_experts": 4, "intermediate_size": 7,
     "engine": {"num_slots": 8}}
GRANITE = bench_run.read_json("benchmark", "configs",
                              "granite-4.0-h-small-serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_widths_and_bytes_by_hand():
    assert ssm_count.widths(C) == (6, 16, 24, 2, 3, 5)
    assert ssm_count.mamba_layers(C) == 2
    # a layer: S 2 x 3 x 5 f32 = 120 B, window 3 x 16 bf16 = 96 B
    assert ssm_count.state_bytes_per_slot(C) == 2 * (120 + 96)
    assert ssm_count.tick_state_bytes(C, 8) == 2 * 8 * 432
    # rows 4: C.B 2 x 16 x 5, quadratic 2 x 16 x 6, three states' worth
    # of 2 x 4 x 30
    assert ssm_count.scan_flops(C, 4) == 2 * (160 + 192 + 3 * 240)


def test_the_issues_numbers_at_the_published_widths():
    assert ssm_count.widths(GRANITE) == (8192, 8448, 16768, 128, 64, 128)
    assert ssm_count.mamba_layers(GRANITE) == 9
    # 37.75 MB of S and 0.46 MB of window a slot; 1.81 GB a tick's
    # updates at 24 slots
    per_slot = ssm_count.state_bytes_per_slot(GRANITE)
    assert per_slot == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert round(per_slot / 1e6, 2) == 38.2
    assert round(ssm_count.tick_state_bytes(GRANITE, 24) / 1e9, 2) == 1.83
    keys = ssm_count.expert_keys(GRANITE)
    assert moe_count.expert_bytes_per_layer(keys) * 10 == 1_698_693_120


@pytest.mark.parametrize("name,ssm,state", [
    # in_proj's result and weight, the rows around the convolution, the
    # scan's input by head, its decay matrix, the gated norm, out_proj
    ("%fusion.1 = bf16[24,1,16768] fusion(bf16[24,1,4096] %h, "
     "bf16[4096,16768] %w)", True, False),
    ("%fusion.2 = bf16[1,259,8448] fusion(bf16[1,3,8448] %win)", True, False),
    ("%fusion.3 = bf16[1,256,128,64] fusion(f32[1,256,128] %dt)", True,
     False),
    ("%fusion.4 = f32[1,256,256,128] fusion(f32[1,256,128] %cum)", True,
     False),
    ("%fusion.5 = bf16[24,1,8192] fusion(f32[24,1,8192] %y)", True, False),
    ("%dot.6 = bf16[256,4096] dot(bf16[256,8192] %g, bf16[8192,4096] %w)",
     True, False),
    # the per-slot state: a tick's update, a chunk's one-slot write
    ("%fusion.7 = (f32[24,128,64,128], f32[24,128,64]) fusion("
     "f32[24,128,64,128] %s, f32[24,128] %dt)", True, True),
    ("%dynamic-update-slice.8 = f32[24,128,64,128] dynamic-update-slice("
     "f32[24,128,64,128] %s, f32[1,128,64,128] %new)", True, True),
    # a snapshot entry and a chunk's states are the layer's, not the slots'
    ("%fusion.9 = f32[6,128,64,128] fusion(f32[1,2,128,64,128] %st)", True,
     False),
    # not the state-space layer's: attention's heads, the experts, the
    # shared MLP, the head, the arena
    ("%fusion.10 = bf16[24,1,32,128] fusion(bf16[24,1,4096] %q)", False,
     False),
    ("%paged_attention.11 = bf16[24,8,128] custom-call(s32[24,96] %t, "
     "bf16[2305,256,128] %k)", False, False),
    ("%fusion.12 = f32[9,24,768] fusion(bf16[9,4096,768] %w_in)", False,
     False),
    ("%dot.13 = bf16[24,1536] dot(bf16[24,4096] %x, bf16[4096,1536] %g)",
     False, False),
    ("%fusion.14 = bf16[24,12544] fusion(bf16[12544,4096] %table)", False,
     False),
    ("%add.15 = f32[] add(f32[] %a, f32[] %b)", False, False),
])
def test_matchers_at_the_published_widths(name, ssm, state):
    ops = {name: (1.0, 1)}
    assert bool(ssm_count.ssm_op_seconds(ops, GRANITE)) is ssm
    assert bool(ssm_count.state_update_seconds(ops, GRANITE, 24)) is state


OPS = {"%in = bf16[8,1,24] fusion(bf16[8,1,10] %h)": (0.1, 10),
       "%step = (f32[8,2,3,5], f32[8,2,3]) fusion(f32[8,2,3,5] %s)": (0.2, 10),
       "%e = f32[4,8,7] fusion(bf16[4,10,7] %p)": (0.4, 10),
       "%attn = bf16[8,4,8] fusion(bf16[8,4,8] %k)": (0.1, 10)}


def _metric(name):
    return bench_run.load_module("layer_metrics", name)


def test_the_cells_metrics_on_a_hand_made_run():
    assert ssm_count.ssm_op_seconds(OPS, C) == pytest.approx(0.3)
    assert ssm_count.state_update_seconds(OPS, C, 8) == pytest.approx(0.2)
    run = {"trace": {"ops": OPS, "busy_s": 0.8, "window_s": 1.0,
                     "module_ms": {"jit_prefill_chunk": [8.0, 10.0, 12.0],
                                   "jit_decode_paged": [2.0, 2.0],
                                   "jit_other": [9.0]}},
           "ssm_config": C, "peak": {"hbm_bytes_per_s": 69_120.0},
           # 100 ticks of the window moved 8 slots' state each way
           "decode_ticks": 100, "ssm_state_bytes": 100 * 2 * 8 * 432,
           "prompt_tokens": 400, "prefix_hit_tokens": 128,
           "prefill_chunks": 4, "prefill_chunk_rows": 800,
           "memory_peak_bytes": 13_800_000_000}
    read = lambda n: _metric(n + ".serve_ssm").compute(run)
    assert read("ssm_share") == pytest.approx(37.5)
    # 2 traced ticks x 6,912 B over 0.2 s at 69,120 B/s
    assert read("ssm_state_roofline") == pytest.approx(100.0)
    assert read("expert_share") == pytest.approx(50.0)
    # 5 executions x 3 layers x (3 x 4 x 10 x 7 x 2 B) over 0.4 s
    assert read("expert_roofline") == pytest.approx(
        100.0 * 5 * 3 * 1680 / (69_120.0 * 0.4))
    assert read("decode_tick_ms") == 2.0
    assert read("prefill_us_per_row") == pytest.approx(50.0)
    assert read("prefix_hit_share") == pytest.approx(32.0)
    assert read("device_idle") == pytest.approx(20.0)
    assert read("hbm_peak_gb") == pytest.approx(13.8)


def test_a_program_without_the_counters_or_the_keys_reads_nothing():
    """What another cell's run, or a program without the counters,
    gives: `None`, never a raise."""
    dense = {"trace": {"ops": OPS, "busy_s": 0.8, "window_s": 1.0,
                       "module_ms": {}}, "peak": {"hbm_bytes_per_s": 1.0},
             "memory_peak_bytes": 0}
    for name in ("ssm_share", "ssm_state_roofline", "expert_share",
                 "expert_roofline", "prefill_us_per_row", "prefix_hit_share",
                 "decode_tick_ms", "hbm_peak_gb"):
        assert _metric(name + ".serve_ssm").compute(dense) is None
    # the keys without the counter: the roofline has nothing to divide
    keyed = {**dense, "ssm_config": C}
    assert _metric("ssm_state_roofline.serve_ssm").compute(keyed) is None


def test_the_runner_refuses_a_program_without_the_model():
    runner = bench_run.load_module("runners", "serve_ssm")

    class Old:
        class LlamaConfig:
            pass
    with pytest.raises(SystemExit, match="cannot build the configuration"):
        runner.granite_config(GRANITE, Old)


@pytest.mark.parametrize("change,match", [
    ({"position_embedding_type": "rope"}, "without positional embedding"),
    ({"mamba_n_groups": 8}, "one group"),
    ({"mamba_expand": 4}, "mamba_expand x hidden_size"),
    ({"num_local_experts": 8}, "experts_held")])
def test_the_runner_refuses_what_the_program_does_not_build(change, match):
    runner = bench_run.load_module("runners", "serve_ssm")

    class Models:
        GraniteHybridConfig = dict
    with pytest.raises(SystemExit, match=match):
        runner.granite_config({**GRANITE, **change}, Models)


def test_the_runner_builds_the_published_widths():
    runner = bench_run.load_module("runners", "serve_ssm")

    class Models:
        GraniteHybridConfig = dict
    c = runner.granite_config(GRANITE, Models)
    assert c["layer_types"] == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (c["dim"], c["num_heads"], c["num_kv_heads"], c["head_size"]) == \
        (4096, 32, 8, 128)
    assert (c["num_experts"], c["experts_held"], c["moe_top_k"]) == \
        (72, tuple(range(9)), 10)
    assert c["vocab_size"] == 12544 and c["attention_multiplier"] == 1 / 128


def test_the_configuration_file_keeps_the_catalogs_numbers():
    """Every number of the catalog's entry under its own key, but the
    three keys `reduced` names; nested groups whole."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    assert GRANITE["source"] == row["source_url"]
    assert set(GRANITE["reduced"]) == {"num_hidden_layers",
                                       "num_local_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key not in GRANITE["reduced"]:
            assert GRANITE[key] == value, key
    assert (GRANITE["num_hidden_layers"], GRANITE["num_local_experts"],
            GRANITE["vocab_size"]) == (10, 9, 12544)
    dep = GRANITE["deployment"]
    assert (dep["chips_per_layer"], dep["num_local_experts"],
            dep["vocab_size"]) == (8, 72, 100352)
    assert dep["experts_held"] == list(range(9))
    assert GRANITE["vocab_size"] * 8 == dep["vocab_size"]


def test_the_cells_traffic_is_the_issues():
    cell = bench_run.read_json("benchmark", "workloads",
                               "serve-rag-closed.json")
    t, e = cell["traffic"], GRANITE["engine"]
    assert (t["clients"], t["tenants"], t["prefix_len"]) == (24, 4, 128)
    assert t["clients"] == e["num_slots"]
    assert t["prompt"] == {"dist": "lognormal", "median": 768, "sigma": 0.8,
                           "lo": 128, "hi": 2304}
    assert t["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                           "lo": 32, "hi": 512}
    # the longest context fits the engine's view
    assert t["prefix_len"] + t["prompt"]["hi"] + t["output"]["hi"] == 2944
    assert 2944 <= e["max_len"] == 3072
