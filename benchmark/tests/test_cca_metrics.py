"""CPU-only tests of what the CCA cell adds to the harness: the shape
rules and counts of `cca_count.py` on hand-made inputs, the per-layer
metrics that the cell brings, its runner's refusal of a program without
the model, and its configuration file against the catalog's numbers.

    python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import cca_count  # noqa: E402
import run as bench_run  # noqa: E402

#: a hand-made configuration: 3 + 1 heads of 4, so C = 16 and S = 32
C = {"num_attention_heads": 3, "num_key_value_heads": 1, "head_dim": 4,
     "cca_time0": 2, "cca_time1": 2, "num_experts": 4, "hidden_size": 10,
     "moe_intermediate_size": 6, "num_hidden_layers": 2}
ZAYA = bench_run.read_json("benchmark", "configs", "zaya1-8b-serve.json")


def test_widths_by_hand():
    assert cca_count.widths(C) == (16, 2 * 16 + 0 * 4, 4, 4)
    # the numbers ISSUE 32 wrote down for the published widths
    assert cca_count.widths(ZAYA) == (1280, 2 * 1280 + 128, 10, 128)


@pytest.mark.parametrize("name,hit", [
    # the packed latent, the depthwise kernel, the grouped kernel with
    # and without its tap axis, the state per slot and per block
    ("%fusion.1 = f32[1,258,1280]{2,1,0} fusion(bf16[1,256,1024] %q, "
     "bf16[1,256,256] %k, bf16[1,2688] %state)", True),
    ("%fusion.2 = f32[64,2,10,128] fusion(f32[2,1280]{1,0} %conv0_w)", True),
    ("%fusion.3 = f32[1,256,10,128] fusion(f32[1,257,10,128] %c0, "
     "bf16[10,128,128] %tap)", True),
    ("%copy.4 = bf16[2,10,128,128]{3,2,1,0} copy(bf16[2,10,128,128] %w)",
     True),
    ("%dynamic-update-slice.5 = bf16[5121,2688] dynamic-update-slice("
     "bf16[5121,2688] %tails, bf16[1,2688] %row, s32[] %b)", True),
    ("%select.6 = bf16[64,2688] select(pred[64,1] %live, bf16[64,2688] %n, "
     "bf16[64,2688] %o)", True),
    # not CCA's: the projections' weights, heads read apart, attention,
    # the experts, the head, another rank of kernel
    ("%dot.7 = bf16[256,1024] dot(bf16[256,2048] %h, bf16[2048,1024] %wq)",
     False),
    ("%fusion.8 = bf16[64,1,8,128] fusion(f32[64,1,8,128] %q, f32[2560,32] "
     "%cos)", False),
    ("%fusion.9 = bf16[64,2560,2,128] fusion(bf16[5121,32,2,128] %arena)",
     False),
    ("%fusion.10 = f32[16,64,2048] fusion(bf16[16,2048,2048] %w_in)", False),
    ("%dot.11 = bf16[64,262272] dot(bf16[64,2048] %x, bf16[262272,2048] %t)",
     False),
    ("%copy.12 = bf16[3,2,10,128,128] copy(bf16[3,2,10,128,128] %w)", False),
    ("%add.13 = f32[] add(f32[] %a, f32[] %b)", False),
])
def test_matcher_at_the_published_widths(name, hit):
    assert cca_count.does_cca_mixing(name, ZAYA) is hit


OPS = {"%f = f32[1,258,16] fusion(bf16[1,256,12] %q)": (0.1, 10),
       "%g = bf16[9,32] dynamic-update-slice(bf16[9,32] %t)": (0.1, 10),
       "%e = f32[4,32,6] fusion(bf16[4,10,6] %p)": (0.4, 10),
       "%attn = bf16[32,8] fusion(bf16[32,8] %k)": (0.2, 10)}


def _metric(name):
    return bench_run.load_module("layer_metrics", name)


def test_the_cells_metrics_on_a_hand_made_run():
    assert cca_count.cca_op_seconds(OPS, C) == pytest.approx(0.2)
    run = {"trace": {"ops": OPS, "busy_s": 0.8, "window_s": 1.0,
                     "module_ms": {"jit_prefill_chunk": [8.0, 10.0, 12.0],
                                   "jit_decode_paged": [2.0, 2.0],
                                   "jit_other": [9.0]}},
           "moe_config": C, "peak": {"hbm_bytes_per_s": 36_000.0},
           "moe_assignments": 64 * 7, "moe_dispatches": 7,
           "prompt_tokens": 400, "prefix_hit_tokens": 128,
           "prefill_chunks": 4, "prefill_chunk_rows": 800,
           "ttft_ms": [float(i) for i in range(1, 22)],
           "memory_peak_bytes": 13_300_000_000}
    read = lambda n: _metric(n + ".serve_cca").compute(run)
    assert read("cca_mix_share") == pytest.approx(25.0)
    assert read("expert_share") == pytest.approx(50.0)
    # 5 executions x 2 layers x 1440 B over 0.4 s at 36,000 B/s
    assert read("expert_roofline") == pytest.approx(100.0)
    assert read("expert_rows_per_dispatch") == pytest.approx(16.0)
    assert read("decode_tick_ms") == 2.0
    # a median chunk of 10 ms over 200 rows a chunk
    assert read("prefill_us_per_row") == pytest.approx(50.0)
    assert read("prefix_hit_share") == pytest.approx(32.0)
    assert read("device_idle") == pytest.approx(20.0)
    assert read("hbm_peak_gb") == pytest.approx(13.3)
    # 1..21 ms: the 95th percentile lies on the twentieth
    assert read("ttft_p95_ms") == pytest.approx(20.0)


def test_a_program_without_the_counters_or_the_keys_reads_nothing():
    """What another cell's run, or a program without the counters,
    gives: `None`, never a raise."""
    dense = {"trace": {"ops": OPS, "busy_s": 0.8, "window_s": 1.0,
                       "module_ms": {}}, "peak": {"hbm_bytes_per_s": 1.0},
             "memory_peak_bytes": 0}
    for name in ("cca_mix_share", "expert_share", "expert_roofline",
                 "expert_rows_per_dispatch", "prefill_us_per_row",
                 "prefix_hit_share", "decode_tick_ms", "hbm_peak_gb",
                 "ttft_p95_ms"):
        assert _metric(name + ".serve_cca").compute(dense) is None
    moe_only = {**dense, "moe_config": {k: v for k, v in C.items()
                                        if not k.startswith("cca_")}}
    assert _metric("cca_mix_share.serve_cca").compute(moe_only) is None


def test_the_runner_refuses_a_program_without_the_model():
    runner = bench_run.load_module("runners", "serve_cca")

    class Old:
        class LlamaConfig:
            pass
    with pytest.raises(SystemExit, match="cannot build the configuration"):
        runner.zaya_config(ZAYA, Old)


def test_the_configuration_file_keeps_the_catalogs_numbers():
    """Every published number under its own key; only the depth is cut."""
    c = ZAYA
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"], c["vocab_size"]) == \
        (2048, 128, 8, 2, 262272)
    assert (c["num_experts"], c["num_experts_per_tok"],
            c["moe_intermediate_size"], c["router_hidden_size"]) == \
        (16, 1, 2048, 256)
    assert (c["cca_time0"], c["cca_time1"], c["partial_rotary_factor"],
            c["rms_norm_eps"], c["max_position_embeddings"]) == \
        (2, 2, 0.5, 1e-5, 131072)
    assert c["tie_word_embeddings"] is True and c["sliding_window"] is None
    assert c["num_hidden_layers"] == 7 and list(c["reduced"]) == \
        ["num_hidden_layers"]
    assert c["layer_types"] == ["hybrid"] * 40
    assert c["rope_theta"] == c["rope_parameters"]["hybrid"]["rope_theta"] \
        == 5000000
    assert c["engine"] == {"num_slots": 64, "max_len": 2560,
                           "block_size": 32, "param_dtype": "bfloat16"}


def test_the_traffic_is_what_the_issue_names():
    cell = bench_run.read_json("benchmark", "workloads",
                               "serve-reason-closed.json")
    t = cell["traffic"]
    assert (t["clients"], t["tenants"], t["prefix_len"],
            t["warmup_rounds"]) == (64, 4, 128, 1)
    assert t["prompt"] == {"dist": "lognormal", "median": 192, "sigma": 0.8,
                           "lo": 32, "hi": 768}
    assert t["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.7,
                           "lo": 128, "hi": 1536}
    assert t["prefix_len"] + t["prompt"]["hi"] + t["output"]["hi"] == 2432 \
        <= ZAYA["engine"]["max_len"]
    assert cell["loop"] == "closed" and cell["runner"] == "serve_cca"
