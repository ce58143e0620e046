"""CPU-only tests of what the mixture-of-experts cell adds to the
harness: the byte count and the op matcher of `moe_count.py` on
hand-made inputs, and the three per-layer metrics that read them.

    python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import moe_count  # noqa: E402
import run as bench_run  # noqa: E402

#: a hand-made configuration: 4 experts of width 6 on a stream of 10
C = {"num_experts": 4, "hidden_size": 10, "moe_intermediate_size": 6,
     "num_hidden_layers": 2}
MELLUM = bench_run.read_json("benchmark", "configs", "mellum2-12b-serve.json")


def test_expert_bytes_by_hand():
    assert moe_count.expert_bytes_per_layer(C) == 3 * 4 * 10 * 6 * 2
    assert moe_count.expert_bytes_per_layer(C, bytes_per_param=1) == 720
    # the number ISSUE 28 wrote down for the published widths
    assert moe_count.expert_bytes_per_layer(MELLUM) == 792_723_456


@pytest.mark.parametrize("name,hit", [
    # the matmuls: a stack as an operand, layouts and all
    ("%fusion.394 = f32[4,32,6]{2,1,0} fusion(bf16[4,10,6]{2,1,0:T(8,128)(2,1)}"
     " %param.3, bf16[32,10]{1,0} %x), kind=kOutput", True),
    ("%fusion.7 = bf16[32,10] fusion(bf16[4,32,6] %h, bf16[4,6,10] %w_out)",
     True),
    # a copy, an async prefetch of a slice of the expert axis, one expert
    ("%copy.12 = bf16[4,6,10]{1,2,0} copy(bf16[4,6,10]{2,1,0} %p)", True),
    ("%slice-start.3 = ((bf16[2,10,6]), bf16[2,10,6], u32[]) slice-start(%w)",
     True),
    ("%dot.1 = f32[32,6] dot(bf16[32,10] %x, bf16[10,6] %w_e)", True),
    # not the experts: activations, the router, attention, more experts
    # than the configuration has, another rank
    ("%fusion.9 = bf16[4,32,6] fusion(f32[4,32,6] %g, f32[4,32] %w)", False),
    ("%dot.2 = f32[32,4] dot(f32[32,10] %x, f32[10,4] %router)", False),
    ("%fusion.11 = bf16[32,2048,4,128] fusion(bf16[2049,32,4,128] %arena)",
     False),
    ("%copy.13 = bf16[5,10,6] copy(bf16[5,10,6] %p)", False),
    ("%copy.14 = bf16[2,4,10,6] copy(bf16[2,4,10,6] %p)", False),
    ("%add.3 = f32[] add(f32[] %a, f32[] %b)", False),
])
def test_matcher_on_hand_made_op_names(name, hit):
    assert moe_count.streams_expert_weights(name, C) is hit


def test_matcher_at_the_published_widths():
    yes = "%fusion.1 = f32[64,32,896] fusion(bf16[64,2304,896]{2,1,0} %p)"
    no = "%fusion.2 = bf16[32,4096] fusion(bf16[2304,4096] %q_proj)"
    assert moe_count.streams_expert_weights(yes, MELLUM)
    assert not moe_count.streams_expert_weights(no, MELLUM)


OPS = {"%f = f32[4,32,6] fusion(bf16[4,10,6] %p)": (0.3, 10),
       "%g = bf16[32,10] fusion(bf16[4,6,10] %q)": (0.1, 10),
       "%attn = bf16[32,8] fusion(bf16[32,8] %k)": (0.4, 10)}


def test_seconds_and_roofline_by_hand():
    assert moe_count.expert_op_seconds(OPS, C) == pytest.approx(0.4)
    # 5 executions x 2 layers x 1440 B over 0.4 s at 36,000 B/s
    assert moe_count.expert_roofline(5, 0.4, C, 36_000.0) == \
        pytest.approx(100.0)
    assert moe_count.expert_roofline(0, 0.4, C, 1.0) is None
    assert moe_count.expert_roofline(5, 0.0, C, 1.0) is None


def _metric(name):
    return bench_run.load_module("layer_metrics", name)


def test_the_three_metrics_on_a_hand_made_run():
    run = {"trace": {"ops": OPS, "busy_s": 0.8, "window_s": 1.0,
                     "module_ms": {"jit_prefill_chunk": [1.0, 1.0, 1.0],
                                   "jit_decode_paged": [2.0, 2.0],
                                   "jit_other": [9.0]}},
           "moe_config": C, "peak": {"hbm_bytes_per_s": 36_000.0},
           "moe_assignments": 32 * 2 * 7, "moe_dispatches": 7}
    assert _metric("expert_share.serve_moe").compute(run) == \
        pytest.approx(50.0)
    assert _metric("expert_roofline.serve_moe").compute(run) == \
        pytest.approx(100.0)
    assert _metric("expert_rows_per_dispatch.serve_moe").compute(run) == \
        pytest.approx(16.0)
    assert _metric("decode_tick_ms.serve_moe").compute(run) == 2.0
    assert _metric("prefill_dispatch_ms.serve_moe").compute(run) == 1.0


def test_the_devices_two_metrics_read_what_the_accepted_cells_read():
    """`device_idle.serve_moe` and `hbm_peak_gb.serve_moe` are the
    accepted `.serve` metrics under this cell's name: same reading of
    the same run."""
    run = {"trace": {"busy_s": 0.8, "window_s": 1.0},
           "memory_peak_bytes": 13_309_839_360}
    for name in ("device_idle", "hbm_peak_gb"):
        assert _metric(name + ".serve_moe").compute(run) == \
            _metric(name + ".serve").compute(run)
    assert _metric("device_idle.serve_moe").compute(run) == \
        pytest.approx(20.0)
    assert _metric("hbm_peak_gb.serve_moe").compute(run) == \
        pytest.approx(13.30983936)
    assert _metric("hbm_peak_gb.serve_moe").compute(
        {"memory_peak_bytes": 0}) is None


def test_a_program_without_the_counters_or_a_dense_run_reads_nothing():
    """What the parent commit gives: no `moe_*` counter in the
    snapshot, or a cell with no expert in it.  `None`, never a raise."""
    dense = {"trace": {"ops": OPS, "busy_s": 0.8, "window_s": 1.0,
                       "module_ms": {}}, "peak": {"hbm_bytes_per_s": 1.0}}
    for name in ("expert_share.serve_moe", "expert_roofline.serve_moe",
                 "expert_rows_per_dispatch.serve_moe"):
        assert _metric(name).compute(dense) is None
        assert _metric(name).compute({"trace": {}}) is None
    no_counter = {"moe_config": C, "moe_assignments": 0, "moe_dispatches": 0}
    assert _metric("expert_rows_per_dispatch.serve_moe").compute(
        no_counter) is None


def test_the_runner_refuses_a_program_without_the_new_keys():
    runner = bench_run.load_module("runners", "serve_moe")

    class Old:
        class LlamaConfig:
            __dataclass_fields__ = {"dim": None, "sliding_window": None}
    with pytest.raises(SystemExit, match="cannot build the configuration"):
        runner.llama_config(MELLUM, Old)


def test_the_configuration_file_keeps_the_catalogs_numbers():
    """Every published number under its own key; only the depth is cut."""
    c = MELLUM
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"], c["vocab_size"]) == \
        (2304, 128, 32, 4, 98304)
    assert (c["num_experts"], c["num_experts_per_tok"],
            c["moe_intermediate_size"], c["sliding_window"]) == \
        (64, 8, 896, 1024)
    assert c["num_hidden_layers"] == 4 and list(c["reduced"]) == \
        ["num_hidden_layers"]
    assert c["layer_types"][:4] == ["sliding_attention"] * 3 + \
        ["full_attention"] and len(c["layer_types"]) == 28
    assert c["rope_theta"] == \
        c["rope_parameters"]["sliding_attention"]["rope_theta"] == \
        c["rope_parameters"]["full_attention"]["rope_theta"]


def test_the_logit_rule_passes_served_weights_and_fails_int8_stacks():
    """Rule 1 of the cell's `correct` at the dry run's size, on the CPU:
    the scorer under the weights a bf16 engine serves reads rounding
    noise against the reference; under expert stacks rounded to int8
    codes (one scale per expert and output column) it reads over the
    configuration's limit."""
    import jax.numpy as jnp
    import numpy as np

    import reference_moe
    from singa_tpu import device, models, tensor

    runner = bench_run.load_module("runners", "serve_moe")
    _, _, cell, cfg = bench_run.load_cell("serve-code-closed", dry_run=True)
    device.set_default_device(device.create_cpu_device())
    tensor.set_seed(5)
    m = models.Llama(runner.llama_config(cfg, models))
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, cfg["init_len"]), np.int32))],
              is_train=False, use_graph=True)
    masters = {n: p.data for n, p in m.get_params().items()}
    served = {n: a.astype(jnp.bfloat16) for n, a in masters.items()}

    def int8(w):
        f = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=1, keepdims=True) / 127.0
        return (jnp.round(f / scale) * scale).astype(w.dtype)
    coarse = {n: int8(a) if n.endswith((".ffn.w_in", ".ffn.w_gate",
                                        ".ffn.w_out")) else a
              for n, a in served.items()}
    chk, e = cfg["check"], cfg["engine"]
    seq = np.random.default_rng(3).integers(
        0, cfg["vocab_size"], 100).astype(np.int32)
    read = {}
    for name, weights in (("served", served), ("int8", coarse)):
        got = runner.engine_scorer(m, (weights, {}), e,
                                   chk["logit_stride"])(seq)
        found = reference_moe.greedy_gap(
            reference_moe.rounded(masters), seq, 50, chk["pad_to"], cfg,
            chk["delta"], chk["tolerance"], got, chk["logit_stride"])
        read[name] = float(np.percentile(found["err"], 25))
    assert read["served"] < 1e-5 < chk["logit_err_limit"] < read["int8"]
