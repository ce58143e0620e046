"""Sliding and full attention layers with their own RoPE in one block
stack, over a dropless mixture of experts (Mellum2's layer equations,
ISSUE 28) — tier-1 CPU coverage at a tiny width, f32, seeded weights.

`tests/reference_moe.py` is the plain reference (the equations in
`jax.numpy`, nothing imported from the program); the program is held to
it through `Llama.forward`, through the cached closures the serving
engine compiles (chunked prefill, then decode), and through
`ServeEngine` itself with prefix sharing on.  Contexts run to three
times the window, the head size is not `dim // heads`, and one test
biases the router until a single expert takes every token, which the
capacity path drops and the dropless path must not.
"""

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

import reference_moe as ref
from singa_tpu import models, tensor
from singa_tpu.models._generate import decode_step, resume_step
from singa_tpu.ops import rope as rope_ops
from singa_tpu.serve import ServeEngine

WINDOW, VOCAB, BS, MAX_LEN = 8, 96, 8, 48
YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
        "original_max_position_embeddings": 16, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.1386294361119891}
#: the source's keys, as the reference reads them
SRC = {"hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "rms_norm_eps": 1e-6, "num_hidden_layers": 4,
       "sliding_window": WINDOW,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
       "num_experts": 8, "num_experts_per_tok": 4, "norm_topk_prob": True,
       "moe_intermediate_size": 24, "vocab_size": VOCAB,
       "rope_parameters": {
           "full_attention": YARN,
           "sliding_attention": {"rope_type": "default",
                                 "rope_theta": 10000.0}}}


def _config(**over):
    return models.LlamaConfig(**{**dict(
        vocab_size=VOCAB, dim=48, num_layers=4, num_heads=4, num_kv_heads=2,
        head_size=16, ffn_dim=24, max_position=64, rope_theta=10000.0,
        sliding_window=WINDOW, eps=1e-6, layer_types=tuple(SRC["layer_types"]),
        yarn_factor=YARN["factor"],
        yarn_original_max_position=YARN["original_max_position_embeddings"],
        num_experts=8, moe_top_k=4, moe_dropless=True), **over})


def _build(cfg, seed=5):
    tensor.set_seed(seed)
    m = models.Llama(cfg)
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


def _params(m):
    return {n: p.data for n, p in m.get_params().items()}


def _reference(m, ids):
    lg, margin = ref.logits_and_margin(_params(m), jnp.asarray(ids),
                                       ref.frozen(SRC))
    return np.asarray(lg), np.asarray(margin)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


@pytest.fixture(scope="module")
def mellum():
    return _build(_config())


def test_forward_matches_the_reference(mellum):
    """(a) two rows of 3x the window through `Llama.forward`."""
    ids = np.stack([_ids(3 * WINDOW, 1), _ids(3 * WINDOW, 2)])
    out = np.asarray(mellum.forward(tensor.from_numpy(ids)).data)
    for row, got in zip(ids, out):
        want, _ = _reference(mellum, row)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_chunked_prefill_then_decode_matches_the_reference(mellum):
    """(b) the closures the engine compiles: two block-sized chunks at
    traced offsets, then one token at a time through the cache, to 30
    positions; every position's logits against the full forward."""
    ids = _ids(30, 3)
    want, _ = _reference(mellum, ids)
    resume, decode = resume_step(mellum), decode_step(mellum)
    params, caches = _params(mellum), mellum.init_caches(1, MAX_LEN)
    got = []
    for start in range(0, 2 * BS, BS):
        lg, caches = resume(params, {}, jnp.asarray(ids[None, start:start + BS]),
                            jnp.asarray(start, jnp.int32), caches)
        got.append(np.asarray(lg[0]))
    for t in range(2 * BS, ids.size):
        lg, caches = decode(params, {}, jnp.asarray(ids[None, t:t + 1]),
                            jnp.asarray([t], jnp.int32), caches)
        got.append(np.asarray(lg))
    np.testing.assert_allclose(np.concatenate(got), want, atol=1e-4, rtol=0)


@pytest.fixture(scope="module")
def served(mellum):
    """Two requests that share a 19-token prefix, through one engine
    whose prefill chunk is two blocks (a chunk of the whole `MAX_LEN`
    would start at 0 and recompute the shared prefix)."""
    from singa_tpu.serve import engine as engine_mod
    prefix = _ids(19, 4)
    prompts = [np.concatenate([prefix, _ids(n, 5 + n)]) for n in (6, 9)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_PREFILL_ROWS", 2 * BS)
        eng = ServeEngine(mellum, num_slots=2, max_len=MAX_LEN,
                          block_size=BS)
    hs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    return eng, prompts, hs


def test_engine_streams_equal_generate_and_are_the_references_argmax(
        mellum, served):
    """(b) paged, prefix sharing on: the served tokens are `generate()`'s
    and, teacher-forced, the reference's own arg-max at every position."""
    eng, prompts, hs = served
    assert eng.metrics.prefix_hit_tokens == 16      # two shared blocks
    for p, h in zip(prompts, hs):
        want = mellum.generate(p[None], max_new_tokens=12)[0, p.size:]
        np.testing.assert_array_equal(want, np.asarray(h.tokens))
        seq = h.result()
        assert seq.size > 3 * WINDOW
        g = ref.greedy_gap(_params(mellum), seq, p.size, MAX_LEN, SRC,
                           delta=0.0, tolerance=0.0)
        assert g["gap"] == 0.0 and g["unsure"] == 0
        assert (g["checked"], g["over"]) == (12, 0)
        # a token that is not the arg-max is counted beyond the tolerance
        seq[-3] = (seq[-3] + 1) % VOCAB
        g = ref.greedy_gap(_params(mellum), seq, p.size, MAX_LEN, SRC,
                           delta=0.0, tolerance=1e-3)
        assert g["over"] >= 1 and g["gap"] > 1e-3


def test_engine_compiles_two_programs_and_counts_what_it_routes(served):
    """(e) `(1, 1)` programs; `moe_assignments` is valid tokens x top-k:
    every prompt token not shared and every decoded token but the last
    of each request (the prefill yields the first)."""
    eng, prompts, hs = served
    assert eng.compiled_counts() == (1, 1)
    snap = eng.metrics.snapshot()
    prefilled = sum(p.size for p in prompts) - 16
    decoded = sum(len(h.tokens) - 1 for h in hs)
    assert snap["moe_assignments"] == 4 * (prefilled + decoded)
    chunks = -(-prompts[0].size // (2 * BS)) \
        + -(-(prompts[1].size - 16) // (2 * BS))
    assert (snap["prefill_chunks"], snap["prefill_chunk_rows"]) \
        == (chunks, prefilled)
    assert snap["moe_dispatches"] == chunks + 11    # both decode together


def test_a_dense_model_routes_nothing():
    m = models.Llama(models.LlamaConfig.tiny())
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    eng = ServeEngine(m, num_slots=2, max_len=32, block_size=8)
    eng.submit(_ids(5), max_new_tokens=3)
    eng.run_until_idle()
    snap = eng.metrics.snapshot()
    assert (snap["moe_dispatches"], snap["moe_assignments"]) == (0, 0)


@pytest.mark.parametrize("dropless", [True, False])
def test_one_expert_taking_every_token(dropless):
    """(c) a router biased so that expert 0 is every token's first
    choice and carries nearly all of its weight: dropless still matches
    the reference; the capacity path (1.25 x 4 x 24 / 8 = 15 places for
    24 tokens) loses expert 0 for nine of them."""
    m = _build(_config(moe_dropless=dropless))
    p = m.get_params()
    # channel 0 of the residual stream is a large positive constant, and
    # expert 0's router column reads that channel alone
    p["tok_emb.table"].data = p["tok_emb.table"].data.at[:, 0].set(10.0)
    for i in range(4):
        r = p[f"blocks.{i}.ffn.router"]
        r.data = r.data.at[:, 0].set(0.0).at[0, 0].set(3.0)
    ids = _ids(3 * WINDOW, 9)
    got = np.asarray(m.forward(tensor.from_numpy(ids[None])).data)[0]
    want, _ = _reference(m, ids)
    err = np.abs(got - want).max()
    assert err < 1e-4 if dropless else err > 1e-2, err


def test_yarn_table_against_numpy_and_sliding_layers_use_the_plain_one(
        mellum):
    """(d) the formula, written again here in numpy float64."""
    d, theta, y = 16, 10000.0, YARN
    j = np.arange(d // 2)
    inv = theta ** (-2.0 * j / d)

    def pair(r):
        return d * np.log(y["original_max_position_embeddings"]
                          / (2 * np.pi * r)) / (2 * np.log(theta))
    lo, hi = np.floor(pair(y["beta_fast"])), np.ceil(pair(y["beta_slow"]))
    lo, hi = max(lo, 0), min(hi, d - 1)
    ramp = np.clip((j - lo) / (hi - lo), 0, 1)
    ang = np.arange(64)[:, None] * (inv / y["factor"] * ramp
                                    + inv * (1 - ramp))[None, :]
    # the program's table is built at beta 32 and 1 and the paper's
    # attention factor 0.1 ln(factor) + 1: the values the source states
    assert (y["beta_fast"], y["beta_slow"]) == (32.0, 1.0)
    assert y["attention_factor"] == pytest.approx(
        0.1 * np.log(y["factor"]) + 1.0, abs=1e-12)
    cos, sin = rope_ops.yarn_frequencies(
        d, 64, theta, y["factor"], y["original_max_position_embeddings"])
    np.testing.assert_allclose(cos, np.cos(ang) * y["attention_factor"],
                               atol=2e-5)
    np.testing.assert_allclose(sin, np.sin(ang) * y["attention_factor"],
                               atol=2e-5)
    assert 0 < ramp.sum() < ramp.size           # a blend, not a no-op
    plain = rope_ops.rope_frequencies(d, 64, theta)
    kinds = [b.attn for b in mellum.blocks]
    assert [a.window for a in kinds] == [WINDOW] * 3 + [0]
    for a in kinds[:3]:
        np.testing.assert_array_equal(a._rope[0], plain[0])
        np.testing.assert_array_equal(a._rope[1], plain[1])
    np.testing.assert_array_equal(kinds[3]._rope[0], cos)
    np.testing.assert_array_equal(kinds[3]._rope[1], sin)
    assert np.abs(np.asarray(cos) - np.asarray(plain[0])).max() > 0.1


def test_the_two_copies_of_the_reference_agree(mellum):
    """(f) `benchmark/reference_moe.py` on the same input."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "benchmark", "reference_moe.py")
    spec = importlib.util.spec_from_file_location("bench_reference_moe", path)
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)
    ids = jnp.asarray(_ids(20, 11))
    for params in (_params(mellum), ref.rounded(_params(mellum))):
        a = ref.logits_and_margin(params, ids, ref.frozen(SRC))
        b = other.logits_and_margin(params, ids, other.frozen(SRC))
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # rounded once, outside the program: bf16 storage, and logits that
    # differ from the masters' by that rounding and no more
    assert {a.dtype for a in other.rounded(_params(mellum)).values()} == \
        {jnp.dtype(jnp.bfloat16)}
    full = np.asarray(ref.logits_and_margin(
        _params(mellum), ids, ref.frozen(SRC))[0])
    assert 0 < np.abs(np.asarray(a[0]) - full).max() < 0.2


def test_logit_error_of_the_engines_weights_against_the_reference(
        mellum, served):
    """What the benchmark's `correct` compares beside the served
    tokens: logits computed with `eng.weights()` through `resume_step`,
    chunk by chunk, against the reference's, every second column.  In
    f32 they agree; doubled logits read an error of exactly 1."""
    eng, prompts, hs = served
    seq = hs[1].result()
    params, buffers = eng.weights()
    assert params["blocks.0.ffn.w_in"] is _params(mellum)["blocks.0.ffn.w_in"]
    resume, caches, rows = resume_step(mellum), mellum.init_caches(1, MAX_LEN), []
    ids = np.zeros((-(-seq.size // BS) * BS,), np.int32)
    ids[:seq.size] = seq
    for start in range(0, ids.size, BS):
        lg, caches = resume(params, buffers, jnp.asarray(ids[None, start:start + BS]),
                            jnp.asarray(start, jnp.int32), caches)
        rows.append(np.asarray(lg[0, :, ::2]))
    got = np.concatenate(rows)[:seq.size]
    assert got.shape == (seq.size, VOCAB // 2)
    g = ref.greedy_gap(params, seq, prompts[1].size, MAX_LEN, SRC,
                       delta=0.0, tolerance=0.0, got=got, stride=2)
    assert g["err"].shape == (seq.size,) and g["err"].max() < 1e-5
    # against the weights a bf16 deployment would serve, these f32 ones
    # differ by that rounding
    g = ref.greedy_gap(ref.rounded(params), seq, prompts[1].size, MAX_LEN,
                       SRC, delta=0.0, tolerance=0.0, got=got, stride=2)
    assert 1e-4 < g["err"].max() < 0.02
    twice = ref.greedy_gap(params, seq, prompts[1].size, MAX_LEN, SRC,
                           delta=0.0, tolerance=0.0, got=2 * got, stride=2)
    np.testing.assert_allclose(twice["err"], 1.0, atol=0.05)
    assert "err" not in ref.greedy_gap(params, seq, prompts[1].size, MAX_LEN,
                                       SRC, delta=0.0, tolerance=0.0)


def test_resume_step_computes_in_the_dtype_of_the_device_the_ids_enter_on(
        mellum):
    """The embedding casts to that device's default dtype and every
    layer follows: the model's own by default, another on request (how
    a check runs f32 weights in f32 on a TPU, whose device computes in
    bf16)."""
    import copy

    from singa_tpu.model import model_device
    ids, pos = jnp.asarray(_ids(BS, 2)[None]), jnp.asarray(0, jnp.int32)
    own, _ = resume_step(mellum)(_params(mellum), {}, ids, pos,
                                 mellum.init_caches(1, MAX_LEN))
    assert own.dtype == jnp.float32
    half = copy.copy(model_device(mellum))
    half.default_dtype = jnp.bfloat16
    caches = [(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))
              for k, v in mellum.init_caches(1, MAX_LEN)]
    got, _ = resume_step(mellum, device=half)(_params(mellum), {}, ids, pos,
                                              caches)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(own),
                               atol=0.1)


def test_a_dense_config_builds_the_parameters_it_always_did():
    """(g) names and shapes of `LlamaConfig.tiny()`: the new keys'
    defaults change nothing."""
    c = models.LlamaConfig.tiny()
    assert (c.head_dim, c.layer_types, c.moe_dropless) == (16, (), False)
    m = models.Llama(c)
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    want = {"tok_emb.table": (256, 64), "norm_f.gamma": (64,),
            "lm_head.W": (64, 256)}
    for i in range(2):
        b = f"blocks.{i}."
        want.update({b + "attn_norm.gamma": (64,), b + "ffn_norm.gamma": (64,),
                     b + "attn.q_proj.W": (64, 64), b + "attn.k_proj.W": (64, 32),
                     b + "attn.v_proj.W": (64, 32), b + "attn.o_proj.W": (64, 64),
                     b + "ffn.gate.W": (64, 128), b + "ffn.up.W": (64, 128),
                     b + "ffn.down.W": (128, 64)})
    assert {n: tuple(p.shape) for n, p in m.get_params().items()} == want
    assert [a.attn.window for a in m.blocks] == [0, 0]


def test_layer_types_are_checked():
    with pytest.raises(ValueError, match="3 entries for 4 layers"):
        models.Llama(_config(layer_types=("full_attention",) * 3))
    with pytest.raises(ValueError, match="unknown layer type"):
        models.Llama(_config(layer_types=("linear_attention",) * 4))
