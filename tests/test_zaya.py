"""ZAYA1's layer equations through the program (ISSUE 32): attention in
a compressed latent with convolutional mixing and a shifted value
(CCA), whose side state lives beside the KV blocks; a top-1 expert
layer behind an MLP router; a head tied to the embedding.  Tier-1 CPU
coverage at a tiny width, f32, seeded weights.

`tests/reference_zaya.py` is the plain reference (no cache, no chunks,
nothing imported from the program).  The program is held to it through
`Zaya.forward`, through the cached closures `generate()` compiles, and
through `ServeEngine`: what its two programs left in the arena (keys,
values, the slot's side state) and the tokens it served, across chunk
splits, pad rows, prefix hits, a reused slot, preemption, `resubmit`
and ticks with inactive slots.  Each engine case runs twice: as built,
where it must agree, and with the side state dropped on its way into
every CCA layer, where the same comparison must fail.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_zaya as ref
from singa_tpu import layer, models, tensor
from singa_tpu.models._generate import decode_step, prefill_step
from singa_tpu.ops import cca as cca_ops
from singa_tpu.ops import moe as moe_ops
from singa_tpu.ops import rope as rope_ops
from singa_tpu.serve import ServeEngine, engine as engine_mod

CFG = models.ZayaConfig.tiny()
VOCAB, BS, MAX_LEN = CFG.vocab_size, 8, 96
#: the source's keys, as the reference reads them
SRC = {"num_attention_heads": CFG.num_heads,
       "num_key_value_heads": CFG.num_kv_heads, "head_dim": CFG.head_size,
       "rms_norm_eps": CFG.eps, "num_hidden_layers": CFG.num_layers,
       "partial_rotary_factor": CFG.partial_rotary_factor,
       "rope_parameters": {"hybrid": {"rope_theta": CFG.rope_theta}}}
TOL = dict(atol=2e-5, rtol=0)


def _build(seed=3):
    tensor.set_seed(seed)
    m = models.Zaya(CFG)
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


def _params(m):
    return {n: p.data for n, p in m.get_params().items()}


def _reference(m, ids):
    """(logits, margins, keys, values) of the reference, as numpy."""
    return tuple(np.asarray(a) for a in ref.logits_and_margin(
        _params(m), jnp.asarray(ids), ref.frozen(SRC)))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


@pytest.fixture(scope="module")
def zaya():
    return _build()


def _state_dropped(mp):
    """Every CCA layer reads zeros for its side state: what a cached
    forward computes when the state is not carried."""
    real = cca_ops.cca_qkv
    mp.setattr(cca_ops, "cca_qkv",
               lambda h, state, *a, **k: real(h, state * 0, *a, **k))


@pytest.fixture(params=["carried", "dropped"])
def engine(request, zaya):
    """`build(**kw)` -> a ServeEngine over the tiny model, its prefill
    chunk `rows` tokens; and whether the comparison has to hold."""
    with pytest.MonkeyPatch.context() as mp:
        if request.param == "dropped":
            _state_dropped(mp)

        def build(rows=16, **kw):
            mp.setattr(engine_mod, "_PREFILL_ROWS", rows)
            kw = {"num_slots": 3, "max_len": MAX_LEN, "block_size": BS, **kw}
            return ServeEngine(zaya, **kw)

        yield build, request.param == "carried"


def _holds(carried, check):
    """`check()` passes as built and fails with the state dropped."""
    if carried:
        check()
    else:
        with pytest.raises(AssertionError):
            check()


def _slot_matches(eng, m, slot, seq):
    """The arena's keys and values of `slot` against the reference's,
    and its side state against one uninterrupted cached forward."""
    got = eng.slot_cache(slot)
    n = got[0][0].shape[0]
    _, _, keys, values = _reference(m, seq[:n])
    _, whole = prefill_step(m, n)(_params(m), {}, jnp.asarray(seq[None, :n]))
    for i, (k, v, state) in enumerate(got):
        np.testing.assert_allclose(k, keys[i], **TOL)
        np.testing.assert_allclose(v, values[i], **TOL)
        np.testing.assert_allclose(state, np.asarray(whole[i][2][0]), **TOL)


def _greedy(m, seq, prompt_len):
    """Every served token is the reference's best at its position."""
    logits = _reference(m, seq)[0]
    rows = logits[prompt_len - 1:len(seq) - 1]
    gap = rows.max(-1) - rows[np.arange(rows.shape[0]), seq[prompt_len:]]
    assert gap.max() <= 1e-5, gap.max()


def _admitted(eng, prompt, new):
    """Submit, step once (admission and the first tick): (handle, slot)."""
    h = eng.submit(prompt, max_new_tokens=new)
    eng.step()
    slot = next(s for s, r in eng.running_items() if r.handle is h)
    return h, slot


# -- the model against the reference ----------------------------------------

def test_forward_matches_the_reference(zaya):
    ids = np.stack([_ids(40, 1), _ids(40, 2)])
    out = np.asarray(zaya.forward(tensor.from_numpy(ids)).data)
    for row, got in zip(ids, out):
        np.testing.assert_allclose(got, _reference(zaya, row)[0], **TOL)


def test_prefill_then_decode_matches_the_reference(zaya):
    """The closures `generate()` compiles: a prompt of 21 through the
    cache, then one token at a time; every position's logits against
    the reference's full forward."""
    ids = _ids(34, 3)
    want = _reference(zaya, ids)[0]
    params = _params(zaya)
    lg, caches = prefill_step(zaya, MAX_LEN, last_only=False)(
        params, {}, jnp.asarray(ids[None, :21]))
    got = [np.asarray(lg[0])]
    decode = decode_step(zaya)
    for t in range(21, ids.size):
        lg, caches = decode(params, {}, jnp.asarray(ids[None, t:t + 1]),
                            jnp.asarray(t, jnp.int32), caches)
        got.append(np.asarray(lg))
    np.testing.assert_allclose(np.concatenate(got), want, **TOL)


def test_generate_is_greedy_under_the_reference(zaya):
    prompt = _ids(19, 4)
    _greedy(zaya, zaya.generate(prompt[None], max_new_tokens=12)[0], 19)


@pytest.mark.parametrize("rows", [[7], [0, 15], [3, 7, 15]])
def test_state_rows_are_the_states_of_shorter_forwards(zaya, rows):
    """`forward_cached(state_rows=)`: the state after row j of a chunk
    is the state a forward of j + 1 rows ends in."""
    ids = jnp.asarray(_ids(16, 5)[None])
    t = tensor.from_numpy(np.asarray(ids))
    _, some = zaya.forward_cached(t, zaya.init_caches(1, 32), 0,
                                  state_rows=jnp.asarray(rows))
    for n, j in enumerate(rows):
        short = tensor.from_numpy(np.asarray(ids[:, :j + 1]))
        _, last = zaya.forward_cached(short, zaya.init_caches(1, 32), 0)
        for a, b in zip(some, last):
            np.testing.assert_allclose(a[2][:, n], b[2], **TOL)


# -- partial rotary, the router and the gate --------------------------------

@pytest.mark.parametrize("offset", [0, 5])
def test_partial_rotary_against_a_hand_made_table(offset):
    """Tables for 8 of a head's 16 dims: dims 0-3 pair with 4-7 under
    theta ** (-2 j / 8), dims 8-15 pass."""
    x = np.random.default_rng(6).normal(size=(1, 6, 2, 16)).astype(np.float32)
    cos, sin = rope_ops.rope_frequencies(8, 32, 100.0)
    got = np.asarray(rope_ops.apply_rope(jnp.asarray(x), cos, sin,
                                         offset=offset))
    want = x.copy()
    for t in range(6):
        for j in range(4):
            ang = (t + offset) * 100.0 ** (-2 * j / 8)
            a, b = x[0, t, :, j], x[0, t, :, 4 + j]
            want[0, t, :, j] = a * np.cos(ang) - b * np.sin(ang)
            want[0, t, :, 4 + j] = a * np.sin(ang) + b * np.cos(ang)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_full_rotary_is_what_it_was():
    x = jnp.asarray(np.random.default_rng(7).normal(
        size=(2, 5, 2, 8)).astype(np.float32))
    cos, sin = rope_ops.rope_frequencies(8, 16, 10000.0)
    a, b = x[..., :4], x[..., 4:]
    c, s = cos[:5][None, :, None], sin[:5][None, :, None]
    want = jnp.concatenate([a * c - b * s, a * s + b * c], -1)
    np.testing.assert_array_equal(
        np.asarray(rope_ops.apply_rope(x, cos, sin)), np.asarray(want))


def _moe_layer(router):
    tensor.set_seed(11)
    moe = layer.MoE(4, ffn_dim=24, top_k=1, act="swiglu", dropless=True,
                    router=router)
    x = tensor.from_numpy(np.random.default_rng(8).normal(
        size=(1, 12, 16)).astype(np.float32))
    return moe, x, np.asarray(moe(x).data)[0]


def test_mlp_router_and_unnormalised_gate_match_the_reference():
    moe, x, got = _moe_layer(layer.MLPRouter(4, 8))
    p = {n: t.data for n, t in moe.get_params().items()}
    assert sorted(n for n in p if n.startswith("router.")) == [
        "router.b1", "router.b2", "router.down", "router.w1", "router.w2",
        "router.w3"]
    with jax.default_matmul_precision("highest"):
        want, gap = ref._moe(jnp.asarray(x.data[0]), p.__getitem__,
                             lambda a: a.astype(jnp.float32))
    np.testing.assert_allclose(got, np.asarray(want), **TOL)
    # the gate is a probability under 1: a renormalised top-1 would
    # return the chosen expert's output whole
    logits = np.asarray(moe.router(x).data)
    prob = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    assert prob.max(-1).max() < 0.9 and float(np.asarray(gap).min()) > 0


def test_a_renormalised_top1_gate_would_not_match():
    moe, x, got = _moe_layer(layer.MLPRouter(4, 8))
    p = {n: t.data for n, t in moe.get_params().items()}
    logits = np.asarray(moe.router(x).data)
    pick = logits.argmax(-1)
    h = np.asarray(x.data[0])
    silu = lambda a: a / (1 + np.exp(-a))
    whole = np.stack([
        (silu(h[t] @ np.asarray(p["w_gate"][e]))
         * (h[t] @ np.asarray(p["w_in"][e]))) @ np.asarray(p["w_out"][e])
        for t, e in enumerate(pick)])
    prob = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    np.testing.assert_allclose(got, whole * prob.max(-1)[:, None],
                               atol=1e-4, rtol=0)
    assert np.abs(got - whole).max() > 1e-3


@pytest.mark.parametrize("top_k,dropless", [(1, False), (2, False),
                                            (2, True), (4, True)])
def test_linear_router_is_bit_for_bit_what_it_was(top_k, dropless):
    """`layer.MoE` without a router sub-module: its parameter is still
    `router`, and its output is the old formula's, written out here:
    logits by one f32 matmul inside `moe_forward`."""
    tensor.set_seed(12)
    moe = layer.MoE(4, ffn_dim=24, top_k=top_k, act="swiglu",
                    dropless=dropless, capacity_factor=4.0)
    x = tensor.from_numpy(np.random.default_rng(9).normal(
        size=(2, 6, 16)).astype(np.float32))
    got = np.asarray(moe(x).data)
    p = {n: t.data for n, t in moe.get_params().items()}
    assert sorted(p) == ["router", "w_gate", "w_in", "w_out"]
    want = moe_ops.moe_forward(x.data, p["router"], p["w_in"], p["w_out"],
                               4.0, top_k=top_k, w_gate=p["w_gate"],
                               dropless=dropless)
    np.testing.assert_array_equal(got, np.asarray(want))
    # and a function that yields the same logits routes the same
    same = moe_ops.moe_forward(
        x.data, lambda xf: moe_ops._router_logits(
            xf, p["router"],
            jax.lax.Precision.HIGHEST if dropless else None),
        p["w_in"], p["w_out"], 4.0, top_k=top_k, w_gate=p["w_gate"],
        dropless=dropless)
    np.testing.assert_array_equal(got, np.asarray(same))


def test_router_of_another_width_is_refused():
    with pytest.raises(ValueError, match="logits"):
        layer.MoE(4, ffn_dim=8, router=layer.MLPRouter(5, 8))


# -- the engine: state beside the KV blocks ----------------------------------

@pytest.mark.parametrize("prompt_len,rows", [(40, 8), (40, 16), (37, 16),
                                             (48, 24), (21, 32)])
def test_chunk_splits(engine, zaya, prompt_len, rows):
    """One prompt prefilled in chunks of `rows` tokens, wherever the
    boundaries fall; (37, 16) and (21, 32) end in pad rows, which the
    state must not follow."""
    build, carried = engine
    eng = build(rows)
    prompt = _ids(prompt_len, 20 + prompt_len)
    h, slot = _admitted(eng, prompt, 7)
    seq = np.concatenate([prompt, h.tokens])
    # even one chunk is followed by a tick, which reads the state
    _holds(carried, lambda: _slot_matches(eng, zaya, slot, seq))
    eng.run_until_idle()
    if carried:
        _greedy(zaya, np.asarray(h.result()), prompt_len)
        assert eng.compiled_counts() == (1, 1)


@pytest.mark.parametrize("shared_blocks", [1, 2, 4])
def test_prefix_hit_resumes_from_the_shared_tail(engine, zaya, shared_blocks):
    build, carried = engine
    eng = build(16)
    first = _ids(44, 30)
    eng.submit(first, max_new_tokens=3)
    eng.run_until_idle()
    second = np.concatenate([first[:shared_blocks * BS],
                             _ids(13, 31 + shared_blocks)])
    h, slot = _admitted(eng, second, 6)
    snap = eng.metrics.snapshot()
    assert snap["prefix_hit_tokens"] == shared_blocks * BS
    assert snap["cca_state_resumes"] == 1 and snap["cca_tail_blocks"] >= 5
    seq = np.concatenate([second, h.tokens])
    _holds(carried, lambda: _slot_matches(eng, zaya, slot, seq))
    eng.run_until_idle()
    if carried:
        _greedy(zaya, np.asarray(h.result()), second.size)


def test_reused_slot_starts_from_zeros(engine, zaya):
    """A request admitted at position 0 into a slot a longer one left:
    whatever the slot and the free blocks' tails held, garbage here."""
    build, carried = engine
    eng = build(16, num_slots=1, share_prefix=False)
    eng.submit(_ids(30, 40), max_new_tokens=9)
    eng.run_until_idle()
    poison = lambda a: jnp.full_like(a, 1e4)
    eng.pool.slot_state = jax.tree.map(poison, eng.pool.slot_state)
    eng.pool.tails = jax.tree.map(poison, eng.pool.tails)
    prompt = _ids(12, 41)
    h, slot = _admitted(eng, prompt, 8)
    assert slot == 0
    seq = np.concatenate([prompt, h.tokens])
    # one chunk from position 0 and one tick: only the tick reads state
    _holds(carried, lambda: _slot_matches(eng, zaya, slot, seq))
    eng.run_until_idle()
    if carried:
        _greedy(zaya, np.asarray(h.result()), prompt.size)


def test_preemption_resumes_exactly(engine, zaya):
    build, carried = engine
    eng = build(16, num_slots=2, max_len=32, num_blocks=6)  # 5 usable
    prompts = [_ids(7, 50), _ids(7, 51)]
    hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
    eng.run_until_idle()
    assert eng.metrics.preempted >= 1

    def check():
        for p, h in zip(prompts, hs):
            _greedy(zaya, np.asarray(h.result()), p.size)

    _holds(carried, check)


def test_resubmit_resumes_exactly(engine, zaya):
    build, carried = engine
    eng = build(16)
    prompt = _ids(26, 52)
    whole = zaya.generate(prompt[None], max_new_tokens=14)[0]
    h = eng.resubmit(prompt, whole[26:33], max_new_tokens=14)
    eng.step()
    slot = next(s for s, r in eng.running_items() if r.handle is h)
    seq = np.concatenate([prompt, h.tokens])
    _holds(carried, lambda: _slot_matches(eng, zaya, slot, seq))
    eng.run_until_idle()
    if carried:
        np.testing.assert_array_equal(np.asarray(h.result()), whole)


def test_inactive_slots_neither_read_nor_write(engine, zaya):
    """One request among three slots: the idle slots' state, NaN here,
    reaches nothing and is left as it is."""
    build, carried = engine
    eng = build(16)
    prompt = _ids(18, 53)
    h, slot = _admitted(eng, prompt, 9)
    idle = [s for s in range(3) if s != slot]
    eng.pool.slot_state = [tuple(s.at[jnp.asarray(idle)].set(jnp.nan)
                                 for s in state)
                           for state in eng.pool.slot_state]
    for _ in range(4):
        eng.step()
    for state in eng.pool.slot_state:
        for s in state:
            assert np.isnan(np.asarray(s)[idle]).all()
            assert np.isfinite(np.asarray(s)[slot]).all()
    seq = np.concatenate([prompt, h.tokens])
    _holds(carried, lambda: _slot_matches(eng, zaya, slot, seq))


def test_mixed_batch_is_greedy_with_one_program_each(zaya):
    """Five requests over a shared prefix through three slots: chunked
    prefill, hits, reuse and decode at once."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_PREFILL_ROWS", 16)
        eng = ServeEngine(zaya, num_slots=3, max_len=MAX_LEN, block_size=BS)
    prefix = _ids(24, 60)
    sizes = ((13, 9), (30, 12), (7, 20), (21, 6), (40, 10))
    hs = [eng.submit(np.concatenate([prefix, _ids(n, 61 + n)]),
                     max_new_tokens=new) for n, new in sizes]
    eng.run_until_idle()
    for h, (n, _) in zip(hs, sizes):
        _greedy(zaya, np.asarray(h.result()), 24 + n)
    snap = eng.metrics.snapshot()
    assert eng.compiled_counts() == (1, 1)
    assert snap["prefix_hits"] == 4 and snap["cca_state_resumes"] == 4
    assert snap["moe_assignments"] > 0


@pytest.mark.parametrize("at", [1, 4])
def test_an_eos_under_a_tick_in_flight_leaves_the_slot_clean(zaya, at):
    """ISSUE 35: the request whose EOS lands took part in the tick
    dispatched before that landing, which moved the slot's side state
    once more and wrote one row past the EOS; nothing follows the EOS,
    and the next request in the slot starts from its own state."""
    eng = ServeEngine(zaya, num_slots=1, max_len=MAX_LEN, block_size=BS)
    first, second = _ids(20, 70), _ids(12, 71)
    ref = [int(t) for t in
           zaya.generate(first[None], max_new_tokens=10)[0, 20:]]
    eos = ref[at]
    a = eng.submit(first, max_new_tokens=10, eos_id=eos)
    b = eng.submit(second, max_new_tokens=8)
    while eng.pending:
        eng.step()
    assert a.finish_reason == "eos"
    assert a.tokens == ref[:ref.index(eos) + 1]
    np.testing.assert_array_equal(
        np.asarray(b.result()),
        zaya.generate(second[None], max_new_tokens=8)[0])
    snap = eng.metrics.snapshot()
    assert snap["decode_ticks_ahead"] > 0 and eng.compiled_counts() == (1, 1)


def test_engine_keeps_host_slot_state_and_device_side_state(zaya):
    """What PR 31 made of the slot state stays: tables, pos and active
    are host numpy; the side state is on the device, donated with the
    arena; the lowered programs name no eager update."""
    eng = ServeEngine(zaya, num_slots=2, max_len=32, block_size=BS)
    assert all(isinstance(a, np.ndarray) for a in eng.pool.snapshot())
    leaves = jax.tree.leaves((eng.pool.slot_state, eng.pool.tails))
    assert leaves and all(isinstance(a, jax.Array) for a in leaves)
    assert eng.pool.slot_state[0][0].shape == (2, CFG.state_size)
    assert eng.pool.tails[0][0].shape == (eng.pool.num_blocks, CFG.state_size)
    texts = {k: v.as_text() for k, v in eng.lower_programs(
        ["prefill_chunk", "decode"]).items()}
    for name, text in texts.items():
        assert "tf.aliasing_output" in text or "jax.buffer_donor" in text
    h = eng.submit(_ids(11, 70), max_new_tokens=4)
    eng.run_until_idle()
    assert h.finish_reason == "length"
    assert eng.compiled_counts() == (1, 1)


def test_tied_head_shares_one_array_with_the_embedding(zaya):
    eng = ServeEngine(zaya, num_slots=2, max_len=32, block_size=BS,
                      param_dtype=jnp.bfloat16)
    params, _ = eng.weights()
    assert not [n for n in params if "lm_head" in n]
    assert params["tok_emb.table"].shape == (VOCAB, CFG.dim)
    assert params["tok_emb.table"].dtype == jnp.bfloat16
    # the arena and the side state follow the cast
    assert eng.pool.caches[0][0].dtype == jnp.bfloat16
    assert eng.pool.slot_state[0][0].dtype == jnp.bfloat16
    assert eng.pool.tails[0][0].dtype == jnp.bfloat16
    h = eng.submit(_ids(20, 71), max_new_tokens=5)
    eng.run_until_idle()
    assert h.finish_reason == "length"


# -- what is not extended refuses at construction ------------------------------

@pytest.mark.parametrize("what,kw", [
    ("draft", {"spec_k": 2}), ("int8", {"kv_dtype": "int8"}),
    ("spill", {"spill_blocks": 4})])
def test_engine_refuses_what_does_not_carry_the_state(zaya, what, kw):
    if what == "draft":
        kw = {**kw, "draft_model": zaya}
    with pytest.raises(NotImplementedError, match="beside its KV blocks"):
        ServeEngine(zaya, num_slots=2, max_len=32, block_size=BS, **kw)


def test_disaggregated_tier_refuses_at_construction(zaya):
    from singa_tpu.serve.disagg import build_pools
    with pytest.raises(NotImplementedError, match="handoff"):
        build_pools(zaya, 1, 1, num_slots=2, max_len=32, block_size=BS)


def test_training_is_refused(zaya):
    with pytest.raises(NotImplementedError, match="inference only"):
        zaya.train_one_batch(tensor.from_numpy(_ids(8)[None]))


def test_the_two_reference_files_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    other = os.path.join(here, "..", "benchmark", "reference_zaya.py")
    with open(os.path.join(here, "reference_zaya.py")) as a, \
            open(other) as b:
        assert a.read() == b.read()


# -- the benchmark's counting functions (benchmark/cca_count.py) --------------

def _cca_count():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "benchmark", "cca_count.py")
    spec = importlib.util.spec_from_file_location("bench_cca_count", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: the published widths' keys, as `cca_count` reads them
PUBLISHED = {"num_attention_heads": 8, "num_key_value_heads": 2,
             "head_dim": 128, "cca_time0": 2, "cca_time1": 2}


def test_cca_count_widths_are_the_programs():
    count = _cca_count()
    src = {**SRC, "cca_time0": CFG.cca_time0, "cca_time1": CFG.cca_time1}
    assert count.widths(src)[1] == CFG.state_size
    assert count.widths(PUBLISHED) == (1280, 2688, 10, 128)
    assert models.ZayaConfig().state_size == 2688


@pytest.mark.parametrize("name,hit", [
    ("%fusion.1 = f32[1,258,1280] fusion(bf16[1,256,1024] %q)", True),
    ("%fusion.2 = f32[1,256,10,128] fusion(bf16[10,128,128] %tap)", True),
    ("%dus.3 = bf16[5121,2688] dynamic-update-slice(bf16[5121,2688] %t)",
     True),
    ("%dot.4 = bf16[256,1024] dot(bf16[256,2048] %h, bf16[2048,1024] %w)",
     False),
    ("%fusion.5 = f32[16,64,2048] fusion(bf16[16,2048,2048] %w_in)", False),
])
def test_cca_count_shape_rules(name, hit):
    assert _cca_count().does_cca_mixing(name, PUBLISHED) is hit


def test_cca_count_finds_the_mixing_in_the_lowered_programs(zaya):
    """The shapes the matcher looks for are in both compiled programs'
    HLO: the packed latent and the state arrays of the tiny model."""
    count = _cca_count()
    src = {**SRC, "cca_time0": CFG.cca_time0, "cca_time1": CFG.cca_time1}
    eng = ServeEngine(zaya, num_slots=2, max_len=32, block_size=BS)
    for name, low in eng.lower_programs(["prefill_chunk", "decode"]).items():
        lines = [l for l in low.compile().as_text().splitlines()
                 if count.does_cca_mixing(l, src)]
        assert len(lines) >= CFG.num_layers, name
