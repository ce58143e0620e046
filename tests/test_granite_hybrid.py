"""Granite 4.0-H's layer equations through the program (ISSUE 34):
Mamba-2 state-space layers whose recurrent state lives per slot and in
snapshots beside one attention layer's KV blocks; attention without
positional embedding at the attention multiplier; a held share of the
experts beside a shared MLP; a tied head.  Tier-1 CPU coverage at a
tiny width, f32, seeded weights.

`tests/reference_granite_hybrid.py` is the plain reference (the
recurrence row by row, no cache, no chunks, nothing imported from the
program).  The program is held to it through `GraniteHybrid.forward`,
through the cached closures `generate()` compiles, and through
`ServeEngine`: what its two programs left in the arena and in the
slot's state, and the tokens it served, across chunk splits with pad
rows, a snapshot hit, a partial hit cut back to the deepest snapshot, a
reused slot, preemption, `resubmit` and ticks with inactive slots.
Each engine case runs twice: as built, where it must agree, and with
the state zeroed on its way into every chunk's scan, where the same
comparison must fail.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_granite_hybrid as ref
from singa_tpu import layer, models, tensor
from singa_tpu.ops import moe as moe_ops
from singa_tpu.ops import ssm as ssm_ops
from singa_tpu.serve import ServeEngine, engine as engine_mod
from singa_tpu.serve.slots import BlockPool

CFG = models.GraniteHybridConfig.tiny()
VOCAB, BS, MAX_LEN = CFG.vocab_size, 8, 96
#: the source's keys, as the reference reads them
SRC = {"hidden_size": CFG.dim, "num_hidden_layers": CFG.num_layers,
       "num_attention_heads": CFG.num_heads,
       "num_key_value_heads": CFG.num_kv_heads,
       "attention_multiplier": CFG.attention_multiplier,
       "embedding_multiplier": CFG.embedding_multiplier,
       "residual_multiplier": CFG.residual_multiplier,
       "logits_scaling": CFG.logits_scaling, "rms_norm_eps": CFG.eps,
       "mamba_n_heads": CFG.mamba_heads, "mamba_d_head": CFG.mamba_head_dim,
       "mamba_d_state": CFG.mamba_d_state, "mamba_d_conv": CFG.mamba_d_conv,
       "num_local_experts": len(CFG.experts_held),
       "num_experts_per_tok": CFG.moe_top_k,
       "layer_types": list(CFG.layer_types),
       "deployment": {"experts_held": list(CFG.experts_held)}}
MAMBA = [i for i, k in enumerate(CFG.layer_types) if k == "mamba"]
TOL = dict(atol=2e-5, rtol=0)


def _build(seed=3, cfg=CFG):
    tensor.set_seed(seed)
    m = models.GraniteHybrid(cfg)
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


def _params(m):
    return {n: p.data for n, p in m.get_params().items()}


def _reference(m, ids, src=SRC):
    """(logits, margins, keys, values, states, windows), as numpy."""
    return tuple(np.asarray(a) for a in ref.logits_and_margin(
        _params(m), jnp.asarray(ids), ref.frozen(src)))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


@pytest.fixture(scope="module")
def granite():
    return _build()


def _state_dropped(mp):
    """Every scan enters from zeros: what a chunk computes when neither
    the slot's state nor a snapshot reaches it."""
    real = ssm_ops.ssd
    mp.setattr(ssm_ops, "ssd", lambda x, dt, A, B, C, D, S, *a, **k:
               real(x, dt, A, B, C, D, S * 0, *a, **k))


@pytest.fixture(params=["carried", "dropped"])
def engine(request, granite):
    """`build(**kw)` -> a ServeEngine over the tiny model, its prefill
    chunk `rows` tokens; and whether the comparison has to hold."""
    with pytest.MonkeyPatch.context() as mp:
        if request.param == "dropped":
            _state_dropped(mp)

        def build(rows=16, **kw):
            mp.setattr(engine_mod, "_PREFILL_ROWS", rows)
            kw = {"num_slots": 3, "max_len": MAX_LEN, "block_size": BS, **kw}
            return ServeEngine(granite, **kw)

        yield build, request.param == "carried"


def _holds(carried, check):
    """`check()` passes as built and fails with the state dropped."""
    if carried:
        check()
    else:
        with pytest.raises(AssertionError):
            check()


def _slot_matches(eng, m, slot, seq):
    """What the engine holds for `slot` against the reference's after
    the same tokens: the attention layer's keys and values, each mamba
    layer's state and window."""
    got = eng.slot_cache(slot)
    n = int(eng.pool.pos[slot])
    _, _, keys, values, states, windows = _reference(m, seq[:n])
    ia = im = 0
    for kind, (k, v, *state) in zip(CFG.layer_types, got):
        if kind == "attention":
            assert not state and k.shape[0] == n
            np.testing.assert_allclose(k, keys[ia], **TOL)
            np.testing.assert_allclose(v, values[ia], **TOL)
            ia += 1
        else:
            assert k is None and v is None
            np.testing.assert_allclose(state[0], states[im], **TOL)
            np.testing.assert_allclose(state[1], windows[im], **TOL)
            im += 1


def _greedy(m, seq, prompt_len):
    """Every served token is the reference's best at its position."""
    logits = _reference(m, seq)[0]
    rows = logits[prompt_len - 1:len(seq) - 1]
    gap = rows.max(-1) - rows[np.arange(rows.shape[0]), seq[prompt_len:]]
    assert gap.max() <= 1e-6, gap.max()


def _admitted(eng, prompt, new):
    """Submit, step once (admission and the first tick): (handle, slot)."""
    h = eng.submit(prompt, max_new_tokens=new)
    eng.step()
    slot = next(s for s, r in eng.running_items() if r.handle is h)
    return h, slot


def _delta(eng, before, *keys):
    after = eng.metrics.snapshot()
    return tuple(after[k] - before[k] for k in keys)


# -- the model against the reference ----------------------------------------

def test_forward_matches_the_reference(granite):
    ids = _ids(45, 1)
    got = np.asarray(granite(tensor.from_numpy(ids[None])).data)[0]
    np.testing.assert_allclose(got, _reference(granite, ids)[0], atol=2e-6,
                               rtol=0)


def test_prefill_then_decode_matches_the_reference(granite):
    """The scan over a prompt, then the recurrence token by token."""
    ids = _ids(40, 2)
    want, _, keys, values, states, windows = _reference(granite, ids)
    caches = granite.init_caches(1, 48)
    lg, caches = granite.forward_cached(tensor.from_numpy(ids[None, :23]),
                                        caches, 0)
    out = [np.asarray(lg.data)[0]]
    for t in range(23, 40):
        lg, caches = granite.forward_cached(
            tensor.from_numpy(ids[None, t:t + 1]), caches, jnp.asarray(t))
        out.append(np.asarray(lg.data)[0])
    np.testing.assert_allclose(np.concatenate(out), want, atol=2e-6, rtol=0)
    for j, i in enumerate(MAMBA):
        assert caches[i][0] is None and caches[i][1] is None
        assert caches[i][2].dtype == jnp.float32
        np.testing.assert_allclose(caches[i][2][0], states[j], **TOL)
        np.testing.assert_allclose(caches[i][3][0], windows[j], **TOL)
    (ia,) = [i for i, k in enumerate(CFG.layer_types) if k == "attention"]
    np.testing.assert_allclose(caches[ia][0][0, :40], keys[0], **TOL)


def test_generate_is_greedy_under_the_reference(granite):
    seq = granite.generate(_ids(19, 3)[None], max_new_tokens=20)[0]
    _greedy(granite, seq, 19)


@pytest.mark.parametrize("rows", [[7], [0, 15], [3, 20, 30]])
def test_state_rows_are_the_states_of_shorter_forwards(granite, rows):
    """Also across the model's own scan chunks (16 rows)."""
    ids = _ids(31, 4)
    caches = granite.init_caches(1, 32)
    _, got = granite.forward_cached(tensor.from_numpy(ids[None]), caches, 0,
                                    state_rows=jnp.asarray(rows, jnp.int32))
    for j, r in enumerate(rows):
        *_, states, windows = _reference(granite, ids[:r + 1])
        for n, i in enumerate(MAMBA):
            np.testing.assert_allclose(got[i][2][0, j], states[n], **TOL)
            np.testing.assert_allclose(got[i][3][0, j], windows[n], **TOL)


@pytest.mark.parametrize("what,change", [
    ("attention scale", {"attention_multiplier": 0.25}),
    ("embedding multiplier", {"embedding_multiplier": 1.0}),
    ("residual multiplier", {"residual_multiplier": 1.0}),
    ("logits scaling", {"logits_scaling": 1.0})])
def test_each_multiplier_is_read(granite, what, change):
    """The reference under another value of the key is another model."""
    ids = _ids(24, 5)
    got = np.asarray(granite(tensor.from_numpy(ids[None])).data)[0]
    other = _reference(granite, ids, {**SRC, **change})[0]
    assert np.abs(got - other).max() > 1e-3, what


def test_attention_carries_no_positional_embedding(granite):
    """Without position, one attention layer's keys depend on the token
    and on what the state-space layer before it has seen, and nothing
    else reads `rope_theta`: the config has no such field."""
    assert not hasattr(CFG, "rope_theta")
    assert not [n for n in _params(granite) if "rope" in n]


def test_a_layer_has_the_cache_it_needs(granite):
    caches = granite.init_caches(2, 16)
    for kind, c in zip(CFG.layer_types, caches):
        if kind == "attention":
            assert len(c) == 2 and c[0].shape == (2, 16, 2, 16)
        else:
            assert c[0] is None and c[1] is None
            assert c[2].shape == (2, 4, 32, 16) and c[2].dtype == jnp.float32
            assert c[3].shape == (2, 3, CFG.conv_dim)


# -- the expert layer: a held share, a shared MLP ---------------------------

def _moe_weights(e=16, d=24, h=10, seed=6):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s).astype(np.float32) * 0.3)
    return dict(router=f(d, e), w_in=f(e, d, h), w_out=f(e, h, d),
                w_gate=f(e, d, h)), f(7, d), \
        dict(gate=f(d, 14), up=f(d, 14), down=f(14, d))


def test_the_shares_add_up():
    """Eight chips that divide a layer of 16 experts, two a chip: their
    routed parts, with the shared MLP counted once, are the uncut
    layer's output, in the program and in the reference alike."""
    w, x, sh = _moe_weights()
    with jax.default_matmul_precision("highest"):
        shared = ref._gated(x, sh["gate"], sh["up"], sh["down"])
        whole = moe_ops.moe_forward(x, w["router"], w["w_in"], w["w_out"],
                                    top_k=4, w_gate=w["w_gate"],
                                    dropless=True) + shared
        parts, ref_parts = [], []
        for chip in range(8):
            held = (2 * chip, 2 * chip + 1)
            cut = lambda a: a[jnp.asarray(held)]
            parts.append(moe_ops.moe_forward(
                x, w["router"], cut(w["w_in"]), cut(w["w_out"]), top_k=4,
                w_gate=cut(w["w_gate"]), dropless=True, experts_held=held))
            p = {"router": w["router"], "w_in": cut(w["w_in"]),
                 "w_out": cut(w["w_out"]), "w_gate": cut(w["w_gate"])}
            c = {"num_experts_per_tok": 4, "num_local_experts": 2,
                 "deployment": {"experts_held": held}}
            ref_parts.append(ref.moe(x, p.__getitem__, c, lambda a: a)[0])
        c = {"num_experts_per_tok": 4, "num_local_experts": 16}
        uncut = ref.moe(x, w.__getitem__, c, lambda a: a)[0] + shared
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-6, rtol=0)
    np.testing.assert_allclose(sum(ref_parts) + shared, uncut, atol=1e-6,
                               rtol=0)
    np.testing.assert_allclose(whole, uncut, atol=1e-6, rtol=0)
    # and a single share is not the whole
    assert np.abs(np.asarray(parts[0] + shared - whole)).max() > 1e-3


def test_gates_are_over_the_routed_ten_not_over_the_held():
    """Renormalised over the held experts alone, a share's gates would
    sum to 1 for every token that routes to any of them."""
    w, x, _ = _moe_weights(seed=7)
    held = (0, 1, 2)
    cut = lambda a: a[jnp.asarray(held)]
    with jax.default_matmul_precision("highest"):
        got = moe_ops.moe_forward(x, w["router"], cut(w["w_in"]),
                                  cut(w["w_out"]), top_k=4,
                                  w_gate=cut(w["w_gate"]), dropless=True,
                                  experts_held=held)
        logits = x @ w["router"]
        routed = logits >= jnp.sort(logits, -1)[:, -4][:, None]
        gates = jax.nn.softmax(jnp.where(routed, logits, -jnp.inf), -1)[:, :3]
        over_held = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        outs = jnp.stack([ref._gated(x, w["w_gate"][e], w["w_in"][e],
                                     w["w_out"][e]) for e in held], 1)
    np.testing.assert_allclose(got, jnp.einsum("ne,ned->nd", gates, outs),
                               atol=1e-6, rtol=0)
    assert np.abs(np.asarray(
        got - jnp.einsum("ne,ned->nd", over_held, outs))).max() > 1e-3


def test_moe_layer_holds_a_share_and_routes_over_all(granite):
    p = _params(granite)
    assert p["blocks.0.ffn.router"].shape == (CFG.dim, CFG.num_experts)
    assert p["blocks.0.ffn.w_in"].shape == (3, CFG.dim, CFG.ffn_dim)
    assert p["blocks.0.shared.gate.W"].shape == (CFG.dim, CFG.shared_dim)


@pytest.mark.parametrize("held,kw,err", [
    ((0, 1), {"dropless": False}, "dropless"),
    ((0, 0), {"dropless": True}, "not a set"),
    ((0, 9), {"dropless": True}, "not a set"),
    ((), {"dropless": True}, "not a set")])
def test_experts_held_is_checked(held, kw, err):
    with pytest.raises(ValueError, match=err):
        layer.MoE(8, 16, top_k=2, act="swiglu", experts_held=held, **kw)


def test_an_uncut_layer_lowers_as_it_did():
    """`experts_held=None` adds nothing to the dropless program."""
    w, x, _ = _moe_weights(seed=8)
    f = lambda **kw: jax.jit(lambda x: moe_ops.moe_forward(
        x, w["router"], w["w_in"], w["w_out"], top_k=4, w_gate=w["w_gate"],
        dropless=True, **kw)).lower(x).as_text()
    assert f() == f(experts_held=None)
    assert f() != f(experts_held=tuple(range(16)))


# -- the engine: state per slot and in snapshots -----------------------------

@pytest.mark.parametrize("prompt_len,rows", [(40, 8), (40, 16), (37, 16),
                                             (48, 24), (21, 32), (70, 32)])
def test_chunk_splits(engine, granite, prompt_len, rows):
    """One prompt prefilled in chunks of `rows` tokens, wherever the
    boundaries fall (24 and 32 rows also split inside the model's scan
    chunks of 16); (37, 16), (21, 32) and (70, 32) end in pad rows,
    which the state must not follow.  A second chunk enters from the
    slot's own state: every case but (21, 32) has one."""
    build, carried = engine
    eng = build(rows)
    prompt = _ids(prompt_len, 20 + prompt_len)
    h, slot = _admitted(eng, prompt, 7)
    seq = np.concatenate([prompt, h.tokens])
    check = lambda: _slot_matches(eng, granite, slot, seq)
    if prompt_len > rows:
        _holds(carried, check)
    else:
        check()                 # one chunk from position 0: zeros anyway
    eng.run_until_idle()
    if carried:
        _greedy(granite, np.asarray(h.result()), prompt_len)
        assert eng.compiled_counts() == (1, 1)


def test_cold_then_partial_then_full_hit(engine, granite):
    """A tenant's first request is cold and leaves no snapshot; its
    second maps the shared blocks, recomputes their rows from position 0
    and leaves the state after them; from the third on the prefill
    starts there.  Every stream is the cold stream."""
    build, carried = engine
    eng = build(16)
    system = _ids(3 * BS, 60)
    prompts = [np.concatenate([system, _ids(n, 61 + n)])
               for n in (13, 9, 21, 5)]
    keys = ("prefix_hit_tokens", "prefix_tokens_recomputed",
            "state_snapshot_hits", "state_snapshot_writes")
    want = [(0, 0, 0, 0), (0, 24, 0, 1), (24, 0, 1, 0), (24, 0, 1, 0)]
    for i, (p, counts) in enumerate(zip(prompts, want)):
        before = eng.metrics.snapshot()
        h, slot = _admitted(eng, p, 6)
        assert _delta(eng, before, *keys) == counts
        seq = np.concatenate([p, h.tokens])
        check = lambda: _slot_matches(eng, granite, slot, seq)
        if i >= 2:
            _holds(carried, check)          # entered from the snapshot
        elif carried:
            check()
        eng.run_until_idle()
        if carried:
            np.testing.assert_array_equal(
                np.asarray(h.result()),
                granite.generate(p[None], max_new_tokens=6)[0])
    assert eng.compiled_counts() == (1, 1)


def test_partial_hit_is_cut_back_to_the_deepest_snapshot(engine, granite):
    """Resident blocks run past the deepest snapshot: the prefill starts
    at the snapshot (block m), recomputes blocks m .. n without
    rewriting them, and leaves the state after block n."""
    build, carried = engine
    eng = build(16)
    system, more = _ids(2 * BS, 70), _ids(3 * BS, 71)
    a = np.concatenate([system, _ids(11, 72)])
    b = np.concatenate([system, more, _ids(7, 73)])
    c = np.concatenate([system, more, _ids(12, 74)])
    for p in (a, b):        # b leaves the state after `system`: m = 2
        eng.submit(p, max_new_tokens=3)
        eng.run_until_idle()
    arena = jax.tree.map(np.asarray, eng.pool.caches)
    before = eng.metrics.snapshot()
    h, slot = _admitted(eng, c, 6)          # n = 5 blocks of b's, m = 2
    assert _delta(eng, before, "prefix_hit_tokens", "prefix_tokens_recomputed",
                  "state_snapshot_hits", "state_snapshot_writes") == \
        (2 * BS, 3 * BS, 1, 1)
    # the shared blocks were recomputed, not rewritten
    for block in eng.pool.mapped_blocks(slot)[:5]:
        for old, new in zip(jax.tree.leaves(arena),
                            jax.tree.leaves(eng.pool.caches)):
            np.testing.assert_array_equal(old[block], np.asarray(new[block]))
    seq = np.concatenate([c, h.tokens])
    _holds(carried, lambda: _slot_matches(eng, granite, slot, seq))
    eng.run_until_idle()
    if carried:
        np.testing.assert_array_equal(
            np.asarray(h.result()),
            granite.generate(c[None], max_new_tokens=6)[0])
        # and the next one with that prefix starts after block 5
        before = eng.metrics.snapshot()
        d = np.concatenate([system, more, _ids(4, 75)])
        h = eng.submit(d, max_new_tokens=4)
        eng.run_until_idle()
        assert _delta(eng, before, "prefix_hit_tokens",
                      "prefix_tokens_recomputed") == (5 * BS, 0)
        _greedy(granite, np.asarray(h.result()), d.size)


def test_a_chunk_may_cross_the_end_of_the_view(engine, granite):
    """A chunk that enters from a carried state cannot start early as
    other models' do: its rows past the view go to the null block."""
    build, carried = engine
    eng = build(32, num_slots=1, max_len=48)
    system = _ids(BS, 80)
    for n in (4, 5):
        eng.submit(np.concatenate([system, _ids(n, 80 + n)]),
                   max_new_tokens=2)
        eng.run_until_idle()
    # third request: prefill starts at row 8, chunks [8, 40) and [40, 72)
    prompt = np.concatenate([system, _ids(35, 89)])
    h, slot = _admitted(eng, prompt, 4)
    seq = np.concatenate([prompt, h.tokens])
    _holds(carried, lambda: _slot_matches(eng, granite, slot, seq))
    eng.run_until_idle()
    if carried:
        _greedy(granite, np.asarray(h.result()), prompt.size)


def test_reused_slot_starts_from_zeros(engine, granite):
    """A request admitted at position 0 into a slot a longer one left:
    whatever the slot and the snapshots held, garbage here."""
    build, _ = engine
    eng = build(16, num_slots=1, share_prefix=False)
    eng.submit(_ids(30, 40), max_new_tokens=9)
    eng.run_until_idle()
    poison = lambda a: jnp.full_like(a, 1e4)
    eng.pool.slot_state = jax.tree.map(poison, eng.pool.slot_state)
    eng.pool.snapshots = jax.tree.map(poison, eng.pool.snapshots)
    prompt = _ids(12, 41)
    h, slot = _admitted(eng, prompt, 8)
    seq = np.concatenate([prompt, h.tokens])
    _slot_matches(eng, granite, slot, seq)
    eng.run_until_idle()
    _greedy(granite, np.asarray(h.result()), prompt.size)


def test_preemption_resumes_exactly(engine, granite):
    build, carried = engine
    eng = build(16, num_slots=2, max_len=32, num_blocks=6)  # 5 usable
    prompts = [_ids(7, 50), _ids(7, 51)]
    hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
    eng.run_until_idle()
    assert eng.metrics.preempted >= 1

    def check():
        for p, h in zip(prompts, hs):
            _greedy(granite, np.asarray(h.result()), p.size)

    # the re-prefill is one chunk from position 0 or from a snapshot of
    # its own prompt's blocks: only the latter reads a state
    if carried:
        check()


def test_resubmit_resumes_exactly(engine, granite):
    build, carried = engine
    eng = build(16)
    prompt = _ids(26, 52)
    whole = granite.generate(prompt[None], max_new_tokens=14)[0]
    h = eng.resubmit(prompt, whole[26:33], max_new_tokens=14)
    eng.step()
    slot = next(s for s, r in eng.running_items() if r.handle is h)
    seq = np.concatenate([prompt, h.tokens])
    _holds(carried, lambda: _slot_matches(eng, granite, slot, seq))
    eng.run_until_idle()
    if carried:
        np.testing.assert_array_equal(np.asarray(h.result()), whole)


def test_recovery_re_prefills_from_zeros(engine, granite):
    build, carried = engine
    eng = build(16)
    prompt = _ids(29, 54)
    h, _ = _admitted(eng, prompt, 12)
    for _ in range(3):
        eng.step()
    eng.recover("test")
    assert not eng.pool._snap_of
    eng.run_until_idle()
    if carried:
        np.testing.assert_array_equal(
            np.asarray(h.result()),
            granite.generate(prompt[None], max_new_tokens=12)[0])


def test_inactive_slots_neither_read_nor_write(engine, granite):
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
    _holds(carried, lambda: _slot_matches(eng, granite, slot, seq))


def test_mixed_batch_is_greedy_with_one_program_each(granite):
    eng = ServeEngine(granite, num_slots=3, max_len=MAX_LEN, block_size=BS)
    prompts = [_ids(n, 90 + n) for n in (5, 33, 17, 48, 9)]
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run_until_idle()
    for p, h in zip(prompts, hs):
        np.testing.assert_array_equal(
            np.asarray(h.result()),
            granite.generate(p[None], max_new_tokens=10)[0])
    assert eng.compiled_counts() == (1, 1)
    snap = eng.metrics.snapshot()
    assert snap["ssm_state_bytes"] > 0 and snap["moe_assignments"] > 0


@pytest.mark.parametrize("at", [1, 4])
def test_an_eos_under_a_tick_in_flight_leaves_the_slot_clean(granite, at):
    """ISSUE 35: the request whose EOS lands took part in the tick
    dispatched before that landing, which moved the slot's recurrent
    state once more; nothing follows the EOS, and the next request in
    the slot enters from zeros or a snapshot, not from what was left."""
    eng = ServeEngine(granite, num_slots=1, max_len=MAX_LEN, block_size=BS)
    first, second = _ids(20, 70), _ids(12, 71)
    ref = [int(t) for t in
           granite.generate(first[None], max_new_tokens=10)[0, 20:]]
    eos = ref[at]
    a = eng.submit(first, max_new_tokens=10, eos_id=eos)
    b = eng.submit(second, max_new_tokens=8)
    while eng.pending:
        eng.step()
    assert a.finish_reason == "eos"
    assert a.tokens == ref[:ref.index(eos) + 1]
    np.testing.assert_array_equal(
        np.asarray(b.result()),
        granite.generate(second[None], max_new_tokens=8)[0])
    snap = eng.metrics.snapshot()
    assert snap["decode_ticks_ahead"] > 0 and eng.compiled_counts() == (1, 1)


# -- the pool: what is allocated, and the snapshots' host side ---------------

def test_only_the_attention_layer_has_blocks(granite):
    eng = ServeEngine(granite, num_slots=4, max_len=64, block_size=BS,
                      param_dtype=jnp.bfloat16)
    pool = eng.pool
    for kind, (ck, cv), state in zip(CFG.layer_types, pool.caches,
                                     pool.slot_state):
        if kind == "attention":
            assert ck.shape == (4 * 8 + 1, BS, 2, 16) and not state
            assert ck.dtype == jnp.bfloat16
        else:
            assert ck is None and cv is None
            # the state is f32 whatever the weights; the window follows them
            assert state[0].shape == (4, 4, 32, 16)
            assert state[0].dtype == jnp.float32
            assert state[1].dtype == jnp.bfloat16
    assert pool.tails is None and pool.snapshot_entries == 2
    assert jax.tree.leaves(pool.snapshots)[0].shape == (2, 4, 32, 16)
    assert pool.slot_state_bytes == 2 * (4 * 32 * 16 * 4
                                         + 3 * CFG.conv_dim * 2)
    h = eng.submit(_ids(20, 71), max_new_tokens=5)
    eng.run_until_idle()
    assert h.finish_reason == "length"


def test_light_state_keeps_its_tail_a_block():
    """`models.Zaya`'s state is lighter than a block's keys and values:
    it keeps a tail a block and no snapshot."""
    tensor.set_seed(1)
    z = models.Zaya(models.ZayaConfig.tiny())
    z.eval()
    z.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    pool = BlockPool(z, 2, 32, block_size=BS)
    assert pool.tails is not None and pool.snapshots is None
    assert pool.snapshot_entries == 0
    assert pool.claim_snapshot(b"k") == (None, False)


def test_snapshot_eviction_never_takes_an_entry_in_use(granite):
    pool = BlockPool(granite, 3, 32, block_size=BS)
    assert pool.snapshot_entries == 2
    k = [bytes([i]) * 16 for i in range(5)]
    for key in k[:2]:
        entry, evicted = pool.claim_snapshot(key)
        assert entry is not None and not evicted
        pool.settle_snapshots(key, entry, written=True)
    assert pool.claim_snapshot(k[0]) == (None, False)   # has one already
    # an admission enters from k[0], the least recently written ...
    assert pool.match_snapshot([k[0]], 1) == (1, pool._snap_of[k[0]])
    in_use = pool._snap_of[k[0]]
    # ... so the entry for its new snapshot is k[1]'s, not the one it reads
    entry, evicted = pool.claim_snapshot(k[2])
    assert evicted and entry != in_use and k[1] not in pool._snap_of
    # every entry in use: nothing to claim, nothing evicted
    assert pool.claim_snapshot(k[3]) == (None, False)
    assert k[0] in pool._snap_of
    pool.settle_snapshots(k[2], entry, written=True)
    assert set(pool._snap_of) == {k[0], k[2]}
    # a claimed entry whose prefill failed is free again, not mapped
    entry, evicted = pool.claim_snapshot(k[3])
    assert evicted and set(pool._snap_of) == {k[2]}  # k[0]: used before it
    pool.settle_snapshots(k[3], entry, written=False)
    assert k[3] not in pool._snap_of and pool._snap_free == [entry]
    # the deepest block of a chain that has one
    assert pool.match_snapshot([k[4], k[2], k[1]], 3) == \
        (2, pool._snap_of[k[2]])
    assert pool.match_snapshot([k[4], k[2], k[1]], 1) == (0, None)


def test_snapshots_are_evicted_least_recently_used_first(granite):
    """Three prompt families over two entries."""
    eng = ServeEngine(granite, num_slots=3, max_len=MAX_LEN, block_size=BS)
    fam = [_ids(2 * BS, 100 + i) for i in range(3)]

    def run(i, seed):
        p = np.concatenate([fam[i], _ids(6, seed)])
        h = eng.submit(p, max_new_tokens=3)
        eng.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(h.result()),
            granite.generate(p[None], max_new_tokens=3)[0])

    for i in range(3):          # cold, then the snapshot's write
        run(i, 200 + i)
        run(i, 210 + i)
    snap = eng.metrics.snapshot()
    assert snap["state_snapshot_writes"] == 3
    assert snap["state_snapshot_evictions"] == 1
    before = eng.metrics.snapshot()
    run(2, 220)                 # still there
    run(0, 221)                 # evicted: recomputed and written again
    assert _delta(eng, before, "state_snapshot_hits", "state_snapshot_writes",
                  "prefix_tokens_recomputed") == (1, 1, 2 * BS)


# -- what is not extended refuses at construction ------------------------------

@pytest.mark.parametrize("what,kw", [
    ("draft", {"spec_k": 2}), ("int8", {"kv_dtype": "int8"}),
    ("spill", {"spill_blocks": 4})])
def test_engine_refuses_what_does_not_carry_the_state(granite, what, kw):
    if what == "draft":
        kw = {**kw, "draft_model": granite}
    with pytest.raises(NotImplementedError, match="beside its KV blocks"):
        ServeEngine(granite, num_slots=2, max_len=32, block_size=BS, **kw)


def test_disaggregated_tier_refuses_at_construction(granite):
    from singa_tpu.serve.disagg import build_pools
    with pytest.raises(NotImplementedError, match="handoff"):
        build_pools(granite, 1, 1, num_slots=2, max_len=32, block_size=BS)


def test_handoff_is_refused_on_an_engine(granite):
    eng = ServeEngine(granite, num_slots=2, max_len=32, block_size=BS)
    eng.submit(_ids(9, 1), max_new_tokens=4)
    eng.step(decode=False)
    with pytest.raises(NotImplementedError, match="handoff"):
        eng.extract_handoff(0)


def test_training_is_refused(granite):
    with pytest.raises(NotImplementedError, match="inference only"):
        granite.train_one_batch(tensor.from_numpy(_ids(8)[None]))


def test_unknown_layer_types_are_refused():
    with pytest.raises(ValueError, match="unknown layer type"):
        models.GraniteHybrid(models.GraniteHybridConfig(
            num_layers=1, layer_types=("hybrid",)))
    with pytest.raises(ValueError, match="entries for"):
        models.GraniteHybrid(models.GraniteHybridConfig(
            num_layers=2, layer_types=("mamba",)))


def test_tied_head_shares_one_array_with_the_embedding(granite):
    eng = ServeEngine(granite, num_slots=2, max_len=32, block_size=BS,
                      param_dtype=jnp.bfloat16)
    params, _ = eng.weights()
    assert not [n for n in params if "lm_head" in n]
    assert params["tok_emb.table"].shape == (VOCAB, CFG.dim)
    assert params["tok_emb.table"].dtype == jnp.bfloat16


def test_the_scopes_are_in_the_lowered_programs(granite):
    eng = ServeEngine(granite, num_slots=2, max_len=32, block_size=BS)
    low = eng.lower_programs(["prefill_chunk", "decode"])
    text = {k: v.as_text(debug_info=True) for k, v in low.items()}
    for scope in ("ssm/ssm.in_proj", "ssm/ssm.conv", "ssm/ssm.norm",
                  "ssm/ssm.out_proj", "moe.shared", "moe.route",
                  "moe.experts", "attn.full", "lm_head.tied"):
        assert scope in text["prefill_chunk"], scope
        assert scope in text["decode"], scope
    assert "ssm/ssm.scan" in text["prefill_chunk"]
    assert "ssm/ssm.step" in text["decode"]
    assert "ssm.step" not in text["prefill_chunk"]
    assert "ssm.scan" not in text["decode"]


def test_the_two_reference_files_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    other = os.path.join(here, "..", "benchmark",
                         "reference_granite_hybrid.py")
    with open(os.path.join(here, "reference_granite_hybrid.py")) as a, \
            open(other) as b:
        assert a.read() == b.read()


def test_reference_state_after_a_valid_prefix_of_a_padded_sequence(granite):
    """`greedy_gap` pads to one shape: the states it returns are those
    after the sequence's last token, not after the padding."""
    seq = granite.generate(_ids(10, 7)[None], max_new_tokens=9)[0]
    found = ref.greedy_gap(_params(granite), seq, 10, 32, SRC, 0.0, 1e-6)
    *_, states, windows = _reference(granite, seq)
    np.testing.assert_allclose(found["states"], states, atol=1e-6, rtol=0)
    np.testing.assert_allclose(found["windows"], windows, atol=1e-6, rtol=0)
    assert found["over"] == 0 and found["checked"] == 9
    assert found["keys"].shape == (1, 19, 2, 16)
