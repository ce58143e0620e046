"""Ling-3.0-flash's layer equations through the program: Kimi delta
attention (a delta rule with a per-channel decay and an f32 state) in
chunks and token by token, MLA with a head-wise gate over a paged latent
cache beside it, experts routed within the best groups of experts, and
both kinds of cache in one `ServeEngine`: the KDA layers' state per slot
and in snapshots, the MLA layer's latent blocks.  Tier-1 CPU coverage at
a tiny width, f32, seeded weights.

`tests/reference_ling.py` is the plain reference (the delta rule token
by token, expanded attention, no cache, nothing imported from the
program).
"""

import contextlib
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_ling as ref
from singa_tpu import models, tensor
from singa_tpu.ops import kda as kda_ops
from singa_tpu.ops import kv_cache as kv_ops
from singa_tpu.ops import moe as moe_ops
from singa_tpu.serve import ServeEngine, engine as engine_mod

CFG = models.LingConfig.tiny()
VOCAB, BS, MAX_LEN = CFG.vocab_size, 8, 96
#: the source's keys, as the reference reads them
SRC = {"hidden_size": CFG.dim, "num_hidden_layers": CFG.num_layers,
       "num_attention_heads": CFG.num_heads, "head_dim": CFG.head_dim,
       "layer_group_size": CFG.layer_group_size,
       "short_conv_kernel_size": CFG.conv_size,
       "kda_lower_bound": CFG.kda_lower_bound,
       "kv_lora_rank": CFG.kv_lora_rank,
       "qk_nope_head_dim": CFG.qk_nope_head_dim,
       "qk_rope_head_dim": CFG.qk_rope_head_dim,
       "v_head_dim": CFG.v_head_dim, "rope_theta": CFG.rope_theta,
       "rms_norm_eps": CFG.eps, "first_k_dense_replace": CFG.first_dense,
       "num_experts": CFG.num_experts, "num_experts_per_tok": CFG.moe_top_k,
       "n_group": CFG.groups[0], "topk_group": CFG.groups[1],
       "routed_scaling_factor": CFG.route_scale,
       "deployment": {"experts_held": list(CFG.experts_held)}}
KDA = [i for i in range(CFG.num_layers) if not CFG.is_mla(i)]
MLA = [i for i in range(CFG.num_layers) if CFG.is_mla(i)]
TOL = dict(atol=5e-5, rtol=0)


def _build(seed=3, cfg=CFG):
    tensor.set_seed(seed)
    m = models.Ling(cfg)
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


def _params(m):
    return {n: p.data for n, p in m.get_params().items()}


def _reference(m, ids, at=None):
    """(logits, margins, latents, ropes, states, windows), the states
    after the first `at` rows (default: all of `ids`)."""
    at = len(ids) if at is None else at
    return jax.tree.map(np.asarray, ref.logits_and_margin(
        _params(m), jnp.asarray(ids), ref.frozen(SRC), at - 1))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


@pytest.fixture(scope="module")
def ling():
    return _build()


def _greedy(m, seq, prompt_len):
    """Every served token is the reference's best at its position."""
    logits = _reference(m, seq)[0]
    rows = logits[prompt_len - 1:len(seq) - 1]
    gap = rows.max(-1) - rows[np.arange(rows.shape[0]), seq[prompt_len:]]
    assert gap.max() <= 1e-6, gap.max()


def _slot_matches(eng, m, slot, seq):
    """What the engine holds for `slot` against the reference's after
    the same tokens: each KDA layer's state and window, the MLA layer's
    latent rows."""
    got = eng.slot_cache(slot)
    n = int(eng.pool.pos[slot])
    _, _, latents, ropes, states, windows = _reference(m, seq[:n])
    for j, i in enumerate(KDA):
        k, v, S, window = got[i]
        assert k is None and v is None
        np.testing.assert_allclose(S, states[j], **TOL)
        np.testing.assert_allclose(window, windows[j], **TOL)
    for j, i in enumerate(MLA):
        ck, cr = got[i]
        np.testing.assert_allclose(ck, latents[j][:n], **TOL)
        np.testing.assert_allclose(cr[:, :CFG.qk_rope_head_dim],
                                   ropes[j][:n], **TOL)


def _admitted(eng, prompt, new):
    """Submit, step once (admission and the first tick): (handle, slot)."""
    h = eng.submit(prompt, max_new_tokens=new)
    eng.step()
    slot = next(s for s, r in eng.running_items() if r.handle is h)
    return h, slot


def _engine(m, rows=16, **kw):
    """A ServeEngine over `m` whose prefill chunk is `rows` tokens."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_PREFILL_ROWS", rows)
        kw = {"num_slots": 3, "max_len": MAX_LEN, "block_size": BS, **kw}
        return ServeEngine(m, **kw)


@contextlib.contextmanager
def _as_on_a_tpu():
    """The platform probes steered while a program traces: the KDA state
    is updated by the decode kernel and the latent pools read in place;
    the kernels themselves still see the CPU (interpret mode)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda_ops, "on_tpu", lambda: True)
        mp.setattr(kv_ops, "on_tpu", lambda: True)
        yield


def test_the_two_reference_files_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "reference_ling.py")) as a, \
            open(os.path.join(here, "..", "benchmark",
                              "reference_ling.py")) as b:
        assert a.read() == b.read()


# -- the delta rule: chunks against the recurrence ---------------------------

def _kda_inputs(T, seed=0, B=2, H=3, dk=16, dv=8, lowest=False):
    rng = np.random.default_rng(seed)
    q = kda_ops.l2_normalize(rng.normal(size=(B, T, H, dk))) * dk ** -0.5
    k = kda_ops.l2_normalize(rng.normal(size=(B, T, H, dk)))
    v = jnp.asarray(rng.normal(size=(B, T, H, dv)), jnp.float32)
    g = jnp.full((B, T, H, dk), -5.0) if lowest else jnp.asarray(
        -5 * rng.uniform(size=(B, T, H, dk)) ** 3, jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(B, T, H)), jnp.float32)
    S0 = jnp.asarray(rng.normal(size=(B, H, dk, dv)), jnp.float32)
    return q, k, v, g, beta, S0


def _recurrence(q, k, v, g, beta, S):
    outs, states = [], []
    for t in range(q.shape[1]):
        o, S = kda_ops.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                beta[:, t], S)
        outs.append(o)
        states.append(S)
    return jnp.stack(outs, 1), jnp.stack(states, 1)


@pytest.mark.parametrize("chunk", [1, 7, 64])
def test_chunked_equals_the_recurrence(chunk):
    """150 rows: chunk boundaries fall inside the sequence, the last
    chunk ends in pad rows, and states are asked for on both sides of a
    boundary."""
    args = _kda_inputs(150)
    want_o, want_S = _recurrence(*args)
    o, S = kda_ops.kda_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=0)
    np.testing.assert_allclose(S, want_S[:, -1], atol=4e-6, rtol=0)
    rows = jnp.asarray([3, 63, 64, 149], jnp.int32)
    _, picked = kda_ops.kda_chunked(*args, state_rows=rows, chunk=chunk)
    np.testing.assert_allclose(picked, want_S[:, np.asarray(rows)],
                               atol=1e-5, rtol=0)


def test_decays_at_the_lower_bound_stay_finite():
    """A decay of -5 every row for 64 rows sums to -320 inside a chunk:
    the chunked form must still agree with the recurrence, not turn
    exp(-G) into inf."""
    args = _kda_inputs(130, seed=1, lowest=True)
    o, S = kda_ops.kda_chunked(*args, chunk=64)
    want_o, want_S = _recurrence(*args)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, atol=2e-6, rtol=0)
    np.testing.assert_allclose(S, want_S[:, -1], atol=2e-6, rtol=0)


def test_the_decode_kernel_is_the_step():
    *rows, S = _kda_inputs(1, seed=2, B=3, H=16, dk=128, dv=128)
    q, k, v, g, beta = (a[:, 0] for a in rows)
    want_o, want_S = kda_ops.kda_step(q, k, v, g, beta, S)
    o, S2 = kda_ops.kda_decode(q, k, v, g, beta, S, interpret=True)
    np.testing.assert_allclose(o, want_o, atol=1e-5, rtol=0)
    np.testing.assert_allclose(S2, want_S, atol=1e-5, rtol=0)


# -- the model against the reference ----------------------------------------

def test_forward_matches_the_reference(ling):
    ids = _ids(45, 1)
    got = np.asarray(ling(tensor.from_numpy(ids[None])).data)[0]
    np.testing.assert_allclose(got, _reference(ling, ids)[0], atol=2e-5,
                               rtol=0)


def test_layer_kinds_and_their_caches(ling):
    assert KDA == [0, 1, 3] and MLA == [2]
    caches = ling.init_caches(2, 32)
    for i in KDA:
        k, v, S, window = caches[i]
        assert k is None and v is None and S.dtype == jnp.float32
        assert S.shape == (2, CFG.num_heads, CFG.head_dim, CFG.head_dim)
        assert window.shape == (2, CFG.conv_size - 1, 3 * CFG.kda_width)
    (ck, cr), = (caches[i] for i in MLA)
    assert ck.shape == (2, 32, CFG.kv_lora_rank) and cr.shape[-1] == 128
    assert sum(caches[i][2].nbytes for i in KDA) \
        == 2 * 3 * CFG.num_heads * CFG.head_dim ** 2 * 4


def test_prefill_then_decode_matches_the_reference(ling):
    """The chunked form over a prompt of two of the model's chunks and a
    part, then the recurrence token by token."""
    ids = _ids(40, 2)
    want, _, latents, ropes, states, windows = _reference(ling, ids)
    caches = ling.init_caches(1, 48)
    lg, caches = ling.forward_cached(tensor.from_numpy(ids[None, :37]),
                                     caches, 0)
    out = [np.asarray(lg.data)[0]]
    for t in range(37, 40):
        lg, caches = ling.forward_cached(
            tensor.from_numpy(ids[None, t:t + 1]), caches, jnp.asarray(t))
        out.append(np.asarray(lg.data)[0])
    np.testing.assert_allclose(np.concatenate(out), want, atol=2e-5, rtol=0)
    for j, i in enumerate(KDA):
        np.testing.assert_allclose(caches[i][2][0], states[j], **TOL)
        np.testing.assert_allclose(caches[i][3][0], windows[j], **TOL)
    np.testing.assert_allclose(caches[MLA[0]][0][0, :40], latents[0], **TOL)


@pytest.mark.parametrize("part", ["head_gate", "erase", "decay_per_channel"])
def test_each_part_of_the_equations_is_read(ling, part):
    """The head-wise gate, the erase term and the per-channel decay each
    move the logits: a program without one would fail the reference."""
    ids = _ids(40, 5)
    want = _reference(ling, ids)[0]
    with pytest.MonkeyPatch.context() as mp:
        if part == "head_gate":
            mp.setattr(ling.blocks[MLA[0]].attn, "head_gate", False)
        elif part == "erase":
            mp.setattr(kda_ops, "kda_chunked", _no_erase)
        else:
            real = kda_ops.kda_chunked
            mp.setattr(kda_ops, "kda_chunked", lambda q, k, v, g, b, S, *a:
                       real(q, k, v, jnp.broadcast_to(
                           g.mean(-1, keepdims=True), g.shape), b, S, *a))
        got = np.asarray(ling(tensor.from_numpy(ids[None])).data)[0]
    assert np.abs(got - want).max() > 1e-3


def _no_erase(q, k, v, g, beta, S, state_rows=None, chunk=64):
    """The recurrence without its erase term: ``S = Diag(exp(g)) S +
    beta k v^T``."""
    outs = []
    for t in range(q.shape[1]):
        S = S * jnp.exp(g[:, t])[..., None] + (beta[:, t][..., None, None]
                                                * k[:, t][..., None]
                                                * v[:, t][..., None, :])
        outs.append(jnp.sum(q[:, t][..., None] * S, axis=-2))
    return jnp.stack(outs, 1), S


# -- group routing -------------------------------------------------------------

def _router(seed=0, N=24, D=16, E=32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(D, E)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(E,)) * 0.05, jnp.float32)
    return x, w, bias


def test_group_routing_equals_its_formula():
    """8 groups of 4, the best 3: the top 4 of score + bias among the
    kept groups' experts; the gates the chosen scores renormalised,
    times the scale."""
    x, w, bias = _router()
    logits = x @ w
    _, topi, gates = moe_ops._sigmoid_gates(logits, 4, bias, 2.5, (8, 3))
    s = np.asarray(jax.nn.sigmoid(logits))
    c = s + np.asarray(bias)
    for n in range(x.shape[0]):
        groups = np.sort(c[n].reshape(8, 4), -1)[:, -2:].sum(-1)
        kept = np.argsort(-groups)[:3]
        allowed = np.concatenate([np.arange(4) + 4 * g for g in kept])
        want = allowed[np.argsort(-c[n][allowed])[:4]]
        assert sorted(np.asarray(topi[n]).tolist()) == sorted(want.tolist())
        np.testing.assert_allclose(
            np.asarray(gates[n]), s[n][np.asarray(topi[n])]
            / s[n][want].sum() * 2.5, rtol=1e-6)
    # with these draws the group step changes some choices
    _, plain, _ = moe_ops._sigmoid_gates(logits, 4, bias, 2.5)
    assert (np.sort(np.asarray(plain), -1)
            != np.sort(np.asarray(topi), -1)).any()


def test_one_group_is_plain_sigmoid_routing():
    """At n_group 1 the choice is bit for bit the top-k of score + bias
    over all experts, as it was before groups existed."""
    x, w, bias = _router(seed=1)
    logits = x @ w
    scores, topi, gates = moe_ops._sigmoid_gates(logits, 4, bias, 1.8, (1, 1))
    s = jax.nn.sigmoid(logits)
    _, want = jax.lax.top_k(s + bias, 4)
    np.testing.assert_array_equal(topi, want)
    v = jnp.take_along_axis(s, want, -1)
    np.testing.assert_array_equal(
        gates, v / (jnp.sum(v, -1, keepdims=True) + 1e-20) * 1.8)


def test_the_shares_add_up():
    """Four chips that divide a layer of 16 experts in 4 groups, 4 a chip:
    their routed parts, with the shared expert counted once, are the
    uncut layer's output, in the program and in the reference alike."""
    rng = np.random.default_rng(4)
    D, E, H = 16, 16, 8
    w = {n: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
         for n, s in (("router", (D, E)), ("w_in", (E, D, H)),
                      ("w_out", (E, H, D)), ("w_gate", (E, D, H)),
                      ("score_bias", (E,)))}
    x = jnp.asarray(rng.normal(size=(20, D)), jnp.float32)
    sh = [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
          for s in ((D, H), (D, H), (H, D))]
    c = {"num_experts": E, "num_experts_per_tok": 3, "n_group": 4,
         "topk_group": 2, "routed_scaling_factor": 2.5}
    kw = dict(top_k=3, dropless=True, score_bias=w["score_bias"],
              route_scale=2.5, groups=(4, 2))
    with jax.default_matmul_precision("highest"):
        shared = ref._gated(x, *sh)
        whole = moe_ops.moe_forward(x, w["router"], w["w_in"], w["w_out"],
                                    w_gate=w["w_gate"], **kw) + shared
        parts, ref_parts = [], []
        for chip in range(4):
            held = tuple(range(4 * chip, 4 * chip + 4))
            cut = lambda a: a[jnp.asarray(held)]
            parts.append(moe_ops.moe_forward(
                x, w["router"], cut(w["w_in"]), cut(w["w_out"]),
                w_gate=cut(w["w_gate"]), experts_held=held, **kw))
            p = {**w, **{n: cut(w[n]) for n in ("w_in", "w_out", "w_gate")}}
            ref_parts.append(ref.moe(x, p.__getitem__, {
                **c, "deployment": {"experts_held": held}},
                lambda a: a)[0])
        uncut = ref.moe(x, w.__getitem__, c, lambda a: a)[0] + shared
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5, rtol=0)
    np.testing.assert_allclose(sum(ref_parts) + shared, uncut, atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(whole, uncut, atol=1e-5, rtol=0)
    assert np.abs(np.asarray(parts[0] + shared - whole)).max() > 1e-3


def test_groups_are_checked():
    from singa_tpu import layer
    with pytest.raises(ValueError, match="groups"):
        layer.MoE(16, 8, top_k=3, dropless=True, groups=(4, 2))   # softmax
    with pytest.raises(ValueError, match="groups"):
        layer.MoE(16, 8, top_k=9, dropless=True, score="sigmoid",
                  groups=(4, 2))


# -- the engine: two kinds of cache ------------------------------------------

@pytest.mark.parametrize("prompt_len,rows", [(40, 16), (37, 24), (21, 32)])
def test_engine_matches_the_reference(ling, prompt_len, rows):
    """A prompt prefilled in chunks of `rows` (a boundary mid-prompt
    where the prompt is longer, pad rows at the end), then decoded: the
    state, window and latent rows the programs left, and every served
    token, against the reference."""
    eng = _engine(ling, rows)
    prompt = _ids(prompt_len, 20 + prompt_len)
    h, slot = _admitted(eng, prompt, 7)
    _slot_matches(eng, ling, slot, np.concatenate([prompt, h.tokens]))
    eng.run_until_idle()
    _greedy(ling, np.asarray(h.result()), prompt_len)
    assert eng.compiled_counts() == (1, 1)


def test_a_snapshot_hit_resumes_both_caches(ling):
    """A tenant's shared prompt: cold, then mapped and recomputed from
    zeros (leaving a snapshot), then entered from the snapshot beside
    its latent prefix blocks.  Every stream is the uninterrupted one."""
    eng = _engine(ling, 16)
    system = _ids(3 * BS, 60)
    prompts = [np.concatenate([system, _ids(n, 61 + n)]) for n in (13, 9, 21)]
    keys = ("prefix_hit_tokens", "state_snapshot_hits",
            "state_snapshot_misses", "state_snapshot_writes")
    want = [(0, 0, 1, 0), (0, 0, 1, 1), (24, 1, 0, 0)]
    for p, counts in zip(prompts, want):
        before = eng.metrics.snapshot()
        h, slot = _admitted(eng, p, 6)
        after = eng.metrics.snapshot()
        assert tuple(after[k] - before[k] for k in keys) == counts
        _slot_matches(eng, ling, slot, np.concatenate([p, h.tokens]))
        eng.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(h.result()), ling.generate(p[None], max_new_tokens=6)[0])


def test_preemption_resumes_exactly(ling):
    eng = _engine(ling, 16, num_slots=2, max_len=32, num_blocks=6)
    prompts = [_ids(7, 50), _ids(7, 51)]
    hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
    eng.run_until_idle()
    assert eng.metrics.preempted >= 1
    for p, h in zip(prompts, hs):
        _greedy(ling, np.asarray(h.result()), p.size)


def test_resubmit_resumes_exactly(ling):
    eng = _engine(ling, 16)
    prompt = _ids(26, 52)
    whole = ling.generate(prompt[None], max_new_tokens=14)[0]
    h = eng.resubmit(prompt, whole[26:33], max_new_tokens=14)
    eng.step()
    slot = next(s for s, r in eng.running_items() if r.handle is h)
    _slot_matches(eng, ling, slot, np.concatenate([prompt, h.tokens]))
    eng.run_until_idle()
    np.testing.assert_array_equal(np.asarray(h.result()), whole)


def test_the_kernels_serve_the_same_tokens(ling):
    """Steered as on a TPU, the decode tick updates the state through
    the kernel and reads the latent blocks in place, and a prefill
    chunk attends its view (one chunk wider than max_len: a part KV
    tile) through the latent prefill kernel (interpret mode): the
    tokens are the reference's, and idle slots pass unmasked."""
    with _as_on_a_tpu():
        eng = _engine(ling, 16)
        prompts = [_ids(19, 80), _ids(30, 81)]
        hs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
    snap = eng.metrics.snapshot()
    assert snap["mla_prefill_kernel_chunks"] == snap["prefill_chunks"] > 0
    for p, h in zip(prompts, hs):
        _greedy(ling, np.asarray(h.result()), p.size)


def test_the_counters(ling):
    eng = _engine(ling, 16)
    h, _ = _admitted(eng, _ids(21, 90), 4)
    snap = eng.metrics.snapshot()
    # a tick moves each running slot's state, S and window, once each way
    assert snap["ssm_state_bytes"] == 2 * eng.pool.slot_state_bytes \
        * snap["decode_ticks"] > 0
    assert snap["prefill_chunk_rows"] == 21
    assert (snap["state_snapshot_hits"], snap["state_snapshot_misses"]) \
        == (0, 1)
    eng.run_until_idle()


@pytest.mark.parametrize("what,kw", [
    ("draft", {"spec_k": 2}), ("int8", {"kv_dtype": "int8"}),
    ("spill", {"spill_blocks": 4})])
def test_engine_refuses_what_does_not_carry_the_state(ling, what, kw):
    if what == "draft":
        kw = {**kw, "draft_model": ling}
    with pytest.raises(NotImplementedError, match="beside its KV blocks"):
        ServeEngine(ling, num_slots=2, max_len=32, block_size=BS, **kw)


def test_the_scopes_are_in_the_lowered_programs(ling):
    eng = ServeEngine(ling, num_slots=2, max_len=64, block_size=BS)
    with _as_on_a_tpu():
        text = {k: v.as_text(debug_info=True)
                for k, v in eng.lower_programs(
                    ["prefill_chunk", "decode"]).items()}
    for name in ("kda/kda.proj", "kda/kda.conv", "kda/kda.prefill",
                 "kda/kda.out", "attn.mla.out", "moe.route", "moe.shared",
                 "mlp.dense"):
        assert name in text["prefill_chunk"], name
    assert "kda.decode" not in text["prefill_chunk"]
    assert "kda/kda.decode" in text["decode"]
    assert "mla_decode_attention" in text["decode"]
    # the state kernel by its name in the decode program itself (in
    # interpret mode it leaves no call of its own in the lowered text),
    # where the state is whole 128-lane heads, as the kernel needs
    wide = _build(cfg=dataclasses.replace(CFG, head_dim=128))
    eng = ServeEngine(wide, num_slots=2, max_len=64, block_size=BS)
    pool = eng.pool
    with _as_on_a_tpu():
        jaxpr = jax.make_jaxpr(eng._decode)(
            eng._params, eng._buffers, eng._toks, pool.pos, pool.active,
            pool.tables, pool.group_plan(), pool.caches, pool.slot_state)
    assert {"kda_decode", "mla_decode_attention"} \
        <= set(_kernel_names(jaxpr.jaxpr))


def _kernel_names(jaxpr):
    """The name of every `pallas_call` of a jaxpr and of those inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _kernel_names(inner)


def test_training_is_refused(ling):
    with pytest.raises(NotImplementedError, match="inference only"):
        ling.train_one_batch(tensor.from_numpy(_ids(8)[None]))
