"""GLM-4.7-Flash's layer equations through the program (ISSUE 39):
multi-head latent attention over a paged cache of one latent row a
token, absorbed in decode and read in place by `ops.mla_attention`'s
kernel; a dense first layer; sigmoid-routed experts with a correction
bias in the choice and not in the gates, a held share of them beside a
shared expert; an untied head.  Tier-1 CPU coverage at a tiny width,
f32, seeded weights.

`tests/reference_glm_mla.py` is the plain reference (expanded
attention, no cache, no chunks, nothing imported from the program).
The program is held to it through `GlmMla.forward`, and through
`ServeEngine`: prefill of more than one chunk, a prefix hit on a shared
document, decode through the latent kernel and a chunk's attention
through the prefill kernel in interpret mode, what the programs left in
the arena, and the tokens served.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_glm_mla as ref
from singa_tpu import models, tensor
from singa_tpu.ops import kv_cache as kv_ops
from singa_tpu.ops import moe as moe_ops
from singa_tpu.ops import mla_attention
from singa_tpu.ops.mla_attention import (blocks_fetched, group_plan,
                                         mla_decode_attention,
                                         mla_prefill_attention)
from singa_tpu.serve import ServeEngine
from singa_tpu.serve.slots import BlockPool

CFG = models.GlmMlaConfig.tiny()
VOCAB, BS = CFG.vocab_size, 8
#: the source's keys, as the reference reads them
SRC = {"hidden_size": CFG.dim, "num_hidden_layers": CFG.num_layers,
       "num_attention_heads": CFG.num_heads, "q_lora_rank": CFG.q_lora_rank,
       "kv_lora_rank": CFG.kv_lora_rank,
       "qk_nope_head_dim": CFG.qk_nope_head_dim,
       "qk_rope_head_dim": CFG.qk_rope_head_dim,
       "v_head_dim": CFG.v_head_dim, "rope_theta": CFG.rope_theta,
       "rms_norm_eps": CFG.eps, "first_k_dense_replace": CFG.first_dense,
       "n_routed_experts": len(CFG.experts_held),
       "num_experts_per_tok": CFG.moe_top_k,
       "routed_scaling_factor": CFG.route_scale,
       "deployment": {"experts_held": list(CFG.experts_held)}}
HI = jax.default_matmul_precision("highest")


def _build(seed=3, cfg=CFG):
    tensor.set_seed(seed)
    m = models.GlmMla(cfg)
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32))],
              is_train=False, use_graph=False)
    return m


def _params(m):
    return {n: p.data for n, p in m.get_params().items()}


@pytest.fixture(scope="module")
def glm():
    return _build()


@contextlib.contextmanager
def _as_on_a_tpu():
    """The platform probe of `ops.kv_cache` steered while a program
    traces: the arena's latent pools are then read in place by the
    decode kernel, and a view the prefill kernel tiles is attended by
    it; the kernels themselves still see the CPU (interpret mode)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kv_ops, "on_tpu", lambda: True)
        yield


def _ref_logits(params, seq, src=SRC):
    with HI:
        out = ref.logits_one(params, jnp.asarray(seq, jnp.int32), src)
    return [np.asarray(a) for a in out]


def test_forward_matches_the_reference(glm):
    ids = np.random.default_rng(0).integers(0, VOCAB, (1, 40))
    with HI:
        got = np.asarray(glm(tensor.from_numpy(ids.astype(np.int32))).data)
    want = _ref_logits(_params(glm), ids[0])[0]
    np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=0)


def test_dense_first_layer_and_expert_layers_after(glm):
    names = set(_params(glm))
    assert {"blocks.0.mlp.gate.W", "blocks.0.mlp.down.W"} <= names
    assert not any(n.startswith("blocks.0.ffn.") for n in names)
    for i in range(1, CFG.num_layers):
        assert f"blocks.{i}.ffn.score_bias" in names
        assert f"blocks.{i}.shared.gate.W" in names
        assert not any(n.startswith(f"blocks.{i}.mlp") for n in names)
    assert _params(glm)["blocks.0.mlp.gate.W"].shape == (CFG.dim,
                                                         CFG.dense_ffn_dim)
    # the dense layer is read: with its FFN taken as an expert layer's
    # the reference moves
    params = _params(glm)
    ids = np.arange(12) % VOCAB
    base = _ref_logits(params, ids)[0]
    zero = dict(params, **{"blocks.0.mlp.down.W": 0 * params[
        "blocks.0.mlp.down.W"]})
    assert np.abs(_ref_logits(zero, ids)[0] - base).max() > 1e-2


@pytest.mark.parametrize("rows", [1, 3, 9])
def test_absorbed_equals_expanded(glm, rows):
    """The program's absorbed attention (W_kvb folded into the query and
    the output) over a dense latent cache, rows at per-row limits,
    against the expanded form (every head's keys and values made from
    the latent): equal in f32."""
    attn = glm.blocks[1].attn
    c, rng = CFG, np.random.default_rng(rows)
    B, S, H, dn = 2, 40, c.num_heads, c.qk_nope_head_dim
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q_nope, q_rope = f(B, rows, H, dn), f(B, rows, H, c.qk_rope_head_dim)
    ck, cr = f(B, S, c.kv_lora_rank), f(B, S, c.qk_rope_head_dim)
    wkv = attn._kv_b(jnp.float32)
    limit = np.asarray([rows + 5, S])
    with HI:
        got = np.asarray(attn._attend(q_nope, q_rope, ck, cr, wkv,
                                      jnp.asarray(limit), 0.2))
        kv = jnp.einsum("bsr,rhe->bshe", ck, wkv)
        sc = (jnp.einsum("bthd,bshd->bhts", q_nope, kv[..., :dn])
              + jnp.einsum("bthe,bse->bhts", q_rope, cr)) * 0.2
    for b in range(B):
        for t in range(rows):
            n = limit[b] - rows + t + 1
            p = jax.nn.softmax(sc[b, :, t, :n], axis=-1)       # (H, n)
            want = jnp.einsum("hs,shv->hv", p, kv[b, :n, :, dn:])
            np.testing.assert_allclose(got[b, t], np.asarray(want),
                                       atol=2e-5)


def test_sigmoid_router_bias_chooses_and_does_not_gate():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
    bias = jnp.zeros((8,)).at[7].set(5.0)     # expert 7 always chosen
    scores, topi, gates = moe_ops._sigmoid_gates(logits, 3, bias, 1.8)
    np.testing.assert_allclose(np.asarray(scores),
                               np.asarray(jax.nn.sigmoid(logits)), rtol=1e-6)
    assert (np.asarray(topi) == 7).any(axis=1).all()
    # the gates are the chosen scores without the bias, renormalised
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(topi), 1)
    np.testing.assert_allclose(np.asarray(gates),
                               1.8 * chosen / chosen.sum(1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(1), 1.8, rtol=1e-6)
    # without the bias the choice is the plain top 3 of the scores
    _, plain, _ = moe_ops._sigmoid_gates(logits, 3, 0 * bias, 1.8)
    np.testing.assert_array_equal(
        np.sort(np.asarray(plain), 1),
        np.sort(np.argsort(-np.asarray(scores), 1)[:, :3], 1))


def test_the_shares_add_up(glm):
    """Four chips of two experts each: the held experts' sums of every
    share, with the shared expert counted once, are the uncut layer."""
    p = {n[len("blocks.1."):]: a for n, a in _params(glm).items()
         if n.startswith("blocks.1.")}
    E, rng = CFG.num_experts, np.random.default_rng(2)
    d, h = CFG.dim, CFG.ffn_dim
    full = {k: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
            for k, s in (("w_in", (E, d, h)), ("w_gate", (E, d, h)),
                         ("w_out", (E, h, d)))}
    x = jnp.asarray(rng.standard_normal((10, d)), jnp.float32)
    with HI:
        total = sum(moe_ops.moe_forward(
            x, p["ffn.router"], full["w_in"][s], full["w_out"][s],
            top_k=CFG.moe_top_k, w_gate=full["w_gate"][s], dropless=True,
            experts_held=tuple(range(s.start, s.stop)),
            score_bias=p["ffn.score_bias"], route_scale=CFG.route_scale)
            for s in (slice(0, 2), slice(2, 4), slice(4, 6), slice(6, 8)))
        shared = ref._gated(x, p["shared.gate.W"], p["shared.up.W"],
                            p["shared.down.W"])
        src = dict(SRC, n_routed_experts=E, deployment={})
        uncut, _ = ref.moe(x, lambda n: full[n] if n in full else p[f"ffn.{n}"],
                           src, lambda a: a)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(uncut + shared), atol=1e-5)


@pytest.mark.parametrize("fault", ["bias_in_gates", "no_bias", "softmax",
                                   "no_scale"])
def test_each_routing_rule_is_read(glm, fault):
    """The reference holds the program's routing: each departure from
    it moves the logits."""
    params = _params(glm)
    ids = np.random.default_rng(4).integers(0, VOCAB, 24)
    base = _ref_logits(params, ids)[0]
    orig = moe_ops._sigmoid_gates

    def wrong(logits, k, bias, scale, groups):
        if fault == "bias_in_gates":
            s, i, _ = orig(logits, k, bias, scale, groups)
            v = jnp.take_along_axis(s + bias, i, -1)
            return s, i, v / v.sum(-1, keepdims=True) * scale
        if fault == "no_bias":
            return orig(logits, k, 0 * bias, scale, groups)
        if fault == "softmax":
            s, i, g = moe_ops._topk_gates(logits, k)
            return s, i, g * scale
        return orig(logits, k, bias, 1.0, groups)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe_ops, "_sigmoid_gates", wrong)
        m = _build()
        with HI:
            got = np.asarray(m(tensor.from_numpy(
                ids[None].astype(np.int32))).data)[0]
    assert np.abs(got - base).max() > 1e-3


def _engine(m, paged, **kw):
    kw = dict(dict(num_slots=3, max_len=640, block_size=BS), **kw)
    eng = ServeEngine(m, **kw)
    if paged:
        # both programs trace here
        with _as_on_a_tpu():
            assert kv_ops.reads_blocks(eng.pool.caches[0][0])
            eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
            eng.run_until_idle()
    return eng


@pytest.mark.parametrize("case", ["view", "kernel", "prefill-kernel"])
def test_engine_matches_the_reference(glm, case):
    """A shared 272-token document and private questions: the first
    prompt prefills in two chunks, the next ones hit the document's
    blocks and prefill their questions; decode reads the arena in place
    through the latent kernel (or a gathered view), and steered as on a
    TPU a chunk attends its view through the prefill kernel, whether
    the view is whole KV tiles (1,024 rows) or ends in a part tile.
    Every served token is the reference's arg-max of the same sequence,
    the latent blocks the programs left are the reference's c_kv and
    rotated k_rope, and one more decode's logits are the reference's."""
    rng = np.random.default_rng(5)
    doc = rng.integers(0, VOCAB, 272).astype(np.int32)
    prompts = [np.concatenate([doc, rng.integers(0, VOCAB, n)])
               .astype(np.int32) for n in (30, 7, 45)]
    with HI:
        eng = _engine(glm, case != "view", **(
            dict(max_len=1024) if case == "prefill-kernel" else {}))
        hs = [eng.submit(p, max_new_tokens=6) for p in prompts[:2]]
        eng.run_until_idle()
        long = eng.submit(prompts[2], max_new_tokens=40)
        for _ in range(12):
            eng.step()
    snap = eng.metrics.snapshot()
    assert snap["prefix_hit_tokens"] >= 2 * 272 - 2 * BS
    assert eng.compiled_counts() == (1, 1)
    if case == "view":
        assert snap["mla_prefill_kernel_chunks"] == 0 < snap["prefill_chunks"]
    else:
        assert snap["mla_prefill_kernel_chunks"] == snap["prefill_chunks"] > 3
    params = _params(glm)
    for h, p in zip(hs, prompts):
        seq = np.concatenate([p, h.tokens])
        logits = _ref_logits(params, seq)[0]
        np.testing.assert_array_equal(
            logits[len(p) - 1:-1].argmax(-1), np.asarray(h.tokens))
    slot = next(s for s, r in eng.running_items())
    held = eng.slot_cache(slot)
    n = len(held[0][0])
    seq = np.concatenate([prompts[2], long.tokens])[:n + 1]
    _, _, latents, ropes = _ref_logits(params, seq)
    dr = CFG.qk_rope_head_dim
    for i, (ck, cr) in enumerate(held):
        np.testing.assert_allclose(ck, latents[i][:n], atol=1e-4)
        np.testing.assert_allclose(cr[:, :dr], ropes[i][:n], atol=1e-4)
        assert not cr[:, dr:].any()             # the lanes of the padding
    assert long.finish_reason is None


def test_slots_of_a_document_read_it_once_a_tick(glm):
    """Three requests behind one 272-token document run at once, steered
    as on a TPU with chunks of 8 blocks (so that a 34-block document is
    a run worth a group): the decode kernel reads the document once for
    the group. Each tick's `mla_latent_bytes` is what a plain walk of
    that tick's plan fetches (each group's run once, each running slot's
    own blocks) and `mla_latent_bytes_attended` every running slot's
    live blocks, as the counter was before groups; the plan is made
    again only where an admission or a release came between two ticks;
    and every served token is the reference's arg-max."""
    rng = np.random.default_rng(11)
    doc = rng.integers(0, VOCAB, 272).astype(np.int32)
    prompts = [np.concatenate([doc, rng.integers(0, VOCAB, n)])
               .astype(np.int32) for n in (30, 7, 45)]
    log = []
    with HI, pytest.MonkeyPatch.context() as mp:
        mp.setattr(mla_attention, "_CHUNK_ROWS", 8 * BS)
        # a view of its own: the kernel traces here with these chunks
        eng = _engine(glm, True, max_len=768)
        pool = eng.pool
        assert pool._group_limits == (32, 8)
        for name in ("map_slot", "activate", "release"):
            real = getattr(pool, name)
            mp.setattr(pool, name, lambda *a, _r=real, _n=name: (
                log.append((_n,)), _r(*a))[1])
        counted = eng.metrics.on_mla_decode
        mp.setattr(eng.metrics, "on_mla_decode", lambda f, a: (
            log.append(("tick", pool.group_plan(), pool.pos.copy(),
                        pool.active.copy(), f, a)), counted(f, a))[1])
        before = eng.metrics.snapshot()
        hs = [eng.submit(p, max_new_tokens=14) for p in prompts]
        eng.run_until_idle()
    snap = eng.metrics.snapshot()
    ticks = [e for e in log if e[0] == "tick"]
    row = BS * pool.latent_row_bytes
    fetched = attended = 0
    for _, plan, pos, active, f, a in ticks:
        walk = 0
        for s in np.flatnonzero(active):
            leader, run = plan[:, s]
            if leader == s:
                walk += run                       # the group's shared pass
            walk += pos[s] // BS + 1 - run        # the slot's own pass
        assert f == walk * row
        assert a == sum(pos[active] // BS + 1) * row
        fetched, attended = fetched + f, attended + a
    assert snap["mla_latent_bytes"] - before["mla_latent_bytes"] == fetched
    assert snap["mla_latent_bytes_attended"] \
        - before["mla_latent_bytes_attended"] == attended
    # the three read the document's 34 blocks through one group
    assert max(int((t[1][1] > 0).sum()) for t in ticks) == 3
    assert fetched < attended
    changed = False
    for e in log:
        if e[0] != "tick":
            changed = True
            continue
        if not changed:
            assert e[1] is last
        last, changed = e[1], False
    params = _params(glm)
    for h, p in zip(hs, prompts):
        seq = np.concatenate([p, h.tokens])
        logits = _ref_logits(params, seq)[0]
        np.testing.assert_array_equal(
            logits[len(p) - 1:-1].argmax(-1), np.asarray(h.tokens))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_at_ragged_lengths(dtype):
    """Lengths on, under and over block edges and across chunks, tables
    in shuffled order: the kernel against softmax over the gathered
    rows."""
    rng = np.random.default_rng(6)
    S, H, r, dr, bs, mb = 6, 5, 128, 128, 8, 160
    N = S * mb + 1
    f = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    lat, rop, ql, qr = f(N, bs, r), f(N, bs, dr), f(S, H, r), f(S, H, dr)
    tables = jnp.asarray(rng.permutation(np.arange(1, N)).reshape(S, mb),
                         jnp.int32)
    lengths = np.asarray([1, 7, 8, 9, 1033, mb * bs], np.int32)
    got = mla_decode_attention(ql, qr, lat, rop, tables, lengths, scale=0.1,
                               interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for s, n in enumerate(lengths):
        L = np.asarray(lat[tables[s]], np.float32).reshape(-1, r)[:n]
        R = np.asarray(rop[tables[s]], np.float32).reshape(-1, dr)[:n]
        q1, q2 = np.asarray(ql[s], np.float32), np.asarray(qr[s], np.float32)
        sc = (q1 @ L.T + q2 @ R.T) * 0.1
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ L
        np.testing.assert_allclose(np.asarray(got[s], np.float32), want,
                                   atol=tol, rtol=tol)



def _softmax_rows(ql, qr, lat, rop, tables, lengths, scale):
    """Each slot's absorbed attention over its gathered rows, f32 numpy."""
    r, dr = lat.shape[-1], rop.shape[-1]
    out = []
    for s, n in enumerate(lengths):
        L = np.asarray(lat[tables[s]], np.float32).reshape(-1, r)[:n]
        R = np.asarray(rop[tables[s]], np.float32).reshape(-1, dr)[:n]
        sc = (np.asarray(ql[s], np.float32) @ L.T
              + np.asarray(qr[s], np.float32) @ R.T) * scale
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out.append((p / p.sum(-1, keepdims=True)) @ L)
    return np.stack(out)


#: ten slots of 32 heads (groups of at most four): six share a
#: 150-block run (a chunk of 128 blocks and 22), the third's row parts
#: from the others' at block 140 and the second ends on the run's edge;
#: the eighth and tenth share six blocks; the seventh reads alone; the
#: ninth does not run (length 1 over the null block)
_LENGTHS = np.asarray([1203, 1200, 1190, 1270, 1280, 1201, 33, 700, 1, 400],
                      np.int32)


def _shared_rows(rng, S=10, mb=160):
    rows = rng.permutation(np.arange(1, S * mb + 1)).reshape(S, mb)
    rows[1:6, :150] = rows[0, :150]
    rows[2, 140:150] = rows[6, 140:150]
    rows[9, :6] = rows[7, :6]
    rows[8] = 0
    return rows


def test_the_plan_groups_slots_by_their_shared_runs():
    """Six sharers split into a group of four and one of two, a member
    reads only the run its row shares with the leader's, a run counts
    whole blocks below both positions, and the plan is the same function
    of the same tables on the host and in a program."""
    rows = _shared_rows(np.random.default_rng(6))
    full = np.where(_LENGTHS > 1, (_LENGTHS - 1) // 8, 0)
    plan = group_plan(rows, full, max_group=4, min_blocks=4)
    np.testing.assert_array_equal(plan, [
        [0, 0, 0, 0, 4, 4, 6, 7, 8, 7],
        [150, 149, 140, 150, 150, 150, 0, 6, 0, 6]])
    assert plan.dtype == np.int32
    on_device = jax.jit(lambda t, f: group_plan(
        t, f, max_group=4, min_blocks=4, xp=jnp))(rows, full)
    np.testing.assert_array_equal(np.asarray(on_device), plan)
    # a run under the threshold reads alone; one group cap of 2 keeps
    # three groups of two
    np.testing.assert_array_equal(
        group_plan(rows, full, max_group=4, min_blocks=8)[1],
        [150, 149, 140, 150, 150, 150, 0, 0, 0, 0])
    np.testing.assert_array_equal(
        group_plan(rows, full, max_group=2, min_blocks=8),
        [[0, 0, 2, 2, 4, 4, 6, 7, 8, 9],
         [149, 149, 140, 140, 150, 150, 0, 0, 0, 0]])
    live = np.where(_LENGTHS > 1, full + 1, 0)
    # each group's run once, then each slot's own blocks
    assert blocks_fetched(plan, live) == (
        150 + 150 + 6 + sum(live) - sum(plan[1]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_kernel_against_softmax_over_each_slots_rows(dtype):
    """The two passes (interpret mode) against softmax over each slot's
    gathered rows: groups of four and of two out of six sharers, a
    member whose run is shorter than its leader's, runs of 150 and 6
    blocks (not whole chunks of 128), a slot alone, a slot that does not
    run, a plan made in the program, and a member whose length ends on
    its run's edge (nothing of its own to walk)."""
    rng = np.random.default_rng(7)
    S, H, r, dr, bs, mb = 10, 32, 128, 128, 8, 160
    f = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    lat, rop = f(S * mb + 1, bs, r), f(S * mb + 1, bs, dr)
    ql, qr = f(S, H, r), f(S, H, dr)
    rows = _shared_rows(rng)
    full = np.where(_LENGTHS > 1, (_LENGTHS - 1) // bs, 0)
    plan = group_plan(rows, full, max_group=4, min_blocks=4)
    edge = plan.copy()
    edge[1, 1] = 150                      # 150 blocks = its 1,200 rows
    want = _softmax_rows(ql, qr, lat, rop, rows, _LENGTHS, 0.1)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for p in (plan, edge, None):
        got = mla_decode_attention(ql, qr, lat, rop, jnp.asarray(rows),
                                   _LENGTHS, p, scale=0.1, interpret=True)
        assert got.shape == (S, H, r) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   atol=tol, rtol=tol)


def _masked_softmax(ql, qr, view, rope_view, limit, scale):
    """Absorbed attention of rows (B, T, H, .) over a dense view under
    per-row limits, in f32 numpy: softmax over the rows each sees."""
    B, T, H, r = ql.shape
    ql, qr, view, rope_view = (np.asarray(a, np.float32)
                               for a in (ql, qr, view, rope_view))
    rope_view = rope_view[..., :qr.shape[-1]]
    out = np.zeros((B, T, H, r), np.float32)
    for b in range(B):
        for t in range(T):
            n = limit[b] - T + t + 1
            sc = (ql[b, t] @ view[b, :n].T
                  + qr[b, t] @ rope_view[b, :n].T) * scale       # (H, n)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[b, t] = (p / p.sum(-1, keepdims=True)) @ view[b, :n]
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,limits,length", [
    (7, [7], 1024),             # a chunk from position 0: limit = T
    (9, [511], 1024),           # a limit under a KV tile's edge
    (9, [512], 1024),           # on it
    (9, [513], 1024),           # over it
    (5, [1536], 1536),          # the view's whole length
    (1, [700], 1024),           # one row, as a dense cache's decode
    (256, [900], 1024),         # a query tile's worth (256 x 4 heads)
    (300, [1024], 1024),        # two query tiles, the second padded
    (3, [9, 1536], 1536),       # two batch rows, a limit each
    (9, [600], 640),            # a view of a tile and a part tile
    (40, [640], 640),           # to its end, the part tile padded
    (5, [300, 1100], 1100),     # two batch rows over two part tiles
], ids=["from-0", "under-edge", "on-edge", "over-edge", "view-end",
        "one-row", "tile", "two-tiles", "batch", "part-tile",
        "part-tile-end", "part-tile-batch"])
def test_prefill_kernel_against_masked_softmax(dtype, rows, limits, length):
    """The prefill kernel (interpret mode) against masked softmax over
    the view, rope lanes zero-padded as the cache holds them."""
    rng = np.random.default_rng(rows + length)
    B, H, r, dr = len(limits), 4, 128, 64
    f = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    ql, qr = f(B, rows, H, r), f(B, rows, H, dr)
    view = f(B, length, r)
    rope_view = f(B, length, 128).at[..., dr:].set(0)
    limit = np.asarray(limits, np.int32)
    got = mla_prefill_attention(
        ql, qr, view, rope_view, jnp.asarray(limit) if B > 1 else
        jnp.int32(limit[0]), scale=0.1, interpret=True)
    assert got.shape == (B, rows, H, r) and got.dtype == dtype
    want = _masked_softmax(ql, qr, view, rope_view, limit, 0.1)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol,
                               rtol=tol)


def test_the_prefill_kernel_is_chosen_by_the_view():
    """bf16 or f32, whole 128-lane latent rows, a TPU, any length (the
    kernel pads the last KV tile): anything else keeps masked softmax
    over the view."""
    takes = lambda shape, dt: kv_ops.attends_view(jnp.zeros(shape, dt))
    assert not takes((1, 1024, 128), jnp.float32)          # the CPU
    with _as_on_a_tpu():
        assert takes((1, 1024, 128), jnp.float32)
        assert takes((2, 18432, 512), jnp.bfloat16)
        assert takes((1, 640, 128), jnp.float32)           # a part tile
        assert takes((1, 12544, 512), jnp.bfloat16)
        assert not takes((1, 1024, 96), jnp.float32)       # lanes
        assert not takes((1, 1024, 128), jnp.float16)      # dtype


def _pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr and of those inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


def test_the_prefill_kernel_names_the_view(glm):
    """What `benchmark/mla_count.py` finds a chunk's attention by: in a
    steered prefill program every layer's kernel call takes an operand
    with the engine's `max_len` axis, and none is named as the decode
    kernel (whose seconds that count leaves out)."""
    eng = ServeEngine(glm, num_slots=2, max_len=1024, block_size=BS)
    zero = np.int32(0)
    with _as_on_a_tpu():
        jaxpr = jax.make_jaxpr(eng._prefill)(
            eng._params, eng._buffers, np.zeros((1, eng._chunk), np.int32),
            zero, np.int32(eng._chunk - 1), zero, zero, eng.pool.tables,
            eng._toks, eng.pool.caches, None, None, None, None)
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert len(calls) == CFG.num_layers
    for eqn in calls:
        assert "mla_decode_attention" not in eqn.params["name"]
        assert eqn.params["name"] == "mla_prefill_attention"
        assert any(1024 in v.aval.shape for v in eqn.invars)


def test_pool_holds_latent_blocks(glm):
    pool = BlockPool(glm, num_slots=2, max_len=64, block_size=BS)
    for ck, cr in pool.caches:
        assert ck.shape == (pool.num_blocks, BS, CFG.kv_lora_rank)
        assert cr.shape == (pool.num_blocks, BS, CFG.rope_lanes)
    assert pool.block_bytes == CFG.num_layers * BS * 4 * (
        CFG.kv_lora_rank + CFG.rope_lanes)
    assert pool.slot_state is None and pool.tails is None
    assert not kv_ops.reads_blocks(pool.caches[0][0])   # not a TPU
    with _as_on_a_tpu():
        assert kv_ops.reads_blocks(pool.caches[0][0])
        assert not kv_ops.reads_blocks(
            jnp.zeros((3, 8, 96), jnp.float32))           # lanes
        assert not kv_ops.reads_blocks(
            jnp.zeros((3, 8, 128), jnp.bfloat16))         # sublanes


def test_int8_arena_is_refused(glm):
    with pytest.raises(NotImplementedError, match="latent"):
        ServeEngine(glm, num_slots=2, max_len=64, block_size=BS,
                    kv_dtype="int8")


def test_spill_and_speculative_decoding_carry_latent_blocks(glm):
    """The spill tier moves a latent block's bytes as it moves keys and
    values (a document's blocks evicted by two others, spilled, and
    restored by its next question), and a speculative engine (the model
    as its own draft) serves the plain engine's stream."""
    rng = np.random.default_rng(7)
    docs = [rng.integers(0, VOCAB, 40).astype(np.int32) for _ in range(3)]
    prompts = [np.concatenate([docs[d], rng.integers(0, VOCAB, 5)])
               .astype(np.int32) for d in (0, 1, 2, 0)]
    streams = []
    with HI:
        for kw in ({}, dict(spill_blocks=16),
                   dict(draft_model=glm, spec_k=2)):
            eng = ServeEngine(glm, num_slots=1, max_len=64, block_size=BS,
                              num_blocks=13, **kw)
            out = []
            for p in prompts:
                h = eng.submit(p, max_new_tokens=8)
                eng.run_until_idle()
                out.append(list(h.tokens))
            streams.append(out)
            snap = eng.metrics.snapshot()
            if "spill_blocks" in kw:
                assert snap["spilled_blocks"] > 0 and snap["prefetch_hits"] > 0
            if "draft_model" in kw:
                assert eng.spec_compiled_counts()[2] == 1
    assert streams[0] == streams[1] == streams[2]


def test_the_scopes_are_in_the_lowered_programs(glm):
    eng = ServeEngine(glm, num_slots=2, max_len=64, block_size=BS)
    with _as_on_a_tpu():
        text = {k: v.as_text(debug_info=True)
                for k, v in eng.lower_programs(
                    ["prefill_chunk", "decode"]).items()}
    for name in ("attn.mla.q", "attn.mla.kv_a", "attn.mla.prefill",
                 "attn.mla.out", "moe.route",
                 "moe.experts", "moe.shared", "mlp.dense"):
        assert name in text["prefill_chunk"], name
    for name in ("attn.mla.decode", "attn.mla.absorb", "moe.route"):
        assert name in text["decode"], name
    assert "mla_decode_attention" in text["decode"]


def test_rope_tables_are_float64_rounded_once():
    """The model's RoPE tables and the reference's rotation are the
    float64 angles and cosines rounded once to f32, up to an 18k
    context (built in f32 on a TPU a cosine is off by up to 0.018 at 16k
    positions: PERF.md §6, PR 39)."""
    from singa_tpu.models import glm_mla
    dim, n, theta = 64, 18432, 1e6
    cos, sin = glm_mla._rope_tables(dim, n, theta)
    ang = np.arange(n)[:, None] / theta ** (np.arange(0, dim, 2) / dim)
    np.testing.assert_array_equal(np.asarray(cos),
                                  np.cos(ang).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(sin),
                                  np.sin(ang).astype(np.float32))
    # the reference rotates (1, 0) pairs onto (cos, sin)
    x = jnp.concatenate([jnp.ones((n, 1, dim // 2)),
                         jnp.zeros((n, 1, dim // 2))], -1)
    got = np.asarray(ref.rope(x, np.arange(n), dim, theta))[:, 0]
    np.testing.assert_array_equal(got[:, :dim // 2], np.asarray(cos))
    np.testing.assert_array_equal(got[:, dim // 2:], np.asarray(sin))


def test_training_is_refused(glm):
    with pytest.raises(NotImplementedError):
        glm.train_one_batch(None, None)


def test_the_two_reference_files_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "reference_glm_mla.py")) as a, \
            open(os.path.join(here, "..", "benchmark",
                              "reference_glm_mla.py")) as b:
        assert a.read() == b.read()
