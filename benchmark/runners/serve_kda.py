"""Serve runner for a configuration whose token mixers are Kimi delta
attention (KDA: a delta rule with a per-channel decay and an f32 state a
slot) in five layers of six and latent attention (MLA) with a head-wise
gate in the sixth, over two dense layers and then a held share of
group-routed experts beside a shared expert, with an untied head: the
cell's traffic through `ServeEngine`, as `runners/serve.py` drives it,
with the model built from the source's own keys and `correct` decided by
`reference_ling.py` (the delta rule token by token, expanded attention,
f32, no cache) under four rules:

1. logits: the arrays the engine's programs read (`ServeEngine.weights()`)
   through the program's cached forward in f32, one chunk of
   `score_rows` rows after another with every KDA layer's state and
   window and the MLA layer's latent cache carried between them (the
   chunked form entering from the state the chunk before left), against
   the reference on the masters rounded to bf16, for ended requests of
   the window;
2. served tokens: the gap between the reference's best logit and its
   logit of the token the timed path served, where no expert layer's
   choice (the eighth of score + bias against the ninth, the fourth
   group against the fifth) is a near tie;
3. what the timed programs left for requests still running when the
   window closed (a snapshot hit, chunks of the chunked form, hundreds
   of ticks of the decode kernel): the first KDA layer's state S, as a
   whole and head by head, and its convolution window; the deeper KDA
   layers' S; the MLA layer's latent rows, against the reference's
   after the same tokens;
4. decode: one more tick of the decode path over the live arena and
   state (the decode program's closure, `decode_step` as the engine
   wraps it: the KDA kernel updating every slot's state in place, the
   latent kernel reading each slot's blocks through its table) returns
   its logits, held to the reference's at that position; then, on the
   same arena and state restored, the engine's own step dispatches its
   compiled decode program, and the token it serves each running slot
   must be the closure's arg-max wherever that is no near tie.

The `LlamaConfig` that `run.py` builds for every cell is ignored here.
A program without `models.Ling` cannot run the configuration: that is
a non-zero exit at once, before any weight is made.
"""

from __future__ import annotations

import time

import numpy as np


def ling_config(cfg: dict, models, max_len: int, chunk_rows: int = 256):
    """The program's LingConfig for the source's keys in `cfg`.  Its
    RoPE tables reach one prefill chunk (`chunk_rows`) past `max_len`:
    a chunk of a model whose state lives in snapshots may run that far
    with pad rows."""
    if not hasattr(models, "LingConfig"):
        raise SystemExit(
            "benchmark: this program has no models.Ling: it cannot build the "
            "configuration (Kimi delta attention with its f32 state beside "
            "latent attention over a paged latent cache, group-routed "
            "experts with a correction bias, a shared expert)")
    dep = cfg["deployment"]
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or cfg["score_function"] != "sigmoid" \
            or not cfg["moe_router_enable_expert_bias"] \
            or cfg["scale_router_input"] or not cfg["norm_topk_prob"] \
            or cfg["q_lora_rank"] is not None or not cfg["use_qk_norm"] \
            or cfg["use_mla_nope"] or cfg["use_nGPT"] or cfg["up_proj_norm"] \
            or cfg["value_norm"] \
            or cfg["gated_attention_proj_granularity_type"] != "head_wise" \
            or not cfg["linear_silu"] or not cfg["kda_safe_gate"] \
            or not cfg["no_kda_lora"] or cfg["use_kda_lora"] \
            or cfg["group_norm_size"] != 1 \
            or cfg["num_kv_heads_for_linear_attn"] != 0 \
            or cfg["rotary_dim"] != cfg["qk_rope_head_dim"] \
            or any(cfg["expert_swiglu_limit_list"][:cfg["num_hidden_layers"]]) \
            or any(cfg["share_expert_swiglu_limit_list"][
                :cfg["num_hidden_layers"]]) \
            or cfg["sliding_window"] is not None:
        raise SystemExit(
            "benchmark: the program builds KDA with the lower-bounded gate "
            "and full-rank projections, MLA without query compression with "
            "a head-wise gate and full rotary dims, sigmoid routing with a "
            "correction bias and renormalised gates on unscaled input, "
            "silu-gated experts without limits, no window, an untied head")
    if len(dep["experts_held"]) != cfg["num_experts"]:
        raise SystemExit("benchmark: deployment.experts_held does not name "
                         "num_experts experts")
    if cfg["v_head_dim"] != cfg["head_dim"] \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise SystemExit("benchmark: the program's KDA heads are "
                         "num_attention_heads of head_dim = v_head_dim")
    return models.LingConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        layer_group_size=cfg["layer_group_size"],
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        conv_size=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        kda_chunk=cfg.get("kda_chunk", 64),
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"], first_dense=cfg["first_k_dense_replace"],
        dense_ffn_dim=cfg["intermediate_size"],
        num_experts=dep["num_experts"],
        experts_held=tuple(dep["experts_held"]),
        ffn_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["moe_shared_expert_intermediate_size"],
        moe_top_k=cfg["num_experts_per_tok"],
        groups=(cfg["n_group"], cfg["topk_group"]),
        route_scale=cfg["routed_scaling_factor"],
        max_position=max_len + chunk_rows,
        latent_norm_init=tuple(cfg["latent_norm_init"]))


def reference_keys(cfg: dict) -> dict:
    """`cfg` under the keys `reference_ling.py` reads: the routed experts
    are the deployment's."""
    return {**cfg, "num_experts": cfg["deployment"]["num_experts"]}


def engine_scorer(m, weights, e: dict, rows: int, stride: int):
    """`score(seq)` -> (len(seq), vocab / stride) f32: the logits of
    every position of `seq`, every `stride`-th column, from `weights` =
    `eng.weights()` (the bf16 cast) through `resume_step`, the closure
    `prefill_chunk` wraps: one chunk of `rows` rows at a traced offset
    after another, the MLA layer over a dense latent cache, every KDA
    layer's state and window handed from chunk to chunk as the model
    returns them.  The weights are widened to f32, activations are f32
    and every matmul runs at "highest", so what separates the result
    from the reference's is what the served weights lost beyond the
    stated precision, or an equation the cached forward has wrong (the
    chunked form against the reference's recurrence among them)."""
    import copy

    import jax
    import jax.numpy as jnp
    from singa_tpu.model import model_device
    from singa_tpu.models._generate import resume_step

    exact = copy.copy(model_device(m))
    exact.default_dtype = np.float32
    resume = resume_step(m, device=exact)

    def chunk_logits(params, buffers, ids, pos, caches):
        wide = {n: a.astype(jnp.float32)
                if jnp.issubdtype(a.dtype, jnp.floating) else a
                for n, a in params.items()}
        with jax.default_matmul_precision("highest"):
            logits, caches = resume(wide, buffers, ids, pos, caches)
        return logits[0, :, ::stride].astype(jnp.float32), caches

    chunk_logits = jax.jit(chunk_logits, donate_argnums=(4,))

    def score(seq) -> np.ndarray:
        caches = jax.tree.map(lambda a: a.astype(jnp.float32),
                              m.init_caches(1, e["max_len"]))
        ids = np.zeros((-(-len(seq) // rows) * rows,), np.int32)
        ids[:len(seq)] = seq
        out = []
        for start in range(0, ids.size, rows):
            lg, caches = chunk_logits(
                *weights, jnp.asarray(ids[None, start:start + rows]),
                jnp.asarray(start, jnp.int32), caches)
            out.append(lg)
        return np.asarray(jnp.concatenate(out))[:len(seq)]

    return score


def decode_logits(eng, m, stride: int):
    """`logits(toks)` -> ((slots, vocab / stride) f32, (slots,) arg-max,
    (slots,) margin of the best logit over the second): one tick of the
    decode path over the live arena and state: `decode_paged`'s closure
    (`decode_step`), the latent pools handed over as
    `ops.kv_cache.PagedKV` where the engine's decode reads them in place
    and as a gathered view where it does not, each KDA layer's state as
    the engine holds it (updated in place by the kernel on a TPU), every
    slot at its position.  Pools and state are donated, as the engine's
    own tick donates them, and what they held before is put back from a
    host copy, so that the engine's next step runs the same tick."""
    import jax
    import jax.numpy as jnp
    from singa_tpu.models._generate import decode_step
    from singa_tpu.ops import kv_cache as kv_ops
    from singa_tpu.serve import spec as spec_mod

    dec, bs = decode_step(m), eng.pool.block_size

    def run(params, buffers, toks, pos, active, tables, caches, state):
        posc = jnp.where(active, pos, 0)
        wb = jnp.where(active, jnp.take_along_axis(
            tables, (posc // bs)[:, None], axis=1)[:, 0], 0)
        off = jnp.where(active, posc % bs, 0)
        paged = kv_ops.reads_blocks(
            next(ck for ck, _ in caches if ck is not None))
        if paged:
            rows = jnp.where(active[:, None], tables, 0)
            dense = [(kv_ops.PagedKV(ck, rows, wb, off),
                      kv_ops.PagedKV(cv, rows, wb, off))
                     if ck is not None else (None, None)
                     for ck, cv in caches]
        else:
            dense = [spec_mod.gather_view(ck, cv, tables)
                     for ck, cv in caches]
        dense = [kv + tuple(st) for kv, st in zip(dense, state)]
        logits, dense = dec(params, buffers, toks[:, None], posc, dense)
        if paged:
            caches = [(d[0].pool, d[1].pool) if d[0] is not None
                      else (None, None) for d in dense]
        lf = logits.astype(jnp.float32)
        top = jax.lax.top_k(lf, 2)[0]
        return (lf[:, ::stride], jnp.argmax(lf, axis=-1).astype(jnp.int32),
                top[:, 0] - top[:, 1], caches, [tuple(d[2:]) for d in dense])

    run = jax.jit(run, donate_argnums=(6, 7))
    params, buffers = eng.weights()

    def logits(toks):
        pool = eng.pool
        before = jax.device_get((pool.caches, pool.slot_state))
        out = run(params, buffers, toks, pool.pos, pool.active,
                  pool.tables.copy(), pool.caches, pool.slot_state)
        got = [np.asarray(a) for a in out[:3]]
        del out
        pool.caches, pool.slot_state = jax.device_put(before)
        return got

    return logits


def state_errors(held, found, mla_layers, dr: int):
    """Rule 3 for one request.  `held` is `eng.slot_cache()`'s per-layer
    `(k, v, *state)`, `found` the reference's `greedy_gap` of the same
    tokens.  Returns (per KDA layer |S - reference's| / |reference's|
    over the layer's whole state; the same of its window; of the FIRST
    KDA layer's S head by head, each head's error over that head's own
    norm: (median, largest) over the heads; per MLA layer the median
    over the positions of that ratio for c_kv and for the rotated
    k_rope's first `dr` lanes; the largest value in the padding lanes,
    which must be 0)."""
    rel = lambda a, b: float(np.linalg.norm(a.astype(np.float32) - b)
                             / np.linalg.norm(b))
    rows = lambda a, b: np.linalg.norm(
        (a.astype(np.float32) - b).reshape(len(a), -1), axis=-1) \
        / np.linalg.norm(b.reshape(len(b), -1), axis=-1)
    states, windows, latents, heads, pad = [], [], [], None, 0.0
    ik = il = 0
    for i, (k, v, *state) in enumerate(held):
        if i in mla_layers:
            n = k.shape[0]
            latents.append(
                (float(np.median(rows(k, found["latents"][il][:n]))),
                 float(np.median(rows(v[:, :dr], found["ropes"][il][:n])))))
            pad = max(pad, float(np.abs(v[:, dr:].astype(np.float32)).max(
                initial=0.0)))
            il += 1
            continue
        S, window = state
        if heads is None:
            by_head = rows(S, found["states"][ik])
            heads = (float(np.median(by_head)), float(by_head.max()))
        states.append(rel(S, found["states"][ik]))
        windows.append(rel(window, found["windows"][ik]))
        ik += 1
    return states, windows, heads, latents, pad


def stop_trace(ctx, eng, kda_count, trace_reduce):
    """`ctx.tracer.stop()`, and the device seconds under the KDA scopes,
    read from the raw profile before it is reduced (the reduction keeps
    an op's name, not its program) and joined with the scopes in the
    engine's two compiled programs' text: ({}, {}) where no trace ran."""
    if not ctx.tracer.running:
        return ctx.tracer.stop(ctx.dump_trace), {}
    scoped, load = {}, trace_reduce.load

    def keep(path):
        profile = load(path)
        programs = eng.lower_programs(["prefill_chunk", "decode"])
        scoped.update(kda_count.scope_seconds(profile, {
            "jit_prefill_chunk": kda_count.op_scopes(
                programs["prefill_chunk"].compile().as_text()),
            "jit_decode_paged": kda_count.op_scopes(
                programs["decode"].compile().as_text())}))
        return profile

    trace_reduce.load = keep
    try:
        trace = ctx.tracer.stop(ctx.dump_trace)
    finally:
        trace_reduce.load = load
    return trace, scoped


def build(ctx):
    """The model of the configuration, its weights made from the seed."""
    from singa_tpu import models, tensor

    cfg = ctx.config
    lcfg = ling_config(cfg, models, cfg["engine"]["max_len"])
    tensor.set_seed(ctx.seed)
    m = models.Ling(lcfg)
    m.eval()
    m.compile([tensor.from_numpy(np.zeros((1, cfg["init_len"]), np.int32))],
              is_train=False, use_graph=True)
    ctx.stamp(f"weights made (jit-init): {m.num_params():,} parameters")
    return m


def run(ctx) -> dict:
    return serve_and_check(ctx, build(ctx))


def serve_and_check(ctx, m) -> dict:
    """The cell's traffic through a `ServeEngine` over `m`, then the
    four rules.  `m`'s weights are left rounded to the served precision,
    which is what the engine serves either way."""
    import jax
    import jax.numpy as jnp

    import kda_count
    import reference_ling as reference
    import trace_reduce
    import traffic
    import yardstick
    from singa_tpu import serve

    cfg, cell = ctx.config, ctx.cell
    e = cfg["engine"]
    eng = serve.ServeEngine(m, num_slots=e["num_slots"], max_len=e["max_len"],
                            block_size=e["block_size"],
                            param_dtype=jnp.dtype(e["param_dtype"]))
    ctx.stamp(f"engine built: {eng.pool.slot_state_bytes:,} B of state a "
              f"slot, {eng.pool.snapshot_entries} snapshot entries, "
              f"{eng.pool.num_blocks:,} latent blocks of "
              f"{eng.pool.block_bytes:,} B")
    t = cell["traffic"]
    streams = [traffic.client_stream(t, cfg["vocab_size"], ctx.seed, c)
               for c in range(t["clients"])]
    loop = ctx.load_module("loops", cell["loop"])
    reqs, w0, w1, active, step_ends, snap0 = loop.drive(
        eng, streams, ctx.seconds, t["warmup_rounds"], ctx.tracer.tick)
    ctx.stamp(f"window closed; it opened at +{w0 - ctx.t0:.1f} s")
    trace, scoped = stop_trace(ctx, eng, kda_count, trace_reduce)
    snap1 = eng.metrics.snapshot()
    if eng.compiled_counts() != (1, 1):
        raise SystemExit(f"benchmark: the engine compiled "
                         f"{eng.compiled_counts()} programs, not (1, 1)")
    window_s = w1 - w0
    delta = lambda key: snap1.get(key, 0) - snap0.get(key, 0)
    slot_state_bytes, chunk_rows = eng.pool.slot_state_bytes, eng._chunk

    inside = lambda ts: w0 < ts <= w1
    tokens = sum(inside(s) for r in reqs for s in r.stamps)
    ttft = [(r.stamps[0] - r.submit) * 1e3 for r in reqs
            if r.stamps and inside(r.stamps[0])]
    itl = [(b - a) * 1e3 for r in reqs
           for a, b in zip(r.stamps, r.stamps[1:]) if inside(b)]
    ended = [r for r in reqs if r.done_at is not None and inside(r.done_at)]
    bad = [r for r in ended
           if r.handle.failed or r.handle.finish_reason != "length"]
    rejected = delta("rejected")
    prompt_tokens = sum(r.prompt_len for r in reqs
                        if r.stamps and inside(r.stamps[0]))
    ticks = delta("decode_ticks")
    print(f"[serve] window {window_s:.3f} s: {len(ended)} requests ended "
          f"({len(bad)} badly, {rejected} rejected), {tokens} tokens, "
          f"{delta('steps')} engine steps, {ticks} decode ticks, mean active "
          f"{np.mean(active):.2f}/{e['num_slots']}, prefix hits "
          f"{delta('prefix_hit_tokens')}/{prompt_tokens} prompt tokens, "
          f"{delta('state_snapshot_hits')} admissions entered from a state "
          f"snapshot and {delta('state_snapshot_misses')} from zeros, "
          f"{delta('state_snapshot_writes')} snapshots written, "
          f"{delta('state_snapshot_evictions')} evicted, "
          f"{delta('prefix_tokens_recomputed')} shared rows prefilled again, "
          f"{delta('ssm_state_bytes'):,} B of state and windows moved by "
          f"the ticks, "
          f"{delta('mla_latent_bytes'):,} B of latent read by the ticks, "
          f"{delta('prefill_chunk_rows')} rows in {delta('prefill_chunks')} "
          f"prefill chunks, {delta('moe_assignments')} expert assignments; "
          f"TTFT n={len(ttft)} ITL n={len(itl)}", flush=True)

    # correctness, outside the window.  First what only the live engine
    # holds: the state and latent rows of requests still running (rule
    # 3), then one more tick of the decode path over them (rule 4)
    chk = cfg["check"]
    stride, dr = chk["logit_stride"], cfg["qk_rope_head_dim"]
    src = reference_keys(cfg)
    mla_layers = {i for i in range(cfg["num_hidden_layers"])
                  if reference.is_mla(src, i)}
    running = sorted(eng.running_items(),
                     key=lambda sr: -int(eng.pool.pos[sr[0]]))
    # half of them the longest contexts (a snapshot hit, chunks, many
    # ticks), half the shortest (the hit and a chunk boundary a few
    # hundred tokens back, which the slower channels still hold)
    n_long = (chk["cache_requests"] + 1) // 2
    chosen = running[:n_long] + running[n_long:][
        ::-1][:chk["cache_requests"] - n_long]
    arena = [eng.slot_cache(s) for s, _ in chosen]     # lands the tick
    replay = {s: np.asarray(r.replay_ids()) for s, r in running}
    pos0 = {s: int(eng.pool.pos[s]) for s, _ in running}
    toks = np.zeros((e["num_slots"],), np.int32)
    for s, _ in running:
        toks[s] = replay[s][pos0[s]]
    dec, picked, margin = decode_logits(eng, m, stride)(toks)
    # the same tick by the engine itself: its step dispatches the
    # compiled decode program the window ran, on the arena and state
    # the closure started from; each running slot's served token is
    # that program's arg-max
    eng.step()
    eng.slot_cache(running[0][0])                     # lands the tick
    served = {s: int(np.asarray(r.replay_ids())[pos0[s] + 1])
              for s, r in running}
    differ = [(s, float(margin[s])) for s, _ in running
              if served[s] != int(picked[s])]
    disagree = sum(mg > chk["decode_tie"] for _, mg in differ)
    n_running = len(running)
    held = [(replay[s][:pos0[s] + 1], int(r.prompt.size), a,
             dec[s]) for (s, r), a in zip(chosen, arena)]
    good = [r for r in ended if r not in bad]
    order = sorted(range(len(good)),
                   key=lambda i: -len(good[i].handle.result()))
    pick = order[:1] + [int(i) for i in np.random.default_rng(
        [ctx.seed, 9]).permutation(order[1:])[:chk["requests"] - 1]]
    seqs = [(good[i].handle.result(), good[i].prompt_len) for i in pick]
    weights = eng.weights()
    del eng, loop, streams, running, chosen, arena
    # the masters at the precision the configuration serves them in,
    # rounded by the reference's own code; the model keeps the rounded
    # arrays and its f32 masters go, one array at a time
    params = {}
    for n, p in m.get_params().items():
        params[n] = p.data = jax.block_until_ready(
            reference.rounded({n: p.data}))[n]
    ctx.stamp("engine dropped; the reference's weights rounded")
    score = engine_scorer(m, weights, e, chk["score_rows"], stride)
    got = [score(seq) for seq, _ in seqs]
    ctx.stamp(f"the engine's weights scored {sum(len(g) for g in got)} "
              f"positions")
    del weights, score
    t_ref = time.perf_counter()
    found = [reference.greedy_gap(
        params, seq, plen, chk["pad_to"], src, chk["delta"],
        chk["tolerance"], g, stride) for (seq, plen), g in zip(seqs, got)]
    found_held = []
    for seq, plen, _, row in held:
        last = np.zeros((len(seq), row.size), np.float32)
        last[-1] = row
        found_held.append(reference.greedy_gap(
            params, seq, min(plen, len(seq) - 1), chk["pad_to"], src,
            chk["delta"], chk["tolerance"], last, stride,
            state_after=len(seq) - 1))
    errs = [state_errors(h[2], f, mla_layers, dr)
            for h, f in zip(held, found_held)]
    worst = lambda vals: max(vals, default=np.inf)
    best = lambda vals: min(vals, default=np.inf)
    # the first KDA layer, whose input no attention and no expert has
    # touched, holds the state's precision, the chunk boundaries and the
    # snapshot in every request; the deeper layers carry what bf16
    # activations and routing flips add up to: their request that reads
    # best (a fault of the program is in every request's state)
    state_first = worst([s[0] for s, _, _, _, _ in errs])
    heads_first = worst([h[1] for _, _, h, _, _ in errs])
    window_first = worst([w[0] for _, w, _, _, _ in errs])
    state_deep = best([max(s[1:] + w[1:]) for s, w, _, _, _ in errs])
    latent_err = best([max(x for pair in lat for x in pair)
                       for _, _, _, lat, _ in errs])
    pad = worst([p for _, _, _, _, p in errs])
    decode_err = worst([float(f["err"][-1]) for f in found_held])
    err = np.concatenate([f.pop("err") for f in found])
    logit_err = float(np.median(err))
    both = found + found_held
    total = lambda key: sum(f[key] for f in both)
    checked, unsure, over = total("checked"), total("unsure"), total("over")
    unsure_share = unsure / max(1, checked + unsure)
    over_share = over / max(1, checked)
    r5 = lambda xs: [round(x, 5) for x in xs]
    print(f"[serve] reference check.  Rule 1, on {len(seqs)} ended requests "
          f"of {[len(s) for s, _ in seqs]} tokens: the engine's weights "
          f"widened to f32 through the cached forward in chunks of "
          f"{chk['score_rows']} rows against the reference: |logits - "
          f"reference's| / |reference's| a position over {err.size} "
          f"positions: lower quartile {np.percentile(err, 25):.3g}, median "
          f"{logit_err:.3g}, p99 {np.percentile(err, 99):.3g}, largest "
          f"{err.max():.3g}; the median against the limit "
          f"{chk['logit_err_limit']}.  Rule 3, on {len(held)} requests "
          f"still running at {[len(s) - 1 for s, _, _, _ in held]} cached "
          f"positions (prompts of {[p for _, p, _, _ in held]}): |engine's "
          f"- reference's| / |reference's| per request: each KDA layer's S "
          f"{[r5(x[0]) for x in errs]}, its window "
          f"{[r5(x[1]) for x in errs]}, the first layer's S head by head "
          f"(median, largest) {[r5(x[2]) for x in errs]}, the MLA layer's "
          f"(median over positions of c_kv, of k_rope) "
          f"{[[r5(p) for p in x[3]] for x in errs]}; the first layer's S "
          f"{state_first:.3g}, limit {chk['state_err_limit']}, its worst "
          f"head {heads_first:.3g}, limit {chk['state_heads_limit']}, its "
          f"window {window_first:.3g}, limit {chk['window_err_limit']}; the "
          f"deeper KDA layers' largest in the request that reads best "
          f"{state_deep:.3g}, limit {chk['state_deep_limit']}; the latent's "
          f"{latent_err:.3g}, limit {chk['latent_err_limit']}; padding "
          f"lanes {pad}.  Rule 4, one more tick of the decode path over the "
          f"live arena and state: |logits - reference's| / |reference's| "
          f"{r5([float(f['err'][-1]) for f in found_held])}, largest "
          f"{decode_err:.3g}, limit {chk['decode_err_limit']}; the "
          f"engine's own decode program served the closure's arg-max in "
          f"{n_running - len(differ)} of {n_running} running slots "
          f"(differing at margins {[round(mg, 4) for _, mg in differ]}), "
          f"{disagree} past the near-tie margin {chk['decode_tie']}.  "
          f"Rule 2, "
          f"served tokens of both sets: (best logit - served token's logit) "
          f"beyond the tolerance {chk['tolerance']} at {over} of {checked} "
          f"positions ({over_share:.4f}, limit {chk['over_share_limit']}), "
          f"largest {max((f['gap'] for f in both), default=0.0):.5f}; "
          f"{unsure} positions ({unsure_share:.4f} of all, limit "
          f"{chk['unsure_share_limit']}) left out because an expert layer's "
          f"routing margin is under {chk['delta']}: beyond the tolerance at "
          f"{total('over_unsure')} of them, largest "
          f"{max((f['gap_unsure'] for f in both), default=0.0):.5f}; "
          f"{time.perf_counter() - t_ref:.1f} s", flush=True)
    correct = checked > 0 and bool(held) and pad == 0.0 \
        and over_share <= chk["over_share_limit"] \
        and unsure_share <= chk["unsure_share_limit"] \
        and logit_err <= chk["logit_err_limit"] \
        and state_first <= chk["state_err_limit"] \
        and heads_first <= chk["state_heads_limit"] \
        and window_first <= chk["window_err_limit"] \
        and state_deep <= chk["state_deep_limit"] \
        and latent_err <= chk["latent_err_limit"] \
        and decode_err <= chk["decode_err_limit"] and disagree == 0

    end_to_end = {"serve_tokens_per_s": tokens / window_s,
                  "ttft_p95_ms": yardstick.percentile(ttft, 95),
                  "itl_p95_ms": yardstick.percentile(itl, 95),
                  "setup_s": w0 - ctx.t0}
    if not ctx.dry_run:         # no time from a CPU run is ever printed
        steps_ms = np.diff([w0] + step_ends) * 1e3
        print(f"[serve] engine step ms: median {np.median(steps_ms):.1f}, "
              f"five longest {np.sort(steps_ms)[-5:].round(1).tolist()}; "
              f"{ctx.compile_log.between(w0, w1)} programs compiled or "
              f"loaded inside the window", flush=True)
        print(f"[serve] {tokens / window_s:.3f} tokens/s; TTFT median "
              f"{yardstick.percentile(ttft, 50):.1f} ms, "
              f"p95 {yardstick.percentile(ttft, 95):.2f} ms; ITL median "
              f"{yardstick.percentile(itl, 50):.2f} ms, p95 "
              f"{yardstick.percentile(itl, 95):.2f} ms", flush=True)
    return {"correct": correct, "attempted": len(ended) + rejected,
            "failed": len(bad) + rejected, "end_to_end": end_to_end,
            "trace": trace, "kda_scoped_s": scoped, "window_s": window_s,
            "kda_config": cfg, "prompt_tokens": prompt_tokens,
            "decode_ticks": ticks,
            # (running slot, tick) pairs: each moves the slot's state
            # and windows once each way (snapshot()["ssm_state_bytes"])
            "slot_ticks": delta("ssm_state_bytes")
            / (2 * slot_state_bytes),
            "itl_ms": itl, "chunk_rows": chunk_rows,
            "mla_latent_bytes": delta("mla_latent_bytes"),
            "state_snapshot_hits": delta("state_snapshot_hits"),
            "state_snapshot_misses": delta("state_snapshot_misses"),
            "prefix_hit_tokens": delta("prefix_hit_tokens"),
            "prefill_chunks": delta("prefill_chunks"),
            "prefill_chunk_rows": delta("prefill_chunk_rows")}
