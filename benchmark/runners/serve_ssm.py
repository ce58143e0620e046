"""Serve runner for a configuration whose token mixers are Mamba-2
state-space layers with one attention layer (no positional embedding)
among them, over a held share of the experts beside a shared MLP and a
tied head: the cell's traffic through `ServeEngine`, as
`runners/serve.py` drives it, with the model built from the source's own
keys and `correct` decided by `reference_granite_hybrid.py` under three
rules:

1. logits: the arrays the engine's programs read (`ServeEngine.weights()`)
   through the program's cached forward in f32, one chunk of
   `score_rows` rows after another with every layer's state carried
   between them (the chunked scan entering from the state the chunk
   before left), against the reference's row-by-row recurrence on the
   masters rounded to bf16;
2. served tokens: the gap between the reference's best logit and its
   logit of the token the timed path served, where no layer's routing
   (top 10 of 72: the tenth logit against the eleventh) is a near tie;
3. the state: what the timed programs left for the requests still
   running when the window closed, those with the longest contexts (a
   snapshot hit, several chunks, hundreds of ticks): each mamba layer's
   recurrent state S and convolution window per slot, and the one
   attention layer's keys and values in its KV blocks, against the
   reference's after the same tokens.  A state dropped at a chunk
   boundary or a wrong snapshot at a hit hardly moves a token hundreds
   of positions on; this rule reads the state itself.  The first
   layer's, whose input no expert has touched, is held tightest and in
   every request; the deeper layers' in the request that reads best.

The `LlamaConfig` that `run.py` builds for every cell knows none of
this model's keys and is ignored here.  A program without
`models.GraniteHybrid` cannot run the configuration: that is a non-zero
exit at once, before any weight is made.
"""

from __future__ import annotations

import time

import numpy as np


def granite_config(cfg: dict, models):
    """The program's GraniteHybridConfig for the source's keys in `cfg`."""
    if not hasattr(models, "GraniteHybridConfig"):
        raise SystemExit(
            "benchmark: this program has no models.GraniteHybrid: it cannot "
            "build the configuration (Mamba-2 layers with their recurrent "
            "state, a held share of the experts, a shared MLP, a tied head)")
    n, dep = cfg["num_hidden_layers"], cfg["deployment"]
    kinds = tuple(cfg["layer_types"][:n])
    if not set(kinds) <= {"mamba", "attention"} \
            or cfg["position_embedding_type"] != "nope" \
            or cfg["hidden_act"] != "silu" or not cfg["tie_word_embeddings"] \
            or cfg["attention_bias"] or cfg["mamba_proj_bias"] \
            or not cfg["mamba_conv_bias"] or cfg["mamba_n_groups"] != 1 \
            or cfg["normalization_function"] != "rmsnorm" \
            or cfg["sliding_window"] is not None:
        raise SystemExit(
            "benchmark: the program builds `mamba` and `attention` layers "
            "without positional embedding or window, one group of B and C, "
            "a convolution with a bias and projections without, silu-gated "
            "experts, RMSNorm, a tied head")
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
            != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise SystemExit("benchmark: mamba_n_heads x mamba_d_head is not "
                         "mamba_expand x hidden_size")
    if len(dep["experts_held"]) != cfg["num_local_experts"]:
        raise SystemExit("benchmark: deployment.experts_held does not name "
                         "num_local_experts experts")
    return models.GraniteHybridConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], num_layers=n,
        layer_types=kinds, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_size=cfg["hidden_size"] // cfg["num_attention_heads"],
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=float(cfg["logits_scaling"]), eps=cfg["rms_norm_eps"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        num_experts=dep["num_local_experts"],
        experts_held=tuple(dep["experts_held"]),
        ffn_dim=cfg["intermediate_size"],
        shared_dim=cfg["shared_intermediate_size"],
        moe_top_k=cfg["num_experts_per_tok"],
        max_position=cfg["max_position_embeddings"])


def engine_scorer(m, weights, e: dict, rows: int, stride: int):
    """`score(seq)` -> (len(seq), vocab / stride) f32: the logits of
    every position of `seq`, every `stride`-th column, computed from
    `weights` = `eng.weights()`, the arrays the engine's programs read
    (the bf16 cast), through `resume_step`, the closure its
    `prefill_chunk` program wraps: one chunk of `rows` rows at a traced
    offset after another, the attention layer over a dense cache, every
    mamba layer's state and window handed from chunk to chunk as the
    model returns them.  The weights are widened to f32 inside the
    program, the activations are f32 and every matmul runs at
    "highest", so what separates the result from the reference's is
    what the served weights have lost beyond the stated precision, or
    an equation the program's cached forward has wrong (the chunked
    scan against the reference's recurrence among them), and not the
    rounding of bf16 activations.  The timed programs return tokens
    only; the served tokens and the state are compared beside this."""
    import copy

    import jax
    import jax.numpy as jnp
    from singa_tpu.model import model_device
    from singa_tpu.models._generate import resume_step

    # activations take the dtype of the device the ids enter on: the
    # model's own computes in bf16 on a TPU, whatever the weights are
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
        # f32, whatever the weights: the caches must hold what the f32
        # activations give them
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


def state_errors(held, found, kinds):
    """Rule 3 for one request.  `held` is `eng.slot_cache()`'s per-layer
    `(k, v, *state)`, `found` the reference's `greedy_gap` of the same
    tokens.  Returns (per mamba layer |S - reference's| / |reference's|
    over the layer's whole state, the same of the window; per attention
    layer the median over the positions of that ratio for the keys and
    for the values; of the FIRST mamba layer's state head by head, each
    head's error over that head's own norm: (the median, the 90th
    percentile and the largest over the heads).  A head forgets over
    its own horizon, 1 to 1,000 tokens here: the whole state's norm is
    mostly the quick heads' and shows what the last tokens did, the slow
    heads carry what a prefix hit or a chunk boundary hundreds of tokens
    back did, and they are a few of 128: the largest over the heads is
    what rule 3 holds)."""
    rel = lambda a, b: float(np.linalg.norm(a.astype(np.float32) - b)
                             / np.linalg.norm(b))
    rows = lambda a, b: np.linalg.norm(
        (a.astype(np.float32) - b).reshape(len(a), -1), axis=-1) \
        / np.linalg.norm(b.reshape(len(b), -1), axis=-1)
    states, windows, kv, heads, im, ia = [], [], [], None, 0, 0
    for kind, (k, v, *state) in zip(kinds, held):
        if kind == "mamba":
            if heads is None:
                by_head = rows(state[0], found["states"][im])
                heads = (float(np.median(by_head)),
                         float(np.percentile(by_head, 90)),
                         float(by_head.max()))
            states.append(rel(state[0], found["states"][im]))
            windows.append(rel(state[1], found["windows"][im]))
            im += 1
        else:
            n = k.shape[0]
            kv.append((float(np.median(rows(k, found["keys"][ia, :n]))),
                       float(np.median(rows(v, found["values"][ia, :n])))))
            ia += 1
    return states, windows, kv, heads


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import reference_granite_hybrid as reference
    import traffic
    import yardstick
    from singa_tpu import models, serve, tensor

    cfg, cell = ctx.config, ctx.cell
    gcfg = granite_config(cfg, models)
    tensor.set_seed(ctx.seed)
    m = models.GraniteHybrid(gcfg)
    m.eval()
    # a short example input: jit-init traces the forward it is given
    m.compile([tensor.from_numpy(np.zeros((1, cfg["init_len"]), np.int32))],
              is_train=False, use_graph=True)
    ctx.stamp(f"weights made (jit-init): {m.num_params():,} parameters")
    e = cfg["engine"]
    eng = serve.ServeEngine(m, num_slots=e["num_slots"], max_len=e["max_len"],
                            block_size=e["block_size"],
                            param_dtype=jnp.dtype(e["param_dtype"]))
    ctx.stamp(f"engine built: {eng.pool.slot_state_bytes:,} B of state a "
              f"slot, {eng.pool.snapshot_entries} snapshot entries, "
              f"{sum(ck is not None for ck, _ in eng.pool.caches)} of "
              f"{len(eng.pool.caches)} layers with KV blocks")
    t = cell["traffic"]
    streams = [traffic.client_stream(t, cfg["vocab_size"], ctx.seed, c)
               for c in range(t["clients"])]
    loop = ctx.load_module("loops", cell["loop"])
    reqs, w0, w1, active, step_ends, snap0 = loop.drive(
        eng, streams, ctx.seconds, t["warmup_rounds"], ctx.tracer.tick)
    ctx.stamp(f"window closed; it opened at +{w0 - ctx.t0:.1f} s")
    trace = ctx.tracer.stop(ctx.dump_trace)
    snap1 = eng.metrics.snapshot()
    if eng.compiled_counts() != (1, 1):
        raise SystemExit(f"benchmark: the engine compiled "
                         f"{eng.compiled_counts()} programs, not (1, 1)")
    window_s = w1 - w0
    delta = lambda key: snap1.get(key, 0) - snap0.get(key, 0)

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
    # a tick adds one whole view's blocks to this counter
    ticks = delta("decode_kv_blocks_view") \
        // (eng.pool.num_slots * eng.pool.max_blocks)
    print(f"[serve] window {window_s:.3f} s: {len(ended)} requests ended "
          f"({len(bad)} badly, {rejected} rejected), {tokens} tokens, "
          f"{delta('steps')} engine steps, {ticks} decode ticks, mean active "
          f"{np.mean(active):.2f}/{e['num_slots']}, prefix hits "
          f"{delta('prefix_hit_tokens')}/{prompt_tokens} prompt tokens, "
          f"{delta('state_snapshot_hits')} admissions entered from a state "
          f"snapshot, {delta('state_snapshot_writes')} snapshots written, "
          f"{delta('state_snapshot_evictions')} evicted, "
          f"{delta('prefix_tokens_recomputed')} shared rows prefilled again, "
          f"{delta('ssm_state_bytes'):,} B of state moved by the ticks, "
          f"{delta('prefill_chunk_rows')} rows in {delta('prefill_chunks')} "
          f"prefill chunks, {delta('moe_assignments')} expert assignments "
          f"in {delta('moe_dispatches')} dispatches; TTFT n={len(ttft)} "
          f"ITL n={len(itl)}", flush=True)

    # correctness, outside the window.  First what only the live engine
    # holds: the state of requests still running (rule 3), the longest
    # contexts first
    chk = cfg["check"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    running = sorted(eng.running_items(),
                     key=lambda sr: -int(eng.pool.pos[sr[0]]))
    # half of them the longest contexts (many chunks, hundreds of
    # ticks), half the shortest (a snapshot hit and a chunk boundary a
    # few hundred tokens back, which the slower heads still hold)
    n_long = (chk["cache_requests"] + 1) // 2
    chosen = running[:n_long] + running[n_long:][
        ::-1][:chk["cache_requests"] - n_long]
    held = [(np.asarray(r.replay_ids())[:int(eng.pool.pos[s])],
             int(r.prompt.size), eng.slot_cache(s)) for s, r in chosen]
    good = [r for r in ended if r not in bad]
    order = sorted(range(len(good)),
                   key=lambda i: -len(good[i].handle.result()))
    pick = order[:1] + [int(i) for i in np.random.default_rng(
        [ctx.seed, 9]).permutation(order[1:])[:chk["requests"] - 1]]
    seqs = [(good[i].handle.result(), good[i].prompt_len) for i in pick]
    weights = eng.weights()
    # the engine is dropped, not drained
    del eng, loop, streams, running, chosen
    # the masters at the precision the configuration serves them in,
    # rounded by the reference's own code and not by the engine's cast;
    # the model keeps the rounded arrays and its f32 masters go, one
    # array at a time: masters, the engine's cast and a whole rounded
    # copy together are the chip's 16 GB
    params = {}
    for n, p in m.get_params().items():
        params[n] = p.data = jax.block_until_ready(
            reference.rounded({n: p.data}))[n]
    ctx.stamp("engine dropped; the reference's weights rounded")
    score = engine_scorer(m, weights, e, chk["score_rows"],
                          chk["logit_stride"])
    got = [score(seq) for seq, _ in seqs]
    ctx.stamp(f"the engine's weights scored {sum(len(g) for g in got)} "
              f"positions")
    del weights, score
    t_ref = time.perf_counter()
    found = [reference.greedy_gap(
        params, seq, plen, chk["pad_to"], cfg, chk["delta"],
        chk["tolerance"], g, chk["logit_stride"])
        for (seq, plen), g in zip(seqs, got)]
    found_held = [reference.greedy_gap(
        params, seq, min(plen, len(seq) - 1), chk["pad_to"], cfg,
        chk["delta"], chk["tolerance"]) for seq, plen, _ in held]
    errs = [state_errors(h[2], f, kinds) for h, f in zip(held, found_held)]
    worst = lambda vals: max(vals, default=np.inf)
    best = lambda vals: min(vals, default=np.inf)
    # the first mamba layer, whose input no expert has touched, is held
    # in every request; of the deeper layers, where a held expert that
    # joins or leaves a late position's ten replaces part of that
    # position's input (bf16 routes a near tie the other way, neither
    # being wrong) and the window is the last three positions, the
    # request that reads best: a fault of the program is in every
    # request's state, a routing flip in some
    state_first = worst([s[0] for s, _, _, _ in errs])
    heads_first = worst([h[2] for _, _, _, h in errs])
    window_first = worst([w[0] for _, w, _, _ in errs])
    state_deep = best([max(s[1:]) for s, _, _, _ in errs])
    window_deep = best([max(w[1:]) for _, w, _, _ in errs])
    cache_median = worst([x for _, _, kv, _ in errs for pair in kv
                          for x in pair])
    err = np.concatenate([f.pop("err") for f in found])
    logit_err = float(np.median(err))
    both = found + found_held
    total = lambda key: sum(f[key] for f in both)
    checked, unsure, over = total("checked"), total("unsure"), total("over")
    unsure_share = unsure / max(1, checked + unsure)
    over_share = over / max(1, checked)
    drop = ("margins", "keys", "values", "states", "windows")
    brief = [{k: v for k, v in f.items() if k not in drop} for f in both]
    r5 = lambda xs: [round(x, 5) for x in xs]
    print(f"[serve] reference check.  Rule 1, on {len(seqs)} ended requests "
          f"of {[len(s) for s, _ in seqs]} tokens: the engine's weights, "
          f"widened to f32, through the cached forward in chunks of "
          f"{chk['score_rows']} rows against the reference: |logits - "
          f"reference's| / |reference's| a position over {err.size} "
          f"positions: lower quartile {np.percentile(err, 25):.3g}, median "
          f"{logit_err:.3g}, p99 {np.percentile(err, 99):.3g}, largest "
          f"{err.max():.3g}; the median against the limit "
          f"{chk['logit_err_limit']}.  Rule 3, on {len(held)} requests "
          f"still running with {[len(s) for s, _, _ in held]} positions "
          f"(prompts of {[p for _, p, _ in held]}): |engine's - "
          f"reference's| / |reference's| per request: each mamba layer's S "
          f"{[r5(s) for s, _, _, _ in errs]}, its window "
          f"{[r5(w) for _, w, _, _ in errs]}, the attention layer's (median "
          f"over positions of keys, of values) "
          f"{[[r5(p) for p in kv] for _, _, kv, _ in errs]}, the first "
          f"layer's S head by head (median, 90th percentile, largest over "
          f"the heads) {[r5(h) for _, _, _, h in errs]}; the first "
          f"layer's S at its largest {state_first:.3g}, limit "
          f"{chk['state_err_limit']}, its worst head "
          f"{heads_first:.3g}, limit {chk['state_heads_limit']}, its window "
          f"{window_first:.3g}, limit "
          f"{chk['window_err_limit']}; the deeper layers' largest in the "
          f"request that reads best: S {state_deep:.3g} and window "
          f"{window_deep:.3g}, limit {chk['state_deep_limit']}; keys and "
          f"values {cache_median:.3g}, limit {chk['cache_median_limit']}.  "
          f"Rule 2, served tokens of both sets: (best logit - served "
          f"token's logit) beyond the tolerance {chk['tolerance']} at "
          f"{over} of {checked} positions ({over_share:.4f}, limit "
          f"{chk['over_share_limit']}), largest "
          f"{max((f['gap'] for f in both), default=0.0):.5f}; {unsure} "
          f"positions ({unsure_share:.4f} of all, limit "
          f"{chk['unsure_share_limit']}) left out because a layer's routing "
          f"margin is under {chk['delta']}: beyond the tolerance at "
          f"{total('over_unsure')} of them, largest "
          f"{max((f['gap_unsure'] for f in both), default=0.0):.5f}; per "
          f"request {brief}; {time.perf_counter() - t_ref:.1f} s", flush=True)
    correct = checked > 0 and over_share <= chk["over_share_limit"] \
        and unsure_share <= chk["unsure_share_limit"] \
        and logit_err <= chk["logit_err_limit"] \
        and bool(held) and state_first <= chk["state_err_limit"] \
        and heads_first <= chk["state_heads_limit"] \
        and window_first <= chk["window_err_limit"] \
        and max(state_deep, window_deep) <= chk["state_deep_limit"] \
        and cache_median <= chk["cache_median_limit"]

    # `ttft_p95_ms` is among them here: the rank falls among the longest
    # retrieved contexts' ten-chunk prefills, device time, and two sets
    # of six runs spread 0.29% and 0.16% of it (PERF.md section 6, PR 34)
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
        print(f"[serve] TTFT median {yardstick.percentile(ttft, 50):.1f} ms, "
              f"p95 {yardstick.percentile(ttft, 95):.2f} ms; ITL median "
              f"{yardstick.percentile(itl, 50):.2f} ms", flush=True)
    return {"correct": correct, "attempted": len(ended) + rejected,
            "failed": len(bad) + rejected, "end_to_end": end_to_end,
            "trace": trace, "window_s": window_s, "ssm_config": cfg,
            "prompt_tokens": prompt_tokens, "decode_ticks": ticks,
            "ssm_state_bytes": delta("ssm_state_bytes"),
            "prefix_hit_tokens": delta("prefix_hit_tokens"),
            "prefill_chunks": delta("prefill_chunks"),
            "prefill_chunk_rows": delta("prefill_chunk_rows")}
