"""Serve runner for a configuration whose attention is CCA (attention in
a compressed latent with convolutional mixing and a shifted value,
whose side state the engine keeps beside its KV blocks) over top-1
experts behind an MLP router and a tied head: the cell's traffic
through `ServeEngine`, as `runners/serve.py` drives it, with the model
built from the source's own keys and `correct` decided by
`reference_zaya.py` under three rules:

1. logits: the arrays the engine's programs read (`ServeEngine.weights()`)
   through the program's cached forward in f32, one chunk of
   `score_rows` rows after another with the CCA state carried between
   them, against the reference on the masters rounded to bf16;
2. served tokens: the gap between the reference's best logit and its
   logit of the token the timed path served, where no layer's routing
   is a near tie (top-1 on random weights flips on near ties, and a
   flip replaces a whole expert's output);
3. the arena: the keys and values the timed programs left in the KV
   blocks of requests still running when the window closed, against
   the reference's own.  A state dropped at a chunk boundary or at a
   prefix hit spoils two positions' keys and one's value and hardly
   moves a token hundreds of positions on; this rule reads those rows:
   the first layer's, whose input no expert has touched, position by
   position (the largest error), the deeper layers' by their median
   (a routing flip upstream replaces an expert's whole output at its
   position, so their largest error is a flip's and says nothing).

The `LlamaConfig` that `run.py` builds for every cell knows none of
this model's keys and is ignored here.  A program without
`models.Zaya` cannot run the configuration: that is a non-zero exit at
once, before any weight is made.
"""

from __future__ import annotations

import time

import numpy as np


def zaya_config(cfg: dict, models):
    """The program's ZayaConfig for the source's keys in `cfg`."""
    if not hasattr(models, "ZayaConfig"):
        raise SystemExit(
            "benchmark: this program has no models.Zaya: it cannot build "
            "the configuration (CCA attention with its side state, an MLP "
            "router, a tied head)")
    n = cfg["num_hidden_layers"]
    if set(cfg["layer_types"][:n]) != {"hybrid"} \
            or cfg["sliding_window"] is not None \
            or cfg["num_experts_per_tok"] != 1 or cfg["hidden_act"] != "silu" \
            or not cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["lm_head_bias"]:
        raise SystemExit(
            "benchmark: the program builds `hybrid` layers without a "
            "window, top-1 silu-gated experts, a tied head, no biases on "
            "the attention's projections or the head")
    rp = cfg["rope_parameters"]["hybrid"]
    if rp["rope_type"] != "default" \
            or rp["partial_rotary_factor"] != cfg["partial_rotary_factor"]:
        raise SystemExit(f"benchmark: rope_parameters.hybrid {rp} is not a "
                         f"plain table over the config's "
                         f"partial_rotary_factor of each head")
    return models.ZayaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], num_layers=n,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_size=cfg["head_dim"],
        cca_time0=cfg["cca_time0"], cca_time1=cfg["cca_time1"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(rp["rope_theta"]),
        # the tables are built to what the engine can hold, not to the
        # source's 131072 positions
        max_position=cfg["engine"]["max_len"], eps=cfg["rms_norm_eps"],
        num_experts=cfg["num_experts"], ffn_dim=cfg["moe_intermediate_size"],
        router_hidden=cfg["router_hidden_size"],
        moe_top_k=cfg["num_experts_per_tok"])


def engine_scorer(m, weights, e: dict, rows: int, stride: int):
    """`score(seq)` -> (len(seq), vocab / stride) f32: the logits of
    every position of `seq`, every `stride`-th column, computed from
    `weights` = `eng.weights()`, the arrays the engine's programs read
    (the bf16 cast), through `resume_step`, the closure its
    `prefill_chunk` program wraps: one chunk of `rows` rows at a traced
    offset after another over a dense cache, each layer's CCA state
    handed from chunk to chunk as the model returns it.  The weights
    are widened to f32 inside the program, the activations are f32 and
    every matmul runs at "highest", so what separates the result from
    the reference's is what the served weights have lost beyond the
    stated precision, or an equation the program's cached forward has
    wrong, and not the rounding of bf16 activations.  The timed
    programs return tokens only; the served tokens and the arena are
    compared beside this."""
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


def cache_errors(held, found):
    """Rule 3 for one request: per layer, of |arena - reference| /
    |reference| of a position's keys and of its values, the largest
    over the positions and the median.  `held` is `eng.slot_cache()`'s
    per-layer (k, v, ...), `found` the reference's `greedy_gap` of the
    same tokens."""
    rel = lambda a, b: np.linalg.norm((a - b).reshape(len(a), -1), axis=-1) \
        / np.linalg.norm(b.reshape(len(b), -1), axis=-1)
    out = []
    for i, (k, v, *_) in enumerate(held):
        n = k.shape[0]
        ek = rel(k.astype(np.float32), found["keys"][i, :n])
        ev = rel(v.astype(np.float32), found["values"][i, :n])
        out.append((float(ek.max()), float(ev.max()),
                    float(np.median(ek)), float(np.median(ev))))
    return out


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import reference_zaya
    import traffic
    import yardstick
    from singa_tpu import models, serve, tensor

    cfg, cell = ctx.config, ctx.cell
    zcfg = zaya_config(cfg, models)
    tensor.set_seed(ctx.seed)
    m = models.Zaya(zcfg)
    m.eval()
    # a short example input: jit-init traces the forward it is given
    m.compile([tensor.from_numpy(np.zeros((1, cfg["init_len"]), np.int32))],
              is_train=False, use_graph=True)
    ctx.stamp(f"weights made (jit-init): {m.num_params():,} parameters")
    e = cfg["engine"]
    eng = serve.ServeEngine(m, num_slots=e["num_slots"], max_len=e["max_len"],
                            block_size=e["block_size"],
                            param_dtype=jnp.dtype(e["param_dtype"]))
    ctx.stamp("engine built")
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
    print(f"[serve] window {window_s:.3f} s: {len(ended)} requests ended "
          f"({len(bad)} badly, {rejected} rejected), {tokens} tokens, "
          f"{delta('steps')} engine steps, mean active "
          f"{np.mean(active):.2f}/{e['num_slots']}, prefix hits "
          f"{delta('prefix_hit_tokens')}/{prompt_tokens} prompt tokens, "
          f"{delta('cca_state_resumes')} admissions resumed from a block's "
          f"tail ({snap1.get('cca_tail_blocks', 0)} tails resident), "
          f"{delta('prefill_chunk_rows')} rows in {delta('prefill_chunks')} "
          f"prefill chunks, {delta('moe_assignments')} expert assignments "
          f"in {delta('moe_dispatches')} dispatches; TTFT n={len(ttft)} "
          f"ITL n={len(itl)}", flush=True)

    # correctness, outside the window.  First what only the live engine
    # holds: the arena of requests still running (rule 3), the longest
    # contexts first
    chk = cfg["check"]
    running = sorted(eng.running_items(),
                     key=lambda sr: -int(eng.pool.pos[sr[0]]))
    held = [(np.asarray(r.replay_ids())[:int(eng.pool.pos[s])],
             int(r.prompt.size), eng.slot_cache(s))
            for s, r in running[:chk["cache_requests"]]]
    good = [r for r in ended if r not in bad]
    order = sorted(range(len(good)),
                   key=lambda i: -len(good[i].handle.result()))
    pick = order[:1] + [int(i) for i in np.random.default_rng(
        [ctx.seed, 9]).permutation(order[1:])[:chk["requests"] - 1]]
    seqs = [(good[i].handle.result(), good[i].prompt_len) for i in pick]
    weights = eng.weights()
    # the engine is dropped, not drained: 64 chains of thought would
    # decode for tens of seconds more to no purpose
    del eng, loop, streams, running
    # the masters at the precision the configuration serves them in,
    # rounded by the reference's own code and not by the engine's cast;
    # the model keeps the rounded arrays and its f32 masters go, or the
    # scorer's f32 copies would not fit beside them
    # one array at a time: masters, the engine's cast and a whole
    # rounded copy together are the chip's 16 GB
    params = {}
    for n, p in m.get_params().items():
        params[n] = p.data = jax.block_until_ready(
            reference_zaya.rounded({n: p.data}))[n]
    ctx.stamp("engine dropped; the reference's weights rounded")
    score = engine_scorer(m, weights, e, chk["score_rows"],
                          chk["logit_stride"])
    got = [score(seq) for seq, _ in seqs]
    ctx.stamp(f"the engine's weights scored {sum(len(g) for g in got)} "
              f"positions")
    del weights, score
    t_ref = time.perf_counter()
    found = [reference_zaya.greedy_gap(
        params, seq, plen, chk["pad_to"], cfg, chk["delta"],
        chk["tolerance"], g, chk["logit_stride"])
        for (seq, plen), g in zip(seqs, got)]
    found_held = [reference_zaya.greedy_gap(
        params, seq, min(plen, len(seq) - 1), chk["pad_to"], cfg,
        chk["delta"], chk["tolerance"]) for seq, plen, _ in held]
    caches = [cache_errors(h[2], f) for h, f in zip(held, found_held)]
    cache_err = max((max(c[0][:2]) for c in caches), default=np.inf)
    cache_median = max((max(layer[2:]) for c in caches for layer in c[1:]),
                       default=0.0)
    err = np.concatenate([f.pop("err") for f in found])
    logit_err = float(np.median(err))
    both = found + found_held
    total = lambda key: sum(f[key] for f in both)
    checked, unsure, over = total("checked"), total("unsure"), total("over")
    unsure_share = unsure / max(1, checked + unsure)
    over_share = over / max(1, checked)
    brief = [{k: v for k, v in f.items()
              if k not in ("margins", "keys", "values")} for f in both]
    print(f"[serve] reference check.  Rule 1, on {len(seqs)} ended requests "
          f"of {[len(s) for s, _ in seqs]} tokens: the engine's weights, "
          f"widened to f32, through the cached forward in chunks of "
          f"{chk['score_rows']} rows against the reference: |logits - "
          f"reference's| / |reference's| a position over {err.size} "
          f"positions: lower quartile {np.percentile(err, 25):.3g}, median "
          f"{logit_err:.3g}, p99 {np.percentile(err, 99):.3g}, largest "
          f"{err.max():.3g}; the median against the limit "
          f"{chk['logit_err_limit']}.  Rule 3, on {len(held)} requests "
          f"still running with {[len(s) for s, _, _ in held]} positions in "
          f"the arena: |arena - reference's| / |reference's| a position, "
          f"per layer (largest of the keys, of the values, median of the "
          f"keys, of the values) "
          f"{[[tuple(round(x, 5) for x in l) for l in c] for c in caches]}; "
          f"the first layer's largest {cache_err:.3g}, limit "
          f"{chk['cache_err_limit']}; the deeper layers' largest median "
          f"{cache_median:.3g}, limit {chk['cache_median_limit']}.  "
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
        and bool(held) and cache_err <= chk["cache_err_limit"] \
        and cache_median <= chk["cache_median_limit"]

    # no `ttft_p95_ms` among them: the cell reports it as the per-layer
    # reading `ttft_p95_ms.serve_cca`, from `ttft_ms` below (the rank
    # falls in the upper tail of 22 three-chunk admissions, and runs of
    # one tree spread wider than the metric's bound: PERF.md section 7 h)
    end_to_end = {"serve_tokens_per_s": tokens / window_s,
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
            "trace": trace, "window_s": window_s, "moe_config": cfg,
            "moe_assignments": delta("moe_assignments"),
            "moe_dispatches": delta("moe_dispatches"),
            "prompt_tokens": prompt_tokens, "ttft_ms": ttft,
            "prefix_hit_tokens": delta("prefix_hit_tokens"),
            "prefill_chunks": delta("prefill_chunks"),
            "prefill_chunk_rows": delta("prefill_chunk_rows")}
