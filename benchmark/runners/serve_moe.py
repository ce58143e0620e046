"""Serve runner for a mixture-of-experts configuration whose blocks mix
sliding and full attention: the cell's traffic through `ServeEngine`,
as `runners/serve.py` drives it, with the model built from the
source's own keys (`head_dim`, `layer_types`, `rope_parameters`,
`num_experts`, ...) and `correct` decided by `reference_moe.py` under
two rules: the logits of the engine's own weights through the program's
cached forward in f32 (`engine_scorer`), and the served tokens' gap to
the reference's best logit where the routing is not a near tie.

The `LlamaConfig` that `run.py` builds for every cell knows none of
those keys and is ignored here.  A program whose `LlamaConfig` lacks
them cannot run the configuration: that is a non-zero exit at once,
before any weight is made.
"""

from __future__ import annotations

import math
import time

import numpy as np

NEEDS = ("head_size", "layer_types", "yarn_factor", "moe_dropless")


def llama_config(cfg: dict, models):
    """The program's LlamaConfig for the source's keys in `cfg`."""
    have = getattr(models.LlamaConfig, "__dataclass_fields__", {})
    missing = [k for k in NEEDS if k not in have]
    if missing:
        raise SystemExit(
            f"benchmark: this program's LlamaConfig has no {missing}: it "
            f"cannot build the configuration (per-layer attention types, "
            f"a head size of its own, dropless experts)")
    n = cfg["num_hidden_layers"]
    if set(cfg["mlp_layer_types"][:n]) != {"sparse"}:
        raise SystemExit("benchmark: a dense FFN layer is not built here")
    rp = cfg["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if full["rope_type"] != "yarn" or sliding["rope_type"] != "default" \
            or full["rope_theta"] != sliding["rope_theta"] \
            or (full["beta_fast"], full["beta_slow"]) != (32, 1) \
            or abs(full["attention_factor"]
                   - (0.1 * math.log(full["factor"]) + 1.0)) > 1e-9:
        raise SystemExit(f"benchmark: rope_parameters {rp} are not a plain "
                         f"table for sliding layers and, for full ones, YaRN "
                         f"at beta 32 and 1 with the attention factor "
                         f"0.1 ln(factor) + 1, which is what the program builds")
    return models.LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], num_layers=n,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_size=cfg["head_dim"],
        ffn_dim=cfg["moe_intermediate_size"],
        # the tables are built to what the engine can hold, not to the
        # source's 131072 positions
        max_position=cfg["engine"]["max_len"],
        rope_theta=float(sliding["rope_theta"]),
        sliding_window=cfg["sliding_window"], eps=cfg["rms_norm_eps"],
        layer_types=tuple(cfg["layer_types"][:n]),
        yarn_factor=float(full["factor"]),
        yarn_original_max_position=full["original_max_position_embeddings"],
        num_experts=cfg["num_experts"], moe_top_k=cfg["num_experts_per_tok"],
        moe_dropless=True)


def engine_scorer(m, weights, e: dict, stride: int):
    """`score(seq)` -> (len(seq), ceil(vocab / stride)) f32: the logits of
    every position of `seq`, every `stride`-th column, computed from
    `weights` = `eng.weights()`, the arrays the engine's programs read
    (the bf16 cast), through `resume_step`, the closure its
    `prefill_chunk` program wraps: one block-sized chunk at a traced
    offset after another over a dense cache.  The weights are widened
    to f32 inside the program, the activations are f32 and every matmul
    runs at "highest", so what separates the result from the
    reference's is what the served weights have lost beyond the stated
    precision, or an equation the program's cached forward has wrong,
    and not the rounding of bf16 activations (which alone reads 1.2 to
    1.5%, where int8 expert stacks read 1.6%: PERF.md section 6, PR
    28).  The timed programs return tokens only; the served tokens are
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
        return logits[0, :, ::stride], caches

    chunk_logits = jax.jit(chunk_logits, donate_argnums=(4,))
    bs = e["block_size"]

    def score(seq) -> np.ndarray:
        # f32, as the model's masters are: the caches follow them
        caches = m.init_caches(1, e["max_len"])
        ids = np.zeros((-(-len(seq) // bs) * bs,), np.int32)
        ids[:len(seq)] = seq
        rows = []
        for start in range(0, ids.size, bs):
            lg, caches = chunk_logits(
                *weights, jnp.asarray(ids[None, start:start + bs]),
                jnp.asarray(start, jnp.int32), caches)
            rows.append(lg)
        return np.asarray(jnp.concatenate(rows))[:len(seq)]

    return score


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import reference_moe
    import traffic
    import yardstick
    from singa_tpu import models, serve, tensor

    cfg, cell = ctx.config, ctx.cell
    if not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["attention_bias"]:
        raise SystemExit("benchmark: the program renormalises the routed "
                         "weights, unties the head and has no biases")
    lcfg = llama_config(cfg, models)
    tensor.set_seed(ctx.seed)
    m = models.Llama(lcfg)
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
    past_window = sum(r.prompt_len + len(r.stamps) > cfg["sliding_window"]
                      for r in ended)
    print(f"[serve] window {window_s:.3f} s: {len(ended)} requests ended "
          f"({len(bad)} badly, {rejected} rejected; {past_window} ran past "
          f"the {cfg['sliding_window']}-token window), {tokens} tokens, "
          f"{delta('steps')} engine steps, mean active "
          f"{np.mean(active):.2f}/{e['num_slots']}, prefix hits "
          f"{delta('prefix_hit_tokens')}/{prompt_tokens} prompt tokens, "
          f"{delta('moe_assignments')} expert assignments in "
          f"{delta('moe_dispatches')} dispatches; TTFT n={len(ttft)} "
          f"ITL n={len(itl)}", flush=True)

    # correctness, outside the window.  The longest request that ended
    # well is always checked (the sliding layers bind only past the
    # window), the others are drawn
    good = [r for r in ended if r not in bad]
    chk = cfg["check"]
    order = sorted(range(len(good)), key=lambda i: -len(good[i].handle.result()))
    pick = order[:1] + [int(i) for i in np.random.default_rng(
        [ctx.seed, 9]).permutation(order[1:])[:chk["requests"] - 1]]
    seqs = [(good[i].handle.result(), good[i].prompt_len) for i in pick]
    weights = eng.weights()
    eng.close()
    ctx.stamp("engine drained and closed")
    score = engine_scorer(m, weights, e, chk["logit_stride"])
    got = [score(seq) for seq, _ in seqs]
    ctx.stamp(f"the engine's weights scored {sum(len(g) for g in got)} "
              f"positions")
    # they go before the reference's weights come: both would not fit
    del eng, weights, score, loop, streams
    # the masters at the precision the configuration serves them in,
    # rounded by the reference's own code and not by the engine's cast
    params = reference_moe.rounded(
        {n: p.data for n, p in m.get_params().items()})
    ctx.stamp("the reference's weights rounded")
    t_ref = time.perf_counter()
    found = [reference_moe.greedy_gap(params, seq, plen, chk["pad_to"], cfg,
                                      chk["delta"], chk["tolerance"],
                                      g, chk["logit_stride"])
             for (seq, plen), g in zip(seqs, got)]
    err = np.concatenate([f.pop("err") for f in found])
    # positions whose context has passed the sliding layers' window read
    # for themselves: a window or a table that is wrong only there must
    # not drown among the shorter ones
    past = np.concatenate([np.arange(len(s)) >= cfg["sliding_window"]
                           for s, _ in seqs])
    total = lambda key: sum(f[key] for f in found)
    checked, unsure, over = total("checked"), total("unsure"), total("over")
    unsure_share = unsure / max(1, checked + unsure)
    over_share = over / max(1, checked)
    groups = {"up to the window": err[~past], "past the window": err[past]}
    # the lower quartile: where f32 itself routes another expert than
    # the reference (a tie under 1e-7), every later position of that
    # request moves by ~1.5e-3, and a group may hold one request
    low = {k: float(np.percentile(a, 25)) for k, a in groups.items() if a.size}
    logit_err = max(low.values(), default=math.inf)
    print(f"[serve] reference check on {len(seqs)} requests of "
          f"{[len(s) for s, _ in seqs]} tokens.  The engine's weights, "
          f"widened to f32, through the prefill closure against the "
          f"reference: |logits - reference's| / |reference's| a position, "
          + "; ".join(f"{k}, {a.size} positions: lower quartile "
                      f"{low.get(k, math.nan):.3g}, median "
                      f"{np.median(a) if a.size else math.nan:.3g}, p99 "
                      f"{np.percentile(a, 99) if a.size else math.nan:.3g}, "
                      f"largest {a.max() if a.size else math.nan:.3g}"
                      for k, a in groups.items())
          + f"; the larger lower quartile {logit_err:.3g}, limit "
          f"{chk['logit_err_limit']}.  Served tokens: (best "
          f"logit - served token's logit) beyond the tolerance "
          f"{chk['tolerance']} at {over} of {checked} positions "
          f"({over_share:.4f}, limit {chk['over_share_limit']}), largest "
          f"{max((f['gap'] for f in found), default=0.0):.5f}; {unsure} "
          f"positions ({unsure_share:.4f} of all, limit "
          f"{chk['unsure_share_limit']}) left out because an expert's routing "
          f"margin is under {chk['delta']}: beyond the tolerance at "
          f"{total('over_unsure')} of them, largest "
          f"{max((f['gap_unsure'] for f in found), default=0.0):.5f}; per "
          f"request {found}; {time.perf_counter() - t_ref:.1f} s", flush=True)
    correct = checked > 0 and over_share <= chk["over_share_limit"] \
        and unsure_share <= chk["unsure_share_limit"] \
        and logit_err <= chk["logit_err_limit"]

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
        print(f"[serve] medians: TTFT {yardstick.percentile(ttft, 50):.1f} "
              f"ms, ITL {yardstick.percentile(itl, 50):.2f} ms", flush=True)
    return {"correct": correct, "attempted": len(ended) + rejected,
            "failed": len(bad) + rejected, "end_to_end": end_to_end,
            "trace": trace, "window_s": window_s, "moe_config": cfg,
            "moe_assignments": delta("moe_assignments"),
            "moe_dispatches": delta("moe_dispatches")}
