"""Plain reference of a decoder whose blocks mix sliding-window and full
attention, each kind with its own RoPE, over a mixture-of-experts FFN:
f32 `jax.numpy`, matmuls at "highest" precision, no cache, no kernel,
no batching, no capacity.

Block i: `a = RMSNorm(h)`, `h += Attn_i(a)`, `m = RMSNorm(h)`,
`h += MoE(m)`.  `Attn_i`: q, k, v projections to heads of `head_dim`
(which need not be hidden / heads), half-split RoPE on q and k,
grouped-query attention at scale 1/sqrt(head_dim), output projection.
`layer_types[i]` is `sliding_attention` (query t sees keys in
(t - window, t], the plain RoPE table) or `full_attention` (causal;
YaRN, written out below from its formula, cos and sin both times
`attention_factor`).  `MoE(m)`: `p = softmax(m Wr)` in f32, the top
`num_experts_per_tok`, their weights renormalised to sum 1, `sum_e w_e
(silu(m G_e) * (m U_e)) D_e`: one expert after another over every row,
the rows an expert was not routed weighted 0.  Untied head.

It reads the program's parameters by name and imports nothing from the
program.  `c` holds the source's keys.  Where the configuration serves
its weights in bf16, the caller hands in `rounded(params)`: the masters
rounded to bf16 one array at a time, *outside* the program that reads
them.  Rounded inside it (`a.astype(bf16).astype(f32)` under one `jit`)
the TPU's compiler keeps the excess precision and the reference reads
the unrounded masters: 1.75% of the logits' norm at the published
widths, 5.8% at a sequence's first position (PERF.md section 6, PR 28).
Beside the logits it returns, per position, the smallest gap over the
layers between the last routed expert's probability and the first
unrouted one's: where that is tiny, a program that rounds its
activations may route another expert there with neither being wrong.

`benchmark/reference_moe.py` and `tests/reference_moe.py` are one file
twice (the benchmark may not import from `tests/`, nor the tests from
`benchmark/`); `tests/test_mellum.py` holds them to the same output.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def rope_tables(t: int, head_dim: int, rp: dict):
    """(cos, sin), each (t, head_dim // 2), for one entry of the
    source's `rope_parameters`: `rope_type` "default" or "yarn"."""
    theta = float(rp["rope_theta"])
    j = np.arange(head_dim // 2, dtype=np.float64)
    inv = theta ** (-2.0 * j / head_dim)
    factor = 1.0
    if rp["rope_type"] == "yarn":
        def pair(rotations):        # the pair that turns `rotations` times
            return head_dim * math.log(
                rp["original_max_position_embeddings"]
                / (2 * math.pi * rotations)) / (2 * math.log(theta))
        lo = max(math.floor(pair(rp["beta_fast"])), 0)
        hi = min(math.ceil(pair(rp["beta_slow"])), head_dim - 1)
        ramp = np.clip((j - lo) / (hi - lo), 0.0, 1.0)
        inv = inv / rp["factor"] * ramp + inv * (1.0 - ramp)
        factor = rp["attention_factor"]
    elif rp["rope_type"] != "default":
        raise ValueError(f"unknown rope_type {rp['rope_type']!r}")
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * factor, jnp.float32),
            jnp.asarray(np.sin(ang) * factor, jnp.float32))


def _rope(x, cos, sin):
    d = x.shape[-1]
    a, b = x[..., :d // 2], x[..., d // 2:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _moe(m, router, gate, up, down, k, renorm, w):
    """(T, D) -> ((T, D), (T,) probability gap between the k-th and the
    (k+1)-th expert): a loop over the experts, each over every row."""
    p = jax.nn.softmax(m @ w(router), axis=-1)                    # (T, E)
    top = jnp.sort(p, axis=-1)[:, ::-1]
    routed = p >= top[:, k - 1:k]
    weight = jnp.where(routed, p, 0.0)
    if renorm:
        weight = weight / jnp.sum(weight, -1, keepdims=True)

    def one(acc, e):
        g, u, d, we = e
        y = (jax.nn.silu(m @ w(g)) * (m @ w(u))) @ w(d)
        return acc + we[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), (gate, up, down, weight.T))
    return out, top[:, k - 1] - top[:, k]


def rounded(params: dict) -> dict:
    """`params` at the precision a bf16 deployment serves them in: each
    array rounded on its own, before any program reads it."""
    return {n: a.astype(jnp.bfloat16) for n, a in params.items()}


def logits_one(params, ids, c):
    """(T,) token ids -> ((T, vocab) f32 logits, (T,) smallest routing
    margin over the layers).  `params` are widened to f32, which is
    exact, and used as they are."""
    w = lambda a: a.astype(jnp.float32)
    heads, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd, eps = c["head_dim"], c["rms_norm_eps"]
    t = ids.shape[0]
    tables = {kind: rope_tables(t, hd, rp)
              for kind, rp in c["rope_parameters"].items()}
    q_pos, k_pos = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    causal = k_pos <= q_pos
    x = w(params["tok_emb.table"])[ids]
    margin = jnp.full((t,), jnp.inf, jnp.float32)
    for i in range(c["num_hidden_layers"]):
        p, kind = f"blocks.{i}.", c["layer_types"][i]
        mask = causal & (k_pos > q_pos - c["sliding_window"]) \
            if kind == "sliding_attention" else causal
        cos, sin = tables[kind]
        a = _rms(x, params[p + "attn_norm.gamma"].astype(jnp.float32), eps)
        q = _rope((a @ w(params[p + "attn.q_proj.W"])).reshape(t, heads, hd),
                  cos, sin)
        k = _rope((a @ w(params[p + "attn.k_proj.W"])).reshape(t, kvh, hd),
                  cos, sin)
        v = (a @ w(params[p + "attn.v_proj.W"])).reshape(t, kvh, hd)
        k, v = (jnp.repeat(z, heads // kvh, axis=1) for z in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr, v).reshape(t, heads * hd)
        x = x + o @ w(params[p + "attn.o_proj.W"])
        m = _rms(x, params[p + "ffn_norm.gamma"].astype(jnp.float32), eps)
        y, gap = _moe(m, params[p + "ffn.router"], params[p + "ffn.w_gate"],
                      params[p + "ffn.w_in"], params[p + "ffn.w_out"],
                      c["num_experts_per_tok"], c["norm_topk_prob"], w)
        x, margin = x + y, jnp.minimum(margin, gap)
    x = _rms(x, params["norm_f.gamma"].astype(jnp.float32), eps)
    return x @ w(params["lm_head.W"]), margin


def frozen(c: dict):
    """`c`'s keys that the equations read, hashable (a static argument)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "num_hidden_layers", "sliding_window",
            "num_experts_per_tok", "norm_topk_prob")
    n = c["num_hidden_layers"]
    return tuple((k, c[k]) for k in keys) + (
        ("layer_types", tuple(c["layer_types"][:n])),
        ("rope_parameters", tuple(
            (kind, tuple(sorted(rp.items())))
            for kind, rp in sorted(c["rope_parameters"].items()))))


def thawed(cf) -> dict:
    c = dict(cf)
    c["rope_parameters"] = {kind: dict(rp)
                            for kind, rp in c["rope_parameters"]}
    return c


@functools.partial(jax.jit, static_argnames=("cf",))
def logits_and_margin(params, ids, cf):
    with jax.default_matmul_precision("highest"):
        return logits_one(params, ids, thawed(cf))


@functools.partial(jax.jit, static_argnames=("cf", "stride"))
def _gaps(params, ids, got, cf, stride):
    with jax.default_matmul_precision("highest"):
        lg, margin = logits_one(params, ids, thawed(cf))
    rows = jnp.arange(ids.shape[0] - 1)
    best = lg[:-1].max(-1)
    ref = lg[:, ::stride]
    err = jnp.linalg.norm(got - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1)
    return best - lg[rows, ids[1:]], margin, best, err


def greedy_gap(params, seq, prompt_len, pad_to, c, delta, tolerance,
               got=None, stride=1) -> dict:
    """One teacher-forced pass over `seq` padded to `pad_to` (one
    shape).  Over the positions that produced `seq[prompt_len:]`: the
    gap between the reference's best logit and its logit of the token
    the system served.  `checked` positions have a routing margin of at
    least `delta`; `over` of them have a gap beyond `tolerance` and
    `gap` is their largest; `unsure` positions lie under `delta`
    (`gap_unsure`: their largest gap).  `top` is the largest best logit
    (the size at which the program's bf16 logits round); `bands` gives
    (margin's upper edge, positions, largest gap) by band of the
    margin, which is what the limits were set from.

    `got`, where given: the system's own logits of every position of
    `seq`, every `stride`-th column of the vocabulary, (len(seq),
    ceil(vocab / stride)).  `err` is then, per position, the norm of
    (`got` - the reference's logits) over the norm of the reference's,
    on those columns."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    vocab = params["lm_head.W"].shape[-1]
    padded = np.zeros((pad_to, -(-vocab // stride)), np.float32)
    if got is not None:
        padded[:len(seq)] = got
    gap, margin, best, err = (
        np.asarray(a) for a in _gaps(params, ids, padded, frozen(c), stride))
    served = slice(prompt_len - 1, len(seq) - 1)
    gap, margin = gap[served], margin[served]
    sure = margin >= delta
    worst = lambda g: float(g.max()) if g.size else 0.0
    edges = [0.0, 3e-5, 1e-4, 2e-4, 3e-4, 5e-4, 1e-3, 3e-3, np.inf]
    bands = [(hi, int(((margin >= lo) & (margin < hi)).sum()),
              round(worst(gap[(margin >= lo) & (margin < hi)]), 5))
             for lo, hi in zip(edges, edges[1:])]
    found = {"gap": worst(gap[sure]), "gap_unsure": worst(gap[~sure]),
             "checked": int(sure.sum()), "unsure": int((~sure).sum()),
             "over": int((gap[sure] > tolerance).sum()),
             "over_unsure": int((gap[~sure] > tolerance).sum()),
             "top": float(best[served].max()), "bands": bands}
    if got is not None:
        found["err"] = err[:len(seq)]
    return found
