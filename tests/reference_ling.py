"""Plain reference of the Ling-3.0-flash decoder (`bailing_hybrid`, the
language model of Ling-3.0-flash-VL): Kimi delta attention (KDA, Kimi
Linear arXiv:2510.26692) in five layers of six and multi-head latent
attention (MLA, DeepSeek-V2 arXiv:2405.04434) with a head-wise output
gate in the sixth, a dense SwiGLU in the first layers and then
sigmoid-routed experts chosen within the best groups (DeepSeek-V3
arXiv:2412.19437) beside a shared expert, an untied head.  f32
`jax.numpy`, matmuls at "highest" precision, no cache, no batching; the
delta rule token by token (a `lax.scan`, no chunks), the latent
attention in its EXPANDED form only: every head's keys and values made
from `W_kvb`, no absorption.

On a sequence of T rows, with `eps` = `rms_norm_eps`:

    h = tok_emb[ids]
    per layer:  h += Mixer(RMSNorm(h));  h += FFN(RMSNorm(h))
    logits = RMSNorm(h) W_head                              (untied)

Layer i is MLA when `(i + 1) % layer_group_size == 0`, else KDA.

`KDA` (H = `num_attention_heads` heads of d = `head_dim`; K =
`short_conv_kernel_size`): `u = x W_qkv`; `[q | k | v] = silu(sum_j
w_j u_{t-K+1+j})`, a depthwise causal convolution without bias, zeros
before position 0; per head `q = q / sqrt(|q|^2 + 1e-6) d^-1/2`, `k = k
/ sqrt(|k|^2 + 1e-6)`; `beta = sigmoid(x W_b)` a head; `g =
kda_lower_bound * sigmoid(exp(A_log)_h (x W_f + dt_bias))` a channel;
per head, from S = 0 (d x d):

    S = Diag(exp(g_t)) S;  S = S + beta_t k_t (v_t - S^T k_t)^T;  o_t = S^T q_t

(`exp(g_t)` taken as `1 + expm1(g_t)`: the same number, without the
bias of a TPU's f32 exp that a product over thousands of rows compounds),
then `o = RMSNorm(o_t) * o_norm * sigmoid(x W_g)` over each head's d,
and `concat_h o W_o`.

`MLA` (r = `kv_lora_rank`, dn / dr / dv = `qk_nope_head_dim` /
`qk_rope_head_dim` / `v_head_dim`; `q_lora_rank` null): `[q_nope |
q_rope]_h = (x W_q)_h`; `[c_kv | k_rope] = x W_kva`, `c_kv = RMSNorm(c_kv)
* kv_a_norm`; `[k_nope | v]_h = (c_kv W_kvb)_h`; RoPE (theta
`rope_theta`, all dr dims) on `q_rope` and on the one `k_rope`; per head
`softmax((q_nope.k_nope + q_rope.k_rope) / sqrt(dn + dr))` under the
causal mask, `o_h = p v_h`, times `sigmoid(x w_gate,h)`; `concat_h W_o`.

`FFN`: layers below `first_k_dense_replace` a SwiGLU of
`intermediate_size`; the others `Routed + Shared`.  `Routed`: `s =
sigmoid(x W_r)` over all `num_experts` of the deployment, `c = s + b`
(b the correction bias); the experts are `n_group` groups in id order,
a group's score the sum of its two largest c, and the choice is the
`num_experts_per_tok` largest c among the `topk_group` best groups; the
gates are the chosen `s`, divided by their sum, times
`routed_scaling_factor`; expert e gives `(silu(x G_e) * (x U_e)) D_e`,
and only the held experts' (`deployment.experts_held`) products are
summed.  `Shared`: the same gated form, every token.

Departures from the published model, each one the program's too: the
RoPE pairs are the half-split (rotate-half) layout; the
multi-token-prediction layer, the vision tower and the SwiGLU limits of
the last layers are not built (the layers built have none).

It reads the program's parameters by name and imports nothing from the
program.  `c` holds the source's keys.  Where the configuration serves
its weights in bf16 the caller hands in `rounded(params)`, the masters
rounded one array at a time outside the program that reads them.
Beside the logits: per layer and position the routing margin, per MLA
layer the latent a cache would hold (`c_kv` and the rotated `k_rope`),
and per KDA layer the state S and the convolution's window as they
stood after a given row.

`benchmark/reference_ling.py` and `tests/reference_ling.py` are one file
twice (the benchmark may not import from `tests/`, nor the tests from
`benchmark/`); `tests/test_ling.py` holds them to the same text.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: query rows a block of the attention (a (H, rows, T) f32 score block)
_Q_ROWS = 512


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def held_experts(c: dict):
    """Ids of the experts whose stacks the configuration holds."""
    return tuple(c.get("deployment", {}).get(
        "experts_held", range(c["num_experts"])))


def is_mla(c: dict, i: int) -> bool:
    return (i + 1) % c["layer_group_size"] == 0


def rope(x, pos, dim, theta):
    """Rotate the last axis of `x` (T, ..., dim) at positions `pos` (T,),
    known on the host: pairs (i, i + dim / 2), frequency theta^(-2i /
    dim).  The angles and their cosines are float64, rounded once to
    f32."""
    inv = 1.0 / float(theta) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.asarray(pos, np.float64)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def kda(h, p, c, w, at):
    """(T, D) -> ((T, D), S (H, d, d) after row `at`, the convolution's
    input rows `at - K + 2 .. at` (K - 1, 3 H d))."""
    H, d, K = (c["num_attention_heads"], c["head_dim"],
               c["short_conv_kernel_size"])
    t = h.shape[0]
    u = h @ w(p("qkv.W"))
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    cw = w(p("conv_w"))
    mixed = jax.nn.silu(sum(cw[j] * ext[j:j + t] for j in range(K)))
    q, k, v = (a.reshape(t, H, d) for a in jnp.split(mixed, 3, -1))
    q, k = _l2(q) * d ** -0.5, _l2(k)
    beta = jax.nn.sigmoid(h @ w(p("b.W")))
    g = c["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w(p("A_log")))[:, None]
        * ((h @ w(p("f.W"))).reshape(t, H, d)
           + w(p("dt_bias")).reshape(H, d)))

    def step(carry, e):
        S, kept = carry
        qt, kt, vt, gt, bt, i = e
        # 1 + expm1: a TPU's f32 exp is biased (-8e-7 on average), and a
        # slow channel multiplies thousands of decays (PERF.md §6)
        S = S * (1 + jnp.expm1(gt))[..., None]
        S = S + bt[:, None, None] * kt[..., None] \
            * (vt - jnp.einsum("hk,hkv->hv", kt, S))[:, None, :]
        kept = jnp.where(i == at, S, kept)
        return (S, kept), jnp.einsum("hk,hkv->hv", qt, S)

    zero = jnp.zeros((H, d, d), jnp.float32)
    (_, S_at), o = jax.lax.scan(step, (zero, zero),
                                (q, k, v, g, beta, jnp.arange(t)))
    o = _rms(o, w(p("o_norm")), c["rms_norm_eps"]) \
        * jax.nn.sigmoid(h @ w(p("g.W"))).reshape(t, H, d)
    window = jax.lax.dynamic_slice_in_dim(ext, at + 1, K - 1, axis=0)
    return o.reshape(t, H * d) @ w(p("o.W")), S_at, window


def mla(h, p, c, w):
    """(T, D) -> ((T, D), c_kv (T, r), rotated k_rope (T, dr))."""
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    eps, t = c["rms_norm_eps"], h.shape[0]
    pos = np.arange(t)
    q = (h @ w(p("q.W"))).reshape(t, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, dr, c["rope_theta"])
    kv = h @ w(p("kv_a.W"))
    c_kv = _rms(kv[:, :r], w(p("kv_a_norm")), eps)
    k_rope = rope(kv[:, r:], pos, dr, c["rope_theta"])
    kv = (c_kv @ w(p("kv_b.W"))).reshape(t, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5
    rows = min(_Q_ROWS, t)
    if t % rows:
        raise ValueError(f"{t} rows are not whole blocks of {rows}")

    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i, rows, axis=0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i, rows, axis=0)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
        causal = jnp.arange(t)[None, :] <= (i + jnp.arange(rows))[:, None]
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v)

    o = jax.lax.map(block, jnp.arange(0, t, rows)).reshape(t, H, dv)
    o = o * jax.nn.sigmoid(h @ w(p("gate.W")))[..., None]
    return o.reshape(t, H * dv) @ w(p("o.W")), c_kv, k_rope


def moe(m, p, c, w):
    """(T, D) -> (the held experts' part of the routed sum (T, D), the
    (T,) margin of the choice: the smaller of the k-th largest score +
    bias among the kept groups less the next, and the `topk_group`-th
    group score less the next)."""
    s = jax.nn.sigmoid(m @ w(p("router")))                       # (T, E)
    cb = s + w(p("score_bias"))
    T, E = cb.shape
    k, G, tg = c["num_experts_per_tok"], c["n_group"], c["topk_group"]
    by_group = jnp.sort(cb.reshape(T, G, E // G), axis=-1)
    gscore = by_group[..., -1] + by_group[..., -2]               # (T, G)
    gsort = jnp.sort(gscore, axis=-1)[:, ::-1]
    kept = jnp.repeat(gscore >= gsort[:, tg - 1:tg], E // G, axis=1)
    masked = jnp.where(kept, cb, -jnp.inf)
    top = jnp.sort(masked, axis=-1)[:, ::-1]
    chosen = masked >= top[:, k - 1:k]
    g = jnp.where(chosen, s, 0.0)
    gates = g / (g.sum(-1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
    gates = gates[:, jnp.asarray(held_experts(c))]               # (T, held)

    def one(acc, e):
        gt, u, dn, we = e
        return acc + we[:, None] * _gated(m, w(gt), w(u), w(dn)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (p("w_gate"), p("w_in"), p("w_out"), gates.T))
    margin = top[:, k - 1] - top[:, k]
    if G > 1:
        margin = jnp.minimum(margin, gsort[:, tg - 1] - gsort[:, tg])
    return out, margin


def rounded(params: dict) -> dict:
    """`params` at the precision a bf16 deployment serves them in: each
    array rounded on its own, before any program reads it."""
    return {n: a.astype(jnp.bfloat16) for n, a in params.items()}


def hidden_one(params, ids, c, at):
    """(T,) token ids -> (the normed last hidden state (T, D), the
    routing margins (L, T) (inf in a dense layer), per MLA layer c_kv
    (T, r) and k_rope (T, dr), per KDA layer S (H, d, d) and the window
    after row `at`).  `params` are widened to f32, which is exact."""
    w = lambda a: a.astype(jnp.float32)
    eps = c["rms_norm_eps"]
    x = w(params["tok_emb.table"][ids])
    margins, latents, ropes, states, windows = [], [], [], [], []
    for i in range(c["num_hidden_layers"]):
        at_ = lambda n, i=i: params[f"blocks.{i}.{n}"]
        h = _rms(x, w(at_("attn_norm.gamma")), eps)
        attn = lambda n, i=i: at_(f"attn.{n}")
        if is_mla(c, i):
            y, c_kv, k_rope = mla(h, attn, c, w)
            latents.append(c_kv)
            ropes.append(k_rope)
        else:
            y, S, window = kda(h, attn, c, w, at)
            states.append(S)
            windows.append(window)
        x = x + y
        m = _rms(x, w(at_("ffn_norm.gamma")), eps)
        if i < c["first_k_dense_replace"]:
            x = x + _gated(m, w(at_("mlp.gate.W")), w(at_("mlp.up.W")),
                           w(at_("mlp.down.W")))
            margins.append(jnp.full(ids.shape, jnp.inf))
            continue
        y, gap = moe(m, lambda n, i=i: at_(f"ffn.{n}"), c, w)
        x = x + y + _gated(m, w(at_("shared.gate.W")), w(at_("shared.up.W")),
                           w(at_("shared.down.W")))
        margins.append(gap)
    x = _rms(x, w(params["norm_f.gamma"]), eps)
    return x, jnp.stack(margins), latents, ropes, states, windows


def logits_one(params, ids, c, at=0):
    """((T, vocab) f32 logits through the untied head, then
    `hidden_one`'s other results): for a vocabulary small enough to hold
    whole."""
    x, *rest = hidden_one(params, ids, c, at)
    return (x @ params["lm_head.W"].astype(jnp.float32), *rest)


_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "head_dim", "layer_group_size", "short_conv_kernel_size",
         "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps",
         "first_k_dense_replace", "num_experts", "num_experts_per_tok",
         "n_group", "topk_group", "routed_scaling_factor")


def frozen(c: dict):
    """`c`'s keys that the equations read, hashable (a static argument)."""
    return tuple((k, c[k]) for k in _KEYS) + (
        ("experts_held", held_experts(c)),)


def thawed(cf) -> dict:
    c = dict(cf)
    c["deployment"] = {"experts_held": c.pop("experts_held")}
    return c


@functools.partial(jax.jit, static_argnames=("cf",))
def logits_and_margin(params, ids, cf, at=0):
    with jax.default_matmul_precision("highest"):
        return logits_one(params, ids, thawed(cf), at)


def vocab_blocks(vocab: int, stride: int, most: int = 8192) -> int:
    """Into how many equal blocks of whole strides, of at most `most`
    rows each, the vocabulary divides."""
    for n in range(1, vocab + 1):
        if vocab % n == 0 and (vocab // n) % stride == 0 \
                and vocab // n <= most:
            return n
    raise ValueError(f"a vocabulary of {vocab} rows does not divide into "
                     f"blocks of whole strides of {stride}")


@functools.partial(jax.jit, static_argnames=("cf", "stride"))
def _gaps(params, ids, got, at, cf, stride):
    """The head in blocks of the vocabulary, so that a (T, vocab) f32
    array never exists: per block the running best logit, the logit of
    the next token where it falls in the block, and the sums of the
    comparison with `got` on every `stride`-th column."""
    with jax.default_matmul_precision("highest"):
        c = thawed(cf)
        x, margins, latents, ropes, states, windows = hidden_one(
            params, ids, c, at)
        head = params["lm_head.W"]
        vocab, t = head.shape[1], ids.shape[0]
        n = vocab_blocks(vocab, stride)
        size = vocab // n
        nxt = jnp.concatenate([ids[1:], ids[:1]])

        def block(carry, e):
            best, picked, num, den = carry
            cols, seen, j = e
            lg = x @ cols.astype(jnp.float32)
            local = nxt - j * size
            here = (local >= 0) & (local < size)
            at_ = jnp.take_along_axis(
                lg, jnp.clip(local, 0, size - 1)[:, None], axis=1)[:, 0]
            ref = lg[:, ::stride]
            return (jnp.maximum(best, lg.max(-1)),
                    jnp.where(here, at_, picked),
                    num + jnp.sum((seen - ref) ** 2, -1),
                    den + jnp.sum(ref ** 2, -1)), None

        zero = jnp.zeros((t,), jnp.float32)
        (best, picked, num, den), _ = jax.lax.scan(
            block, (jnp.full((t,), -jnp.inf), zero, zero, zero),
            (jnp.moveaxis(head.reshape(head.shape[0], n, size), 1, 0),
             jnp.moveaxis(got.reshape(t, n, size // stride), 1, 0),
             jnp.arange(n)))
    return (best - picked)[:-1], margins, best[:-1], jnp.sqrt(num / den), \
        latents, ropes, states, windows


def greedy_gap(params, seq, prompt_len, pad_to, c, delta, tolerance,
               got=None, stride=1, state_after=None) -> dict:
    """One teacher-forced pass over `seq` padded to `pad_to` (one
    shape; causality keeps the pad rows out of every valid one).  Over
    the positions that produced `seq[prompt_len:]`: the gap between the
    reference's best logit and its logit of the token the system
    served.  `checked` positions have a routing margin of at least
    `delta` in every expert layer; `over` of them have a gap beyond
    `tolerance` and `gap` is their largest; `unsure` positions lie under
    `delta` (`gap_unsure`: their largest gap).

    `got`, where given: the system's own logits of every position of
    `seq`, every `stride`-th column of the vocabulary, (len(seq), vocab
    / stride).  `err` is then, per position, the norm of (`got` - the
    reference's logits) over the norm of the reference's, on those
    columns.  `latents` / `ropes`: per MLA layer the reference's c_kv
    (len(seq), r) and k_rope (len(seq), dr); `states` / `windows`: per
    KDA layer S (H, d, d) and the convolution's window after the first
    `state_after` rows (default: all of `seq`)."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    vocab = params["lm_head.W"].shape[1]
    padded = np.zeros((pad_to, vocab // stride), np.float32)
    if got is not None:
        padded[:len(seq)] = got
    at = np.int32((len(seq) if state_after is None else state_after) - 1)
    gap, margins, best, err, latents, ropes, states, windows = _gaps(
        params, ids, padded, at, frozen(c), stride)
    gap, margins, err = np.asarray(gap), np.asarray(margins), np.asarray(err)
    served = slice(prompt_len - 1, len(seq) - 1)
    margin = margins.min(0)
    gap, margin = gap[served], margin[served]
    sure = margin >= delta
    worst = lambda g: float(g.max()) if g.size else 0.0
    found = {"gap": worst(gap[sure]), "gap_unsure": worst(gap[~sure]),
             "checked": int(sure.sum()), "unsure": int((~sure).sum()),
             "over": int((gap[sure] > tolerance).sum()),
             "over_unsure": int((gap[~sure] > tolerance).sum()),
             "latents": [np.asarray(a)[:len(seq)] for a in latents],
             "ropes": [np.asarray(a)[:len(seq)] for a in ropes],
             "states": [np.asarray(a) for a in states],
             "windows": [np.asarray(a) for a in windows]}
    if got is not None:
        found["err"] = err[:len(seq)]
    return found
