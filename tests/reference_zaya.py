"""Plain reference of the ZAYA1 decoder (Zyphra; arXiv:2511.17127), whose
attention is Compressed Convolutional Attention (CCA, arXiv:2510.04476):
f32 `jax.numpy`, matmuls at "highest" precision, no cache, no chunks,
no batching, the convolutions as explicit shifted sums.

Block i, on a sequence of T rows: `h = RMSNorm(x)`, `x += CCA(h)`,
`m = RMSNorm(x)`, `x += MoE(m)`.  With D the stream's width, Hq query
and Hkv KV heads of width d, G = Hq / Hkv:

1. latents `q~ = h Wq` (T, Hq d), `k~ = h Wk` (T, Hkv d);
2. shifted value `v_t = [h_t Wv_cur ; h_{t-1} Wv_prev]`, `h_{-1} = 0`:
   the first half of the KV heads sees the current token, the second
   half the one before it;
3. q-k means, taken before the convolutions: `mq = (q~ + rep_G(k~)) / 2`,
   each KV head repeated to its G query heads (query head j belongs to
   KV head j // G); `mk = (mean_G(q~) + k~) / 2`;
4. `c = conv1(conv0([q~ ; k~]))` over the (Hq + Hkv) d packed channels,
   causal in time: the packed latents get `(cca_time0 - 1) + (cca_time1
   - 1)` zero rows on top once; conv0 is depthwise with `cca_time0`
   taps, conv1 has `cca_time1` taps and maps d -> d within each of the
   Hq + Hkv heads; both add a bias, neither pads again (so the row
   before the first holds conv0's bias and not zero);
5. `q = c_q + mq`, `k = c_k + mk`; each head scaled to norm sqrt(d);
   `k` times a learned scalar per KV head; rotary embedding on the
   first `partial_rotary_factor` of each head's dims (half-split
   within them, theta from `rope_parameters.hybrid`), the rest passed;
6. `o = softmax(q k^T / sqrt(d)) v`, causal, grouped-query;
   `CCA(h) = o Wo` (Hq d -> D).

`MoE(m)`: `s = m Wdown`; `logits = W3 gelu(W2 gelu(W1 s + b1) + b2)`
(gelu by erf); `p = softmax(logits)`; `e = argmax p`; `MoE(m) = p_e
(silu(m G_e) * (m U_e)) D_e`: the gate is the chosen expert's
probability, not renormalised.  Logits `RMSNorm(x) tok_emb^T`: the head
is tied.

Where this differs from the two papers' text, as far as that can be
told without the network: (a) both describe the shifted value and the
q-k mean as above; the order "mean before the convolutions, added
after them" follows the CCA paper's reference listing; (b) the
listing applies its L2 norm and a learned temperature to k alone
after the norm, as here ("norm then temperature"); (c) depth averaging
of the router's input across layers, a skip expert and learned
residual scales, which the ZAYA1 report describes, are named by no key
of the published config and are not computed.

It reads the program's parameters by name and imports nothing from the
program.  `c` holds the source's keys.  Where the configuration serves
its weights in bf16 the caller hands in `rounded(params)`: the masters
rounded one array at a time outside the program that reads them (the
TPU's compiler keeps the excess precision of a rounding made inside:
`reference_moe.py`).  Beside the logits: per layer and position the gap
between the routed expert's probability and the next one's (where that
is tiny a program that rounds its activations may route the other
expert with neither being wrong), and per layer the keys and values a
KV cache would hold.

`benchmark/reference_zaya.py` and `tests/reference_zaya.py` are one
file twice (the benchmark may not import from `tests/`, nor the tests
from `benchmark/`); `tests/test_zaya.py` holds them to the same text.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def rope_tables(t: int, rot: int, theta: float):
    """(cos, sin), each (t, rot // 2): `rot` is the number of rotated
    dims of a head."""
    inv = float(theta) ** (-2.0 * np.arange(rot // 2, dtype=np.float64) / rot)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """(T, H, d): the first 2 x cos.shape[-1] dims of each head rotated,
    half-split among themselves; the rest as they are."""
    r = cos.shape[-1]
    a, b, rest = x[..., :r], x[..., r:2 * r], x[..., 2 * r:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest], -1)


def _down(x, n: int):
    """Row t of the result is row t - n of `x`; zeros above."""
    return jnp.pad(x, ((n, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def cca_qkv(h, p, c, w):
    """Steps 1 to 5 but the rotary embedding: (q (T, Hq, d), k and v
    (T, Hkv, d)).  `p(name)` is a parameter of this layer."""
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    t, g = h.shape[0], hq // hkv
    qt, kt = h @ w(p("wq")), h @ w(p("wk"))
    v = jnp.concatenate([h @ w(p("wv_cur")), _down(h, 1) @ w(p("wv_prev"))],
                        axis=-1).reshape(t, hkv, d)
    qh, kh = qt.reshape(t, hkv, g, d), kt.reshape(t, hkv, d)
    mq = 0.5 * (qh + kh[:, :, None, :])
    mk = 0.5 * (jnp.mean(qh, axis=2) + kh)
    lat = jnp.concatenate([qt, kt], axis=-1)                  # (T, C)
    w0, b0 = w(p("conv0_w")), w(p("conv0_b"))
    w1, b1 = w(p("conv1_w")), w(p("conv1_b"))
    t0, t1 = w0.shape[0], w1.shape[0]
    # conv0 at rows -(t1 - 1) .. T - 1 of the sequence: `t1 - 1` rows
    # above the first see zeros alone and hold the bias
    rows = t + t1 - 1
    top = jnp.pad(lat, ((t1 - 1, 0), (0, 0)))
    c0 = sum(w0[j] * _down(top, t0 - 1 - j) for j in range(t0)) + b0
    c0 = c0.reshape(rows, hq + hkv, d)
    # conv1 at rows 0 .. T - 1: tap j reads conv0's row t - (t1 - 1 - j)
    c1 = sum(jnp.einsum("thc,hcd->thd", c0[j:j + t], w1[j])
             for j in range(t1)) + b1.reshape(hq + hkv, d)
    q = c1[:, :hq] + mq.reshape(t, hq, d)
    k = c1[:, hq:] + mk

    def unit(a):
        return a * (math.sqrt(d) / jnp.linalg.norm(a, axis=-1, keepdims=True))

    return unit(q), unit(k) * w(p("k_scale"))[:, None], v


def _moe(m, p, w):
    """(T, D) -> ((T, D), (T,) probability gap between the routed expert
    and the next): a loop over the experts, each over every row."""
    s = m @ w(p("router.down"))
    s = jax.nn.gelu(s @ w(p("router.w1")) + w(p("router.b1")),
                    approximate=False)
    s = jax.nn.gelu(s @ w(p("router.w2")) + w(p("router.b2")),
                    approximate=False)
    prob = jax.nn.softmax(s @ w(p("router.w3")), axis=-1)        # (T, E)
    top = jnp.sort(prob, axis=-1)[:, ::-1]
    weight = jnp.where(prob >= top[:, :1], prob, 0.0)            # top 1

    def one(acc, e):
        g, u, dn, we = e
        y = (jax.nn.silu(m @ w(g)) * (m @ w(u))) @ w(dn)
        return acc + we[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (p("w_gate"), p("w_in"), p("w_out"), weight.T))
    return out, top[:, 0] - top[:, 1]


def rounded(params: dict) -> dict:
    """`params` at the precision a bf16 deployment serves them in: each
    array rounded on its own, before any program reads it."""
    return {n: a.astype(jnp.bfloat16) for n, a in params.items()}


def hidden_one(params, ids, c):
    """(T,) token ids -> (the normed last hidden state (T, D), the
    routing margins (L, T), the keys and the values (L, T, Hkv, d) a
    cache would hold).  `params` are widened to f32, which is exact."""
    w = lambda a: a.astype(jnp.float32)
    hq, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, t = c["rms_norm_eps"], ids.shape[0]
    cos, sin = rope_tables(t, int(d * c["partial_rotary_factor"]),
                           c["rope_parameters"]["hybrid"]["rope_theta"])
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    x = w(params["tok_emb.table"][ids])
    margins, keys, values = [], [], []
    for i in range(c["num_hidden_layers"]):
        at = lambda n, i=i: params[f"blocks.{i}.attn.{n}"]
        ff = lambda n, i=i: params[f"blocks.{i}.ffn.{n}"]
        h = _rms(x, w(params[f"blocks.{i}.attn_norm.gamma"]), eps)
        q, k, v = cca_qkv(h, at, c, w)
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        keys.append(k)
        values.append(v)
        kr, vr = (jnp.repeat(z, hq // hkv, axis=1) for z in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, kr) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr, vr).reshape(t, hq * d)
        x = x + o @ w(at("o_proj.W"))
        m = _rms(x, w(params[f"blocks.{i}.ffn_norm.gamma"]), eps)
        y, gap = _moe(m, ff, w)
        x = x + y
        margins.append(gap)
    x = _rms(x, w(params["norm_f.gamma"]), eps)
    return x, jnp.stack(margins), jnp.stack(keys), jnp.stack(values)


def logits_one(params, ids, c):
    """((T, vocab) f32 logits through the tied head, margins, keys,
    values): for a vocabulary small enough to hold whole."""
    x, margins, keys, values = hidden_one(params, ids, c)
    return (x @ params["tok_emb.table"].astype(jnp.float32).T, margins,
            keys, values)


def frozen(c: dict):
    """`c`'s keys that the equations read, hashable (a static argument)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "num_hidden_layers", "partial_rotary_factor")
    return tuple((k, c[k]) for k in keys) + (
        ("rope_theta", c["rope_parameters"]["hybrid"]["rope_theta"]),)


def thawed(cf) -> dict:
    c = dict(cf)
    c["rope_parameters"] = {"hybrid": {"rope_theta": c.pop("rope_theta")}}
    return c


@functools.partial(jax.jit, static_argnames=("cf",))
def logits_and_margin(params, ids, cf):
    with jax.default_matmul_precision("highest"):
        return logits_one(params, ids, thawed(cf))


def vocab_blocks(vocab: int, stride: int, most: int = 40000) -> int:
    """Into how many equal blocks of whole strides, of at most `most`
    rows each, the vocabulary divides."""
    for n in range(1, vocab + 1):
        if vocab % n == 0 and (vocab // n) % stride == 0 \
                and vocab // n <= most:
            return n
    raise ValueError(f"a vocabulary of {vocab} rows does not divide into "
                     f"blocks of whole strides of {stride}")


@functools.partial(jax.jit, static_argnames=("cf", "stride"))
def _gaps(params, ids, got, cf, stride):
    """The head in blocks of the vocabulary, so that a (T, vocab) f32
    array never exists: per block the running best logit, the logit of
    the next token where it falls in the block, and the sums of the
    comparison with `got` on every `stride`-th column."""
    with jax.default_matmul_precision("highest"):
        x, margins, keys, values = hidden_one(params, ids, thawed(cf))
        table = params["tok_emb.table"]
        vocab, t = table.shape[0], ids.shape[0]
        n = vocab_blocks(vocab, stride)
        size = vocab // n
        nxt = jnp.concatenate([ids[1:], ids[:1]])

        def block(carry, e):
            best, picked, num, den = carry
            rows, seen, j = e
            lg = x @ rows.astype(jnp.float32).T               # (T, size)
            local = nxt - j * size
            here = (local >= 0) & (local < size)
            at = jnp.take_along_axis(
                lg, jnp.clip(local, 0, size - 1)[:, None], axis=1)[:, 0]
            ref = lg[:, ::stride]
            return (jnp.maximum(best, lg.max(-1)),
                    jnp.where(here, at, picked),
                    num + jnp.sum((seen - ref) ** 2, -1),
                    den + jnp.sum(ref ** 2, -1)), None

        zero = jnp.zeros((t,), jnp.float32)
        (best, picked, num, den), _ = jax.lax.scan(
            block, (jnp.full((t,), -jnp.inf), zero, zero, zero),
            (table.reshape(n, size, -1),
             jnp.moveaxis(got.reshape(t, n, size // stride), 1, 0),
             jnp.arange(n)))
    return (best - picked)[:-1], margins, best[:-1], jnp.sqrt(num / den), \
        keys, values


def greedy_gap(params, seq, prompt_len, pad_to, c, delta, tolerance,
               got=None, stride=1) -> dict:
    """One teacher-forced pass over `seq` padded to `pad_to` (one
    shape).  Over the positions that produced `seq[prompt_len:]`: the
    gap between the reference's best logit and its logit of the token
    the system served.  `checked` positions have a routing margin of at
    least `delta` in every layer; `over` of them have a gap beyond
    `tolerance` and `gap` is their largest; `unsure` positions lie
    under `delta` (`gap_unsure`: their largest gap).  `top` is the
    largest best logit; `bands` gives (margin's upper edge, positions,
    largest gap) by band of the margin.

    `got`, where given: the system's own logits of every position of
    `seq`, every `stride`-th column of the vocabulary, (len(seq),
    vocab / stride).  `err` is then, per position, the norm of (`got` -
    the reference's logits) over the norm of the reference's, on those
    columns.  `margins` (L, len(seq)), `keys` and `values` (L,
    len(seq), Hkv, d) are the reference's own, for a comparison with
    what a cache holds."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    vocab = params["tok_emb.table"].shape[0]
    padded = np.zeros((pad_to, vocab // stride), np.float32)
    if got is not None:
        padded[:len(seq)] = got
    gap, margins, best, err, keys, values = (
        np.asarray(a) for a in _gaps(params, ids, padded, frozen(c), stride))
    served = slice(prompt_len - 1, len(seq) - 1)
    margin = margins.min(0)
    gap, margin = gap[served], margin[served]
    sure = margin >= delta
    worst = lambda g: float(g.max()) if g.size else 0.0
    edges = [0.0, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, np.inf]
    bands = [(hi, int(((margin >= lo) & (margin < hi)).sum()),
              round(worst(gap[(margin >= lo) & (margin < hi)]), 5))
             for lo, hi in zip(edges, edges[1:])]
    found = {"gap": worst(gap[sure]), "gap_unsure": worst(gap[~sure]),
             "checked": int(sure.sum()), "unsure": int((~sure).sum()),
             "over": int((gap[sure] > tolerance).sum()),
             "over_unsure": int((gap[~sure] > tolerance).sum()),
             "top": float(best[served].max()), "bands": bands,
             "margins": margins[:, :len(seq)], "keys": keys[:, :len(seq)],
             "values": values[:, :len(seq)]}
    if got is not None:
        found["err"] = err[:len(seq)]
    return found
