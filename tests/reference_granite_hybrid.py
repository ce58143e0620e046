"""Plain reference of the Granite 4.0-H decoder (`granitemoehybrid`: Mamba-2
state-space layers, arXiv:2405.21060, to one attention layer without
positional embedding, over a mixture of experts with a shared MLP): f32
`jax.numpy`, matmuls at "highest" precision, no cache, no chunks, no
batching; the state-space layer as the row-by-row recurrence, the
convolution as explicit shifted sums.

On a sequence of T rows, with `eps` = `rms_norm_eps`:

    h = tok_emb[ids] * embedding_multiplier
    per layer:  h += residual_multiplier * Mixer(RMSNorm(h))
                x = RMSNorm(h);  h += residual_multiplier * (MoE(x) + Shared(x))
    logits = RMSNorm(h) tok_emb^T / logits_scaling          (tied head)

`attention`: `q = x Wq` (heads of `hidden_size / num_attention_heads`),
`k, v = x Wk, x Wv` (`num_key_value_heads` heads), no bias, no
positional embedding of any kind, causal softmax of `q.k *
attention_multiplier`, grouped-query, then `Wo`.

`mamba`, with `d_inner = mamba_expand * hidden_size = mamba_n_heads *
mamba_d_head`, N = `mamba_d_state`, one group: `z, xBC, dt = split(x
W_in, [d_inner, d_inner + 2 N, heads])`; `xBC_t = silu(sum_j w_j *
xBC_{t - (K-1) + j} + b)` over the K = `mamba_d_conv` taps, zeros before
position 0; `x, B, C = split(xBC, [d_inner, N, N])`; `dt = softplus(dt
+ dt_bias)`, `A = -exp(A_log)`; per head, with S (d_head, N):
`S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t = S_t C_t + D x_t`;
`y = RMSNorm(y * silu(z)) * norm_w` over the d_inner values; `y W_out`.

`MoE`: `logits = x W_r` over all the router's outputs; the
`num_experts_per_tok` largest; gates = softmax over those; expert e
gives `(silu(x G_e) * (x U_e)) D_e`.  The configuration holds a share
of the experts (`deployment.experts_held`: the ids of the stacks' rows,
in order): the gates are computed over all of the router's experts and
only the held ones' products are summed.  `Shared`: the same gated form,
every token.

It reads the program's parameters by name and imports nothing from the
program.  `c` holds the source's keys.  Where the configuration serves
its weights in bf16 the caller hands in `rounded(params)`: the masters
rounded one array at a time outside the program that reads them (the
TPU's compiler keeps the excess precision of a rounding made inside).
Beside the logits: per layer and position the gap between the last
routed expert's logit and the next one's (where that is tiny a program
that rounds its activations may route the other expert with neither
being wrong), per attention layer the keys and values a cache would
hold, and per mamba layer the state S and the convolution's window as
they stand after the sequence's last valid row.

`benchmark/reference_granite_hybrid.py` and
`tests/reference_granite_hybrid.py` are one file twice (the benchmark
may not import from `tests/`, nor the tests from `benchmark/`);
`tests/test_granite_hybrid.py` holds them to the same text.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _down(x, n: int):
    """Row t of the result is row t - n of `x`; zeros above."""
    return jnp.pad(x, ((n, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def _gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def held_experts(c: dict):
    """Ids of the experts whose stacks the configuration holds."""
    return tuple(c.get("deployment", {}).get(
        "experts_held", range(c["num_local_experts"])))


def mamba(h, p, c, w, n_valid):
    """(T, D) -> ((T, D), S (heads, d_head, N) and the convolution's
    window (K - 1, conv_dim) after row `n_valid - 1`)."""
    heads, dh, n, k = (c["mamba_n_heads"], c["mamba_d_head"],
                       c["mamba_d_state"], c["mamba_d_conv"])
    di, t = heads * dh, h.shape[0]
    z, xbc, dt = jnp.split(h @ w(p("in_proj.W")), [di, 2 * di + 2 * n],
                           axis=-1)
    taps, bias = w(p("conv_w")), w(p("conv_b"))
    # the window after the last valid row: the rows before the convolution
    window = jax.lax.dynamic_slice_in_dim(
        jnp.pad(xbc, ((k - 1, 0), (0, 0))), n_valid, k - 1, axis=0)
    xbc = jax.nn.silu(sum(taps[j] * _down(xbc, k - 1 - j) for j in range(k))
                      + bias)
    x, b, cc = jnp.split(xbc, [di, di + n], axis=-1)
    x = x.reshape(t, heads, dh)
    dt = jax.nn.softplus(dt + w(p("dt_bias")))                  # (T, heads)
    a, d = -jnp.exp(w(p("A_log"))), w(p("D"))

    def row(s, e):
        x_t, dt_t, b_t, c_t, live = e
        new = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        y = jnp.sum(new * c_t[None, None, :], axis=-1) + d[:, None] * x_t
        return jnp.where(live, new, s), y

    s, y = jax.lax.scan(row, jnp.zeros((heads, dh, n), jnp.float32),
                        (x, dt, b, cc, jnp.arange(t) < n_valid))
    y = _rms(y.reshape(t, di) * jax.nn.silu(z), w(p("norm_w")),
             c["rms_norm_eps"])
    return y @ w(p("out_proj.W")), s, window


def attention(h, p, c, w):
    """(T, D) -> ((T, D), keys and values (T, Hkv, d))."""
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    d, t = c["hidden_size"] // hq, h.shape[0]
    q = (h @ w(p("q_proj.W"))).reshape(t, hq, d)
    k = (h @ w(p("k_proj.W"))).reshape(t, hkv, d)
    v = (h @ w(p("v_proj.W"))).reshape(t, hkv, d)
    kr, vr = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, kr) * c["attention_multiplier"]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr, vr).reshape(t, hq * d)
    return o @ w(p("o_proj.W")), k, v


def moe(m, p, c, w):
    """(T, D) -> (the held experts' part of the routed sum (T, D), the
    (T,) gap between the last routed logit and the next)."""
    logits = m @ w(p("router"))                                  # (T, E)
    k = c["num_experts_per_tok"]
    top = jnp.sort(logits, axis=-1)[:, ::-1]
    routed = logits >= top[:, k - 1:k]
    gates = jax.nn.softmax(jnp.where(routed, logits, -jnp.inf), axis=-1)
    gates = gates[:, jnp.asarray(held_experts(c))]               # (T, held)

    def one(acc, e):
        g, u, dn, we = e
        return acc + we[:, None] * _gated(m, w(g), w(u), w(dn)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (p("w_gate"), p("w_in"), p("w_out"), gates.T))
    return out, top[:, k - 1] - top[:, k]


def rounded(params: dict) -> dict:
    """`params` at the precision a bf16 deployment serves them in: each
    array rounded on its own, before any program reads it."""
    return {n: a.astype(jnp.bfloat16) for n, a in params.items()}


def hidden_one(params, ids, c, n_valid=None):
    """(T,) token ids -> (the normed last hidden state (T, D), the
    routing margins (L, T), the attention layers' keys and values
    (La, T, Hkv, d), the mamba layers' S (Lm, heads, d_head, N) and
    windows (Lm, K - 1, conv_dim) after row `n_valid - 1`, default the
    last).  `params` are widened to f32, which is exact."""
    w = lambda a: a.astype(jnp.float32)
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    n_valid = ids.shape[0] if n_valid is None else n_valid
    x = w(params["tok_emb.table"][ids]) * c["embedding_multiplier"]
    margins, keys, values, states, windows = [], [], [], [], []
    for i in range(c["num_hidden_layers"]):
        at = lambda n, i=i: params[f"blocks.{i}.{n}"]
        mx = lambda n, i=i: params[f"blocks.{i}.mixer.{n}"]
        ff = lambda n, i=i: params[f"blocks.{i}.ffn.{n}"]
        h = _rms(x, w(at("mixer_norm.gamma")), eps)
        if c["layer_types"][i] == "mamba":
            y, s, win = mamba(h, mx, c, w, n_valid)
            states.append(s)
            windows.append(win)
        else:
            y, k, v = attention(h, mx, c, w)
            keys.append(k)
            values.append(v)
        x = x + r * y
        m = _rms(x, w(at("ffn_norm.gamma")), eps)
        y, gap = moe(m, ff, c, w)
        shared = _gated(m, w(at("shared.gate.W")), w(at("shared.up.W")),
                        w(at("shared.down.W")))
        x = x + r * (y + shared)
        margins.append(gap)
    x = _rms(x, w(params["norm_f.gamma"]), eps)
    stack = lambda a: jnp.stack(a) if a else jnp.zeros((0,), jnp.float32)
    return (x, jnp.stack(margins), stack(keys), stack(values), stack(states),
            stack(windows))


def logits_one(params, ids, c, n_valid=None):
    """((T, vocab) f32 logits through the tied head, then `hidden_one`'s
    other results): for a vocabulary small enough to hold whole."""
    x, *rest = hidden_one(params, ids, c, n_valid)
    return (x @ params["tok_emb.table"].astype(jnp.float32).T
            / c["logits_scaling"], *rest)


_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "attention_multiplier",
         "embedding_multiplier", "residual_multiplier", "logits_scaling",
         "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
         "mamba_d_conv", "num_local_experts", "num_experts_per_tok")


def frozen(c: dict):
    """`c`'s keys that the equations read, hashable (a static argument)."""
    return tuple((k, c[k]) for k in _KEYS) + (
        ("layer_types", tuple(c["layer_types"][:c["num_hidden_layers"]])),
        ("experts_held", held_experts(c)))


def thawed(cf) -> dict:
    c = dict(cf)
    c["deployment"] = {"experts_held": c.pop("experts_held")}
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
def _gaps(params, ids, n_valid, got, cf, stride):
    """The head in blocks of the vocabulary, so that a (T, vocab) f32
    array never exists: per block the running best logit, the logit of
    the next token where it falls in the block, and the sums of the
    comparison with `got` on every `stride`-th column."""
    with jax.default_matmul_precision("highest"):
        c = thawed(cf)
        x, margins, keys, values, states, windows = hidden_one(
            params, ids, c, n_valid)
        table = params["tok_emb.table"]
        vocab, t = table.shape[0], ids.shape[0]
        n = vocab_blocks(vocab, stride)
        size = vocab // n
        nxt = jnp.concatenate([ids[1:], ids[:1]])

        def block(carry, e):
            best, picked, num, den = carry
            rows, seen, j = e
            lg = x @ rows.astype(jnp.float32).T / c["logits_scaling"]
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
        keys, values, states, windows


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
    columns.  `margins` (L, len(seq)), `keys` and `values` (La,
    len(seq), Hkv, d), and `states` (Lm, heads, d_head, N) and
    `windows` (Lm, K - 1, conv_dim) after the last token of `seq` are
    the reference's own, for a comparison with what an engine holds."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    vocab = params["tok_emb.table"].shape[0]
    padded = np.zeros((pad_to, vocab // stride), np.float32)
    if got is not None:
        padded[:len(seq)] = got
    gap, margins, best, err, keys, values, states, windows = (
        np.asarray(a) for a in _gaps(params, ids, np.int32(len(seq)), padded,
                                     frozen(c), stride))
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
             "values": values[:, :len(seq)], "states": states,
             "windows": windows}
    if got is not None:
        found["err"] = err[:len(seq)]
    return found
