"""Plain reference of the Mistral/Llama decoder block: f32 `jax.numpy`,
matmuls at "highest" precision, no cache, no kernel, no batching.

RMSNorm, half-split RoPE, grouped-query attention under a causal and
sliding-window mask, SwiGLU, untied head, as the source's modelling code
has them.  It reads the program's parameters by name and shares no code
with it.  Weights are rounded to bf16 and back inside the jit, one use
at a time, because that is the precision both configurations state; a
second whole f32 copy would not fit beside the masters.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _w(params, name):
    return params[name].astype(jnp.bfloat16).astype(jnp.float32)


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _rope(x, theta):
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def logits_one(params, ids, c):
    """(T,) token ids -> (T, vocab) f32 logits; `c` has the source's keys."""
    heads, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd, eps = c["hidden_size"] // heads, c["rms_norm_eps"]
    t = ids.shape[0]
    x = _w(params, "tok_emb.table")[ids]
    q_pos, k_pos = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = k_pos <= q_pos
    if c.get("sliding_window"):
        mask &= k_pos > q_pos - c["sliding_window"]
    for i in range(c["num_hidden_layers"]):
        p = f"blocks.{i}."
        h = _rms(x, params[p + "attn_norm.gamma"], eps)
        q = _rope((h @ _w(params, p + "attn.q_proj.W")).reshape(t, heads, hd),
                  c["rope_theta"])
        k = _rope((h @ _w(params, p + "attn.k_proj.W")).reshape(t, kvh, hd),
                  c["rope_theta"])
        v = (h @ _w(params, p + "attn.v_proj.W")).reshape(t, kvh, hd)
        k, v = (jnp.repeat(a, heads // kvh, axis=1) for a in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        w = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, v).reshape(t, heads * hd)
        x = x + o @ _w(params, p + "attn.o_proj.W")
        h = _rms(x, params[p + "ffn_norm.gamma"], eps)
        x = x + (jax.nn.silu(h @ _w(params, p + "ffn.gate.W"))
                 * (h @ _w(params, p + "ffn.up.W"))) \
            @ _w(params, p + "ffn.down.W")
    return _rms(x, params["norm_f.gamma"], eps) @ _w(params, "lm_head.W")


def _frozen(c):
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size",
            "rms_norm_eps", "num_hidden_layers", "rope_theta",
            "sliding_window")
    return tuple((k, c.get(k)) for k in keys)


@functools.partial(jax.jit, static_argnames=("cf",))
def _gap(params, ids, first, last, cf):
    with jax.default_matmul_precision("highest"):
        lg = logits_one(params, ids, dict(cf))
    rows = jnp.arange(ids.shape[0] - 1)
    gap = lg[:-1].max(-1) - lg[rows, ids[1:]]
    return jnp.where((rows >= first) & (rows < last), gap, 0.0).max()


def greedy_gap(params, seq, prompt_len, pad_to, c) -> float:
    """Largest gap between the reference's best logit and its logit of
    the token the system served, over `seq[prompt_len:]`, in one
    teacher-forced pass over `seq` padded to `pad_to` (one shape)."""
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    return float(_gap(params, ids, prompt_len - 1, len(seq) - 1, _frozen(c)))


@functools.partial(jax.jit, static_argnames=("cf", "wrt"))
def _loss_and_grad(params, ids, cf, wrt):
    @jax.checkpoint             # one row's activations at a time
    def row(w, r):
        lg = logits_one({**params, wrt: w}, r, dict(cf))[:-1]
        return jnp.mean(jax.nn.logsumexp(lg, -1)
                        - lg[jnp.arange(r.shape[0] - 1), r[1:]])
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda w: jnp.mean(jax.lax.map(
            functools.partial(row, w), ids)))(params[wrt])


def loss_and_grad(params, ids, c, wrt):
    """Mean next-token cross-entropy of the (B, T) batch `ids` and its
    gradient with respect to the parameter named `wrt`."""
    loss, grad = _loss_and_grad(params, jnp.asarray(ids), _frozen(c), wrt)
    return float(loss), grad
