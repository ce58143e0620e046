"""Mixture-of-Experts with expert parallelism (the 'expert' mesh axis).

Not a reference capability (SURVEY.md §2.3: the reference's only
strategy is DP) — this is the TPU-native extension that completes the
framework's parallelism axes (dp/tp/sp/pp/ep).  Formulation follows the
GShard/Switch static-shape recipe, which is what XLA partitions well:

  * router: (N, D) -> (N, E) logits -> top-k gate with a static expert
    capacity C = ceil(cf * N / E).  The logits come from the `router`
    argument: a (D, E) matrix (a linear router) or a function of the
    rows (`mlp_router_logits` behind `layer.MLPRouter`);
  * dispatch: two equivalent token-movement formulations sharing one
    router (`_route`): gather/SCATTER into the (E, C, D) buffers
    (O(k*N*D) memory ops — the single-chip default; the one-hot
    einsums cost O(cf*k*N^2*D) MAC, quadratic in tokens, and were the
    whole 0.16-MFU story on chip in r4) and the one-hot EINSUM form
    (the EP default: GSPMD partitions it into all-to-alls over ICI).
    NO dynamic shapes in either; dropped tokens (over capacity) pass
    through with zero expert contribution;
  * expert compute: (E, C, D) batched einsums over stacked expert
    weights, leading E axis sharded over the 'expert' mesh axis;
  * combine: gate-weighted gather back to (N, D).

Everything is pure jnp (fwd differentiates via jax.vjp), so the whole
MoE layer compiles into the model's single step module like any other
op; router load-balance auxiliary loss follows Switch (mean fraction *
mean probability per expert).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["moe_dispatch", "moe_forward", "load_balance_loss",
           "mlp_router_logits"]


def _router_logits(xf, router, precision=None):
    """(N, E) f32 routing logits of the rows `xf`.  `router` is the
    (D, E) matrix of a linear router, or a function of the rows that
    yields the logits itself (an MLP router)."""
    if callable(router):
        return router(xf)
    return jnp.matmul(xf.astype(jnp.float32), router.astype(jnp.float32),
                      precision=precision)


def mlp_router_logits(xf, w_down, w1, b1, w2, b2, w3):
    """Routing logits of an MLP router: the rows projected down to the
    router's width, two gelu layers with biases there, then one logit
    an expert: `w3 gelu(w2 gelu(w1 (x w_down) + b1) + b2)`.  f32 at
    "highest", as the linear router's logits are: a top-1 pick between
    near ties must not hang on a bf16 product."""
    hi = dict(precision=jax.lax.Precision.HIGHEST)
    f32 = lambda a: a.astype(jnp.float32)
    s = jnp.matmul(f32(xf), f32(w_down), **hi)
    s = jax.nn.gelu(jnp.matmul(s, f32(w1), **hi) + f32(b1),
                    approximate=False)
    s = jax.nn.gelu(jnp.matmul(s, f32(w2), **hi) + f32(b2),
                    approximate=False)
    return jnp.matmul(s, f32(w3), **hi)


def moe_dispatch(logits, capacity: int, k: int = 1):
    """Top-k routing with static capacity (k=1: Switch; k=2: GShard).

    logits: (N, E).  Returns (combine (N, E, C) f32, probs (N, E),
    onehot (N, E) of the FIRST choice — the balance loss follows the
    primary assignment).  combine[n, e, c] is token n's gate weight at
    slot c of expert e (0 everywhere else; 0 for dropped assignments).
    Gates renormalize over the k selected experts; capacity slots fill
    rank-major (every token's first choice outranks any second choice,
    the GShard priority)."""
    N, E = logits.shape
    # one router for both dispatch formulations (_route): identical
    # softmax/top-k/gating/rank-major slot positions as the scatter path
    e_flat, gate_flat, pos, keep, probs, onehot = _route(logits, capacity, k)
    oh = jax.nn.one_hot(e_flat, E, dtype=jnp.float32)  # (k*N, E)
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                          dtype=jnp.float32)           # (k*N, C)
    contrib = (oh * (gate_flat * keep)[:, None])[:, :, None] \
        * slot[:, None, :]                             # (k*N, E, C)
    combine = jnp.sum(contrib.reshape(k, N, E, capacity), axis=0)
    return combine, probs, onehot


def load_balance_loss(probs, onehot):
    """Switch aux loss: E * sum_e mean_n(frac_e) * mean_n(prob_e)."""
    E = probs.shape[-1]
    frac = jnp.mean(onehot, axis=0)
    prob = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac * prob)


def _topk_gates(logits, k: int):
    """(probs (N, E) f32, topi (N, k), gates (N, k)): softmax in f32, the
    k largest, their weights renormalised to sum 1 when k > 1.  A top-1
    gate is the chosen expert's probability itself (renormalised it
    would be the constant 1, and the router would get no gradient)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    gates = topv if k == 1 else \
        topv / jnp.sum(topv, axis=-1, keepdims=True)
    return probs, topi, gates


def _route(logits, capacity: int, k: int):
    """Shared top-k routing state, rank-major (GShard priority: every
    token's first choice outranks any second choice for a slot).

    Returns (e_flat (k*N,) expert ids, gate_flat (k*N,) f32 gates,
    pos (k*N,) slot index within the expert, keep (k*N,) bool,
    probs (N, E), onehot (N, E) of the first choice)."""
    N, E = logits.shape
    probs, topi, gates = _topk_gates(logits, k)        # (N, k)
    e_flat = topi.T.reshape(-1)                        # rank-major (k*N,)
    oh = jax.nn.one_hot(e_flat, E, dtype=jnp.float32)
    pos = jnp.sum(jnp.cumsum(oh, axis=0) * oh - oh, axis=-1)  # (k*N,)
    keep = pos < capacity
    onehot = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    return e_flat, gates.T.reshape(-1), pos, keep, probs, onehot


_warned_auto_trace = False


def _warn_auto_under_trace(x, resolved: str) -> None:
    """dispatch_mode='auto' resolved the global mesh while tracing: the
    choice is baked into this jit cache entry and will NOT re-resolve if
    the mesh changes later (the cache is not keyed on the mesh global).
    Warn ONCE per process so raw-jit users learn to pass an explicit
    mode; the model executor re-traces per compile and is fine."""
    global _warned_auto_trace
    if _warned_auto_trace or not isinstance(x, jax.core.Tracer):
        return
    _warned_auto_trace = True
    import warnings
    warnings.warn(
        f"moe_forward(dispatch_mode='auto') resolved to {resolved!r} at "
        "trace time from the global mesh; the jit cache is not keyed on "
        "that global, so a later set_mesh() will NOT re-route already-"
        "jitted callers.  Pass dispatch_mode='scatter'/'einsum' "
        "explicitly when jitting moe_forward directly around mesh "
        "changes.", stacklevel=3)


def _expert_ffn(buf, w_in, w_out, w_gate):
    """(E, C, D) expert buffers -> (E, C, D) outputs (relu or SwiGLU)."""
    up = jnp.einsum("ecd,edh->ech", buf, w_in.astype(buf.dtype))
    if w_gate is not None:
        h = jax.nn.silu(jnp.einsum("ecd,edh->ech", buf,
                                   w_gate.astype(buf.dtype))) * up
    else:
        h = jax.nn.relu(up)
    return jnp.einsum("ech,ehd->ecd", h, w_out.astype(buf.dtype))


def _moe_dropless(xf, router, w_in, w_out, w_gate, top_k, experts_held=None):
    """(N, D) rows -> ((N, D) f32, balance loss): exact top-k, every
    assignment computed: every expert over every row, the unrouted ones
    weighted 0.  No buffer, no capacity, row n's result a function of
    row n alone.

    `experts_held`: the ids of the experts whose weights the stacks
    hold, in the stacks' order, where that is a share of the experts
    the router routes over (one chip's share of a layer that several
    chips divide).  Routing, top-k and the gates are over all of the
    router's experts; the result is the held experts' part of the sum,
    and what the others would have added is left out.

    Right where a dispatch holds a few rows an expert (serving: 32
    rows, top 8 of 64), because the matmuls are then weight streaming
    whatever the rows: on one v5e chip 1.07 ms a layer of 64 x 3 x
    2304 x 896 bf16, 90% of the HBM peak, against 1.10 ms for the
    scatter path at capacity = N and 4.69 ms for a sort and
    `jax.lax.ragged_dot` (PERF.md, PR 28).  At training's row counts it
    multiplies E / k times too many rows: that path keeps capacity."""
    N = xf.shape[0]
    with jax.named_scope("moe.route"):
        logits = _router_logits(xf, router, jax.lax.Precision.HIGHEST)
        E = logits.shape[-1]
        probs, topi, gates = _topk_gates(logits, top_k)
        w = jnp.zeros((N, E), jnp.float32).at[
            jnp.arange(N)[:, None], topi].set(gates)   # (N, E), k nonzero
        if experts_held is not None:
            w = w[:, jnp.asarray(experts_held)]
    f32 = dict(preferred_element_type=jnp.float32)
    with jax.named_scope("moe.experts"):
        h = jnp.einsum("nd,edh->enh", xf, w_in.astype(xf.dtype), **f32)
        if w_gate is not None:
            h = jax.nn.silu(jnp.einsum("nd,edh->enh", xf,
                                       w_gate.astype(xf.dtype), **f32)) * h
        else:
            h = jax.nn.relu(h)
        # the gate weight rides the hidden row, so the down projection
        # and the sum over experts are one contraction over (e, h)
        h = (h * w.T[:, :, None]).astype(xf.dtype)
        out = jnp.einsum("enh,ehd->nd", h, w_out.astype(xf.dtype), **f32)
    onehot = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    return out, load_balance_loss(probs, onehot)


def moe_forward(x, router, w_in, w_out, capacity_factor: float = 1.25,
                return_aux: bool = False, top_k: int = 1, w_gate=None,
                dispatch_mode: str = "auto", dropless: bool = False,
                experts_held=None):
    """Top-k MoE FFN over flattened tokens (k=1 Switch, k=2 GShard).

    x: (..., D); router: (D, E), or a function (N, D) -> (N, E) f32
    logits (`_router_logits`); w_in: (E, D, H); w_out: (E, H, D).
    Expert e computes relu(x @ w_in[e]) @ w_out[e] — or, with `w_gate`
    (E, D, H) given, the SwiGLU form silu(x @ w_gate[e]) * (x @
    w_in[e]) @ w_out[e] (Mixtral-style experts).  Shard the stacked
    weights' leading axis over the 'expert' mesh axis (SHARD_RULES)
    for EP.

    dispatch_mode:
      * 'scatter' — gather/scatter token movement: O(k*N*D) memory ops
        into the (E, C, D) buffers and back.  Default off-mesh: the
        one-hot einsums below cost O(cf*k*N^2*D) MAC each — quadratic
        in token count and pure overhead (r4 on-chip MoE MFU 0.1585;
        scatter dispatch removed the einsums' N^2 term, r5).
      * 'einsum' — GShard one-hot dispatch/combine einsums.  Default
        when an 'expert' mesh axis is live: GSPMD partitions einsums
        over E into all-to-alls cleanly, which is the EP wire format.
      * 'auto' — scatter without an EP axis, einsum with one.
        CAVEAT: 'auto' reads the global `parallel.mesh.current_mesh()`
        AT TRACE TIME, and the jit cache is NOT keyed on that global —
        a function jitted before the 'expert' mesh is installed stays
        cached on the scatter path (numerics identical; the einsum
        all-to-all wire format is what's silently missed).  The model
        executor re-traces per compile so it is unaffected, but code
        that jits `moe_forward` directly around mesh changes should
        pass an explicit mode (the `MoE` layer forwards its
        `dispatch_mode` argument for exactly this).  A one-time warning
        fires when 'auto' resolves under a trace.

    Both modes share `_route` (identical routing, gating, capacity
    drops) and are equivalence-tested against each other.

    dropless: exact top-k, every assignment computed, a row's result
    independent of what else the batch holds: what serving needs (a
    capacity drop there silently changes a served token).  It takes the
    place of capacity and `dispatch_mode`.  `experts_held`, dropless
    only: the stacks hold those of the router's experts and no others
    (`_moe_dropless`)."""
    orig_shape = x.shape
    D = orig_shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    E = w_in.shape[0]
    # capacity covers the k-fold assignment load at the same factor
    capacity = max(1, math.ceil(capacity_factor * top_k * N / E))

    if dropless:
        out, aux = _moe_dropless(xf, router, w_in, w_out, w_gate, top_k,
                                 experts_held)
        out = out.astype(xf.dtype).reshape(orig_shape)
        return (out, aux) if return_aux else out
    logits = _router_logits(xf, router)
    if dispatch_mode == "auto":
        from ..parallel import mesh as mesh_mod
        m = mesh_mod.current_mesh()
        ep = m is not None and m.shape.get("expert", 1) > 1
        dispatch_mode = "einsum" if ep else "scatter"
        _warn_auto_under_trace(x, dispatch_mode)

    if dispatch_mode == "scatter":
        e_flat, gate_flat, pos, keep, probs, onehot = _route(
            logits, capacity, top_k)
        # dropped assignments write out of bounds -> mode='drop' elides
        pos_i = jnp.where(keep, pos, capacity).astype(jnp.int32)
        tok = jnp.tile(jnp.arange(N), top_k)
        xs = xf[tok]                                   # (k*N, D)
        buf = jnp.zeros((E, capacity, D), xf.dtype) \
            .at[e_flat, pos_i].set(xs, mode="drop")
        y = _expert_ffn(buf, w_in, w_out, w_gate)      # (E, C, D)
        # combine: gather each assignment's expert output, gate, sum k
        w = (gate_flat * keep).astype(xf.dtype)
        out_a = y[e_flat, jnp.clip(pos_i, 0, capacity - 1)] * w[:, None]
        out = jnp.sum(out_a.reshape(top_k, N, D), axis=0)
    else:
        combine, probs, onehot = moe_dispatch(logits, capacity, top_k)
        dispatch = (combine > 0).astype(xf.dtype)      # (N, E, C)
        # dispatch tokens into per-expert buffers: (E, C, D)
        buf = jnp.einsum("nec,nd->ecd", dispatch, xf)
        y = _expert_ffn(buf, w_in, w_out, w_gate)
        # gate-weighted combine back to tokens
        out = jnp.einsum("nec,ecd->nd", combine.astype(xf.dtype), y)
    out = out.astype(xf.dtype).reshape(orig_shape)
    if return_aux:
        return out, load_balance_loss(probs, onehot)
    return out
