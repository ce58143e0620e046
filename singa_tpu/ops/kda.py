"""Kimi delta attention (KDA; Kimi Linear, arXiv:2510.26692): a delta
rule whose state decays channel by channel along the key, in the two
forms a server needs and that must agree.

Per head, with a key ``k_t`` (dk,) of unit norm, a query ``q_t`` (dk,),
a value ``v_t`` (dv,), a write strength ``beta_t`` in (0, 1) and a log
decay ``g_t`` (dk,) <= 0 a channel, the state ``S`` (dk, dv) obeys

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

the state decayed, then erased along ``k_t``, then written.

* :func:`kda_step` is that recurrence for one row of every batch row:
  what a decode tick runs over every slot.  On a TPU it is one Pallas
  kernel (``kda_decode``) that reads each state once and writes it
  once; elsewhere elementwise products and sums in f32.
* :func:`kda_chunked` is the same over a sequence in chunks of
  ``chunk`` rows (the WY / UT form of DeltaNet, arXiv:2406.06484, with
  the decay per channel).  Inside a chunk, with ``G_t`` the cumulative
  sum of ``g`` from the chunk's first row to row t and the entry state
  ``S_0``, the state is ``S_t = Diag(exp(G_t)) S_0 + sum_{s<=t}
  Diag(exp(G_t - G_s)) k_s w_s^T`` where the written values ``w`` solve
  the unit lower-triangular system ``(I + Diag(beta) A) W = Diag(beta)
  (V - (K * exp(G)) S_0)``, ``A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] -
  G_s[c])`` for s < t.  Then ``O = (Q * exp(G)) S_0 + B W`` with ``B``
  the same product of q against k for s <= t, and the chunk leaves
  ``S_C``.  The system is solved by block forward substitution, whose
  products are at "highest" precision by construction.  The decays
  always enter as ``exp(G_t - G_s)`` with t at or
  after s, which is at most 1: at the gate's lower bound of -5 a token,
  ``G`` reaches -320 over 64 rows, and ``exp(-G)`` would overflow.  The
  products are formed in sub-chunks of ``sub`` rows: a row against the
  rows of earlier sub-chunks through the cumulative decay at its own
  sub-chunk's start, ``exp(G_t - G_r) exp(G_r - G_s)`` (both factors at
  most 1: a matrix product), and against its own sub-chunk pair by pair.

The state and every sum are f32, and the matrix products of the chunked
form run at "highest" precision whatever the caller's: a state carried
over thousands of tokens keeps what a bf16 pass would round away.  It
yields the state after each of ``state_rows`` where asked: a paged
engine needs it after the chunk's last valid row and after at most one
more (``ops/ssm.py`` does the same for Mamba-2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import on_tpu

__all__ = ["kda_gate", "l2_normalize", "kda_step", "kda_decode",
           "kda_chunked"]

_HI = jax.lax.Precision.HIGHEST
_f32 = lambda a: a.astype(jnp.float32)
#: heads one step of the decode kernel updates
_HEADS = 16


def kda_gate(f, A_log, dt_bias, lower_bound: float):
    """The lower-bounded per-channel log decay: ``lower_bound *
    sigmoid(exp(A_log)_h * (f + dt_bias))`` in (lower_bound, 0).  ``f``
    (B, T, H, dk), ``A_log`` (H,), ``dt_bias`` (H * dk,) -> f32."""
    B, T, H, dk = f.shape
    z = _f32(f) + _f32(dt_bias).reshape(H, dk)
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(_f32(A_log))[:, None] * z)


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in f32."""
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def kda_step(q, k, v, g, beta, S):
    """One row a batch row, in XLA.  ``q``, ``k``, ``g`` (B, H, dk),
    ``v`` (B, H, dv), ``beta`` (B, H), ``S`` (B, H, dk, dv) f32 -> (o
    (B, H, dv) f32, the new S).  Elementwise products and sums only:
    f32 whatever the matmul precision."""
    Sd = S * jnp.exp(_f32(g))[..., None]
    kS = jnp.sum(_f32(k)[..., None] * Sd, axis=-2)
    u = _f32(beta)[..., None] * (_f32(v) - kS)
    S = Sd + _f32(k)[..., None] * u[..., None, :]
    return jnp.sum(_f32(q)[..., None] * S, axis=-2), S


def _decode_kernel(q_ref, k_ref, bk_ref, eg_ref, v_ref, s_ref, o_ref, s_out):
    # a block of heads of one slot: the vectors along the key come in as
    # rows (heads, dk) and go down the sublanes, one column a head, to
    # scale, erase and write the head's (dk, dv) state along its rows
    qT, kT, bkT, egT = (r[0].T for r in (q_ref, k_ref, bk_ref, eg_ref))
    v = v_ref[0]
    rows = []
    for h in range(q_ref.shape[1]):
        S = s_ref[0, h] * egT[:, h:h + 1]
        kS = jnp.sum(S * kT[:, h:h + 1], axis=0, keepdims=True)
        S = S + bkT[:, h:h + 1] * (v[h:h + 1] - kS)
        s_out[0, h] = S
        rows.append(jnp.sum(S * qT[:, h:h + 1], axis=0, keepdims=True))
    o_ref[0] = jnp.concatenate(rows, axis=0)


def kda_decode(q, k, v, g, beta, S, *, interpret: bool = None):
    """:func:`kda_step` as one Pallas kernel (name ``kda_decode``): a
    grid of (slot, block of heads), each step reading its states once
    and writing them in place (the state is aliased to the output).
    ``dk`` and ``dv`` whole 128-lane rows."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    if interpret is None:
        interpret = not on_tpu()
    hb = _HEADS if H % _HEADS == 0 else H
    vec = lambda d: pl.BlockSpec((1, hb, d), lambda b, j: (b, j, 0))
    state = pl.BlockSpec((1, hb, dk, dv), lambda b, j: (b, j, 0, 0))
    f = _f32
    return pl.pallas_call(
        _decode_kernel,
        grid=(B, H // hb),
        in_specs=[vec(dk), vec(dk), vec(dk), vec(dk), vec(dv), state],
        out_specs=[vec(dv), state],
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_decode",
    )(f(q), f(k), f(beta)[..., None] * f(k), jnp.exp(f(g)), f(v), S)


def reads_state_in_place(S) -> bool:
    """Whether a decode tick updates this state through
    :func:`kda_decode`: an f32 state of whole 128-lane heads, on a TPU."""
    return (S.dtype == jnp.float32 and S.shape[-1] % 128 == 0
            and S.shape[-2] % 8 == 0 and on_tpu())


def _gaps(gl, P, t_rows, s_rows):
    """``G_t - G_s`` for rows (t, s) of a chunk, from the cumulative log
    decay held as ``G = P + gl``: ``gl`` summed from the row's sub-chunk
    start, ``P`` the sub-chunks' totals before it.  Each part is
    differenced on its own, so rows of one sub-chunk subtract short sums
    and never two long ones: at -5 a row, G reaches -320 over a chunk,
    where f32 holds a sum to 2e-5, while two rows a few apart differ by
    a few units that a decay has to keep to f32's relative precision."""
    return (gl[..., t_rows, None, :] - gl[..., None, s_rows, :]) \
        + (P[..., t_rows, None, :] - P[..., None, s_rows, :])


def _decayed(a, b, gl, P, sub: int, inclusive: bool):
    """``M[t, s] = sum_c a[t, c] b[s, c] exp(G[t, c] - G[s, c])`` for s
    before t (``inclusive``: s at or before t), zero elsewhere.  ``a``,
    ``b`` (..., C, d) f32 with C whole sub-chunks of ``sub`` rows, ``G =
    P + gl`` non-increasing along C (``_gaps``) -> (..., C, C)."""
    *lead, C, d = a.shape
    m = C // sub
    split = lambda x: x.reshape(*lead, m, sub, d)
    As, Bs, gls = split(a), split(b), split(gl)
    ref = split(P)[..., 0, :]                    # G at each sub-chunk's start
    a_r = As * jnp.exp(gls)                                     # (.., m, sub, d)
    before = jnp.arange(C)[None, :] < (jnp.arange(m) * sub)[:, None]
    b_r = b[..., None, :, :] * jnp.exp(jnp.where(
        before[:, :, None], (ref[..., :, None, :] - P[..., None, :, :])
        - gl[..., None, :, :], -jnp.inf))                       # (.., m, C, d)
    inter = jnp.einsum("...itd,...isd->...its", a_r, b_r, precision=_HI)
    t = jnp.arange(sub)
    keep = t[:, None] >= t[None, :] if inclusive else t[:, None] > t[None, :]
    gap = jnp.where(keep[:, :, None],
                    gls[..., :, None, :] - gls[..., None, :, :],
                    -jnp.inf)                                   # (.., m, sub, sub, d)
    intra = jnp.sum(As[..., :, None, :] * Bs[..., None, :, :] * jnp.exp(gap),
                    axis=-1)                                    # (.., m, sub, sub)
    eye = jnp.eye(m, dtype=intra.dtype)
    diag = intra[..., :, :, None, :] * eye[:, None, :, None]    # (.., m, sub, m, sub)
    return (inter + diag.reshape(*lead, m, sub, C)).reshape(*lead, C, C)


def _solve_unit_lower(M, R, sub: int):
    """X with ``M X = R``: ``M`` (..., C, C) unit lower-triangular, ``R``
    (..., C, F), C whole blocks of ``sub`` rows.  Block forward
    substitution: each diagonal block inverted row by row in f32
    elementwise sums, the blocks below it brought in by matrix products
    at "highest" precision."""
    C = M.shape[-1]
    m = C // sub
    diag = jnp.stack([M[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
                      for i in range(m)], axis=-3)              # (.., m, s, s)
    eye = jnp.eye(sub, dtype=M.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (sub,))]
    for t in range(1, sub):
        done = jnp.stack(rows, axis=-2)                         # (.., m, t, s)
        rows.append(eye[t] - jnp.sum(diag[..., t, :t, None] * done, axis=-2))
    inv = jnp.stack(rows, axis=-2)                              # (.., m, s, s)
    X = []
    for i in range(m):
        acc = R[..., i * sub:(i + 1) * sub, :]
        if i:
            acc = acc - jnp.einsum("...ij,...jf->...if",
                                   M[..., i * sub:(i + 1) * sub, :i * sub],
                                   jnp.concatenate(X, axis=-2), precision=_HI)
        X.append(jnp.einsum("...ij,...jf->...if", inv[..., i, :, :], acc,
                            precision=_HI))
    return jnp.concatenate(X, axis=-2)


def kda_chunked(q, k, v, g, beta, S_in, state_rows=None, chunk: int = 64,
                sub: int = 16):
    """A sequence of T rows in chunks of ``chunk`` rows.  ``q``, ``k``,
    ``g`` (B, T, H, dk), ``v`` (B, T, H, dv), ``beta`` (B, T, H), ``S_in``
    (B, H, dk, dv) f32 as it stood before the first row.  Returns (o (B,
    T, H, dv) f32, S after the last row (B, H, dk, dv), or after each of
    ``state_rows`` (R,) int32: (B, R, H, dk, dv)).  Rows past T up to a
    whole chunk are zeros of key, value and decay, which leave the state
    as it was."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    sub = sub if chunk % sub == 0 else chunk
    n = -(-T // chunk)
    pad = n * chunk - T

    def chunks(x):          # (B, T, H, ...) -> (B, H, n, chunk, ...)
        x = jnp.pad(_f32(x), [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((B, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    # the cumulative log decay G = P + gl (_gaps), each part summed by
    # elementwise adds
    m = chunk // sub
    gl = jax.lax.associative_scan(
        jnp.add, g.reshape(B, H, n, m, sub, dk), axis=-2)
    tot = gl[..., -1, :]                                        # (B, H, n, m, dk)
    P = jnp.concatenate([jnp.zeros_like(tot[..., :1, :]),
                         jax.lax.associative_scan(jnp.add, tot, axis=-2)
                         [..., :-1, :]], axis=-2)
    gl = gl.reshape(B, H, n, chunk, dk)
    P = jnp.broadcast_to(P[..., None, :], (B, H, n, m, sub, dk)).reshape(
        B, H, n, chunk, dk)
    A = _decayed(k, k, gl, P, sub, inclusive=False)
    Bm = _decayed(q, k, gl, P, sub, inclusive=True)
    eG = jnp.exp(P + gl)
    M = jnp.eye(chunk, dtype=jnp.float32) + beta[..., None] * A
    rhs = beta[..., None] * jnp.concatenate([v, k * eG], axis=-1)
    sol = _solve_unit_lower(M, rhs, sub)
    U, Wk = sol[..., :dv], sol[..., dv:]
    Qg = q * eG
    rows = jnp.arange(chunk)
    G_last = eG[..., -1, :]                          # exp(G) after the chunk
    Kd = k * jnp.exp(_gaps(gl, P, rows[-1:], rows)[..., 0, :, :])
    mm = functools.partial(jnp.einsum, precision=_HI)
    S, outs, picked = S_in, [], None
    for j in range(n):
        W = U[:, :, j] - mm("bhcd,bhde->bhce", Wk[:, :, j], S)
        outs.append(mm("bhcd,bhde->bhce", Qg[:, :, j], S)
                    + mm("bhts,bhse->bhte", Bm[:, :, j], W))
        if state_rows is not None:
            r = jnp.clip(state_rows - j * chunk, 0, chunk - 1)  # (R,)
            w = jnp.exp(jnp.where(
                (rows[None, :] <= r[:, None])[:, :, None],
                _gaps(gl[:, :, j], P[:, :, j], r, rows), -jnp.inf))
            at = jnp.take(eG[:, :, j], r, axis=-2)[..., None] \
                * S[:, :, None] \
                + mm("bhrcd,bhce->bhrde", k[:, :, j][:, :, None] * w, W)
            here = ((state_rows >= j * chunk)
                    & (state_rows < (j + 1) * chunk))[None, None, :, None, None]
            picked = jnp.where(here, at, 0.0 if picked is None else picked)
        S = G_last[:, :, j][..., None] * S \
            + mm("bhcd,bhce->bhde", Kd[:, :, j], W)
    o = jnp.stack(outs, axis=2).reshape(B, H, n * chunk, dv)[:, :, :T]
    o = jnp.moveaxis(o, 1, 2)
    return o, (S if state_rows is None else jnp.moveaxis(picked, 2, 1))
