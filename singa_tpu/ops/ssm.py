"""The state-space arithmetic of a Mamba-2 layer (Dao & Gu,
arXiv:2405.21060), in the two forms a server needs and that must agree.

Per head h of width P, with a scalar decay ``A_h < 0``, a step size
``dt_t > 0`` a head and a row, input ``x_t`` (P,), and ``B_t``, ``C_t``
(N,) shared by the heads of a group (one group here), the state ``S``
(P, N) obeys

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

* :func:`ssm_step` is that recurrence for one row of every batch row:
  what a decode tick runs over every slot.  Elementwise products and
  one sum over N, no matrix product: f32 whatever the matmul precision.
* :func:`ssd_chunk` is the same over a chunk of T rows at once (the
  paper's "state-space dual" form): with ``cum_t = sum_{s<=t} dt_s A``,
  ``y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s`` (the
  quadratic form inside the chunk) ``+ exp(cum_t) S_in C_t`` (the entry
  state's part), and the state after row r is ``exp(cum_r) S_in +
  sum_{s<=r} exp(cum_r - cum_s) dt_s x_s B_s^T``.  It yields the state
  after each of ``state_rows`` and not after every row: a state is
  H x P x N values (4 MB a layer at 128 x 64 x 128 in f32), and a paged
  engine needs it after the chunk's last valid row and after at most
  one more.  :func:`ssd` runs a longer sequence as such chunks, the
  state carried from one to the next.

The state is f32 whatever the activations are.  The matrix products of
the chunked form take their inputs in the activations' dtype and sum in
f32, under the caller's ``jax.default_matmul_precision``.

:func:`causal_conv` is the depthwise convolution in front of the scan,
with the window of rows it has to carry.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssm_step", "ssd_chunk", "ssd", "causal_conv"]

_f32 = lambda a: a.astype(jnp.float32)


def ssm_step(x, dt, A, B, C, D, S):
    """One row a batch row.  ``x`` (B, H, P), ``dt`` (B, H) f32 and
    positive, ``A`` and ``D`` (H,), ``B`` and ``C`` (B, N), ``S``
    (B, H, P, N) f32 -> (y (B, H, P) in ``x``'s dtype, the new S)."""
    xf = _f32(x)
    decay = jnp.exp(dt * A)                                     # (B, H)
    S = S * decay[:, :, None, None] \
        + (dt[:, :, None] * xf)[..., None] * _f32(B)[:, None, None, :]
    y = jnp.sum(S * _f32(C)[:, None, None, :], axis=-1) + D[:, None] * xf
    return y.astype(x.dtype), S


def ssd_chunk(x, dt, A, B, C, D, S_in, state_rows):
    """A chunk of T rows.  ``x`` (B, T, H, P), ``dt`` (B, T, H) f32,
    ``B`` and ``C`` (B, T, N), ``S_in`` (B, H, P, N) f32 as it stood
    before the first row, ``state_rows`` (R,) int32 rows of the chunk
    -> (y (B, T, H, P) in ``x``'s dtype, S after each of those rows
    (B, R, H, P, N) f32)."""
    T = x.shape[1]
    acc = dict(preferred_element_type=jnp.float32)
    cum = jnp.cumsum(dt * A, axis=1)                            # (B, T, H)
    xdt = (_f32(x) * dt[..., None]).astype(x.dtype)             # (B, T, H, P)
    # the quadratic form: L[t, s] = exp(cum_t - cum_s) for s <= t.  The
    # exponent is masked, not the result: above the diagonal it is
    # positive and may overflow
    t = jnp.arange(T)
    gap = cum[:, :, None, :] - cum[:, None, :, :]               # (B, T, S, H)
    L = jnp.exp(jnp.where((t[:, None] >= t[None, :])[None, :, :, None],
                          gap, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", C, B, **acc)
    M = (L * cb[..., None]).astype(x.dtype)
    y = jnp.einsum("btsh,bshp->bthp", M, xdt, **acc)
    # the entry state's part
    y = y + jnp.einsum("btn,bhpn->bthp", C, S_in.astype(x.dtype), **acc) \
        * jnp.exp(cum)[..., None]
    y = y + D[:, None] * _f32(x)
    # the state after each asked-for row r: rows s <= r, decayed to r
    cum_r = jnp.take(cum, state_rows, axis=1)                   # (B, R, H)
    w = jnp.exp(jnp.where(
        (t[None, :] <= state_rows[:, None])[None, :, :, None],
        cum_r[:, :, None, :] - cum[:, None, :, :], -jnp.inf))   # (B, R, T, H)
    xw = (_f32(xdt)[:, None] * w[..., None]).astype(x.dtype)    # (B, R, T, H, P)
    S = jnp.einsum("brshp,bsn->brhpn", xw, B, **acc) \
        + jnp.exp(cum_r)[..., None, None] * S_in[:, None]
    return y.astype(x.dtype), S


def ssd(x, dt, A, B, C, D, S_in, state_rows=None, chunk: int = 256):
    """A sequence of any length as chunks of at most ``chunk`` rows, the
    state carried between them.  Returns (y, S): S after the last row
    (B, H, P, N), or after each of ``state_rows`` (B, R, H, P, N)."""
    T = x.shape[1]
    ys, S, picked = [], S_in, None
    for start in range(0, T, chunk):
        n = min(chunk, T - start)
        last = start + n == T
        rows = jnp.zeros((0,), jnp.int32) if state_rows is None \
            else jnp.clip(state_rows - start, 0, n - 1)
        if not (last and state_rows is not None):
            rows = jnp.append(rows, n - 1)      # what the next chunk enters with
        cut = slice(start, start + n)
        y, states = ssd_chunk(x[:, cut], dt[:, cut], A, B[:, cut], C[:, cut],
                              D, S, rows.astype(jnp.int32))
        ys.append(y)
        if state_rows is None:
            S = states[:, -1]
            continue
        here = ((state_rows >= start) & (state_rows < start + n)
                )[None, :, None, None, None]
        asked = states[:, :state_rows.shape[0]]
        picked = jnp.where(here, asked, 0.0 if picked is None else picked)
        if not last:
            S = states[:, -1]
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    return y, (S if state_rows is None else picked)


def causal_conv(u, window, w, b, state_rows=None):
    """Depthwise causal convolution along time, then silu.  ``u``
    (B, T, C) the rows before the convolution, ``window`` (B, K - 1, C)
    the K - 1 rows before them (zeros before position 0), ``w`` (K, C)
    the taps oldest first (the last on the current row), ``b`` (C,)
    -> (silu(conv) (B, T, C) in ``u``'s dtype, the window after the
    last row, or after each of ``state_rows``: (B, R, K - 1, C))."""
    T, K = u.shape[1], w.shape[0]
    ext = jnp.concatenate([window.astype(u.dtype), u], axis=1)  # (B, K-1+T, C)
    wide = _f32(ext)
    out = sum(_f32(w[j]) * wide[:, j:j + T] for j in range(K)) + _f32(b)
    if state_rows is None:
        new = ext[:, T:]
    else:       # after chunk row r: rows r - (K - 2) .. r, ext's r + 1 ..
        idx = state_rows[:, None] + 1 + jnp.arange(K - 1)[None, :]
        new = jnp.take(ext, idx, axis=1)
    return jax.nn.silu(out).astype(u.dtype), new.astype(window.dtype)
