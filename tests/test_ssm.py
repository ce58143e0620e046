"""`ops/ssm.py` (ISSUE 34): the Mamba-2 arithmetic in its two forms, the
chunked scan a prefill runs and the one-row recurrence a decode tick
runs, held to each other and to a recurrence written out in numpy
float64; the convolution in front of them with the window it carries.
CPU, tiny sizes, f32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.ops import ssm

B, T, H, P, N = 2, 37, 3, 8, 5
TOL = dict(atol=2e-5, rtol=2e-5)


def _inputs(seed=0, t=T):
    r = np.random.default_rng(seed)
    f = lambda *s: r.standard_normal(s).astype(np.float32)
    return dict(x=f(B, t, H, P), dt=np.exp(r.uniform(-5, -1, (B, t, H))
                                           ).astype(np.float32),
                A=-r.uniform(1, 16, H).astype(np.float32), Bm=f(B, t, N),
                Cm=f(B, t, N), D=f(H), S=f(B, H, P, N))


def _by_hand(x, dt, A, Bm, Cm, D, S):
    """The recurrence in float64, row by row: (y, S after every row)."""
    x, dt, A, Bm, Cm, D, S = (np.asarray(a, np.float64)
                              for a in (x, dt, A, Bm, Cm, D, S))
    ys, states = [], []
    for t in range(x.shape[1]):
        S = np.exp(dt[:, t] * A)[:, :, None, None] * S \
            + (dt[:, t, :, None] * x[:, t])[..., None] \
            * Bm[:, t, None, None, :]
        ys.append(np.einsum("bhpn,bn->bhp", S, Cm[:, t]) + D[:, None] * x[:, t])
        states.append(S)
    return np.stack(ys, 1), np.stack(states, 1)


def test_step_is_the_recurrence():
    i = _inputs(1)
    want_y, want_s = _by_hand(**i)
    S = jnp.asarray(i["S"])
    for t in range(6):
        y, S = ssm.ssm_step(i["x"][:, t], i["dt"][:, t], i["A"], i["Bm"][:, t],
                            i["Cm"][:, t], i["D"], S)
        np.testing.assert_allclose(y, want_y[:, t], **TOL)
        np.testing.assert_allclose(S, want_s[:, t], **TOL)
    assert S.dtype == jnp.float32


@pytest.mark.parametrize("rows", [[T - 1], [0, 11], [5, 5, 36]])
def test_chunk_is_the_recurrence(rows):
    i = _inputs(2)
    want_y, want_s = _by_hand(**i)
    y, S = ssm.ssd_chunk(i["x"], i["dt"], i["A"], i["Bm"], i["Cm"], i["D"],
                         i["S"], jnp.asarray(rows, jnp.int32))
    np.testing.assert_allclose(y, want_y, **TOL)
    np.testing.assert_allclose(S, want_s[:, rows], **TOL)


@pytest.mark.parametrize("chunk", [1, 8, 16, 37, 64])
@pytest.mark.parametrize("rows", [None, [36], [3, 20], [15, 16]])
def test_a_sequence_split_anywhere_gives_the_same(chunk, rows):
    i = _inputs(3)
    want_y, want_s = _by_hand(**i)
    asked = None if rows is None else jnp.asarray(rows, jnp.int32)
    y, S = ssm.ssd(i["x"], i["dt"], i["A"], i["Bm"], i["Cm"], i["D"], i["S"],
                   asked, chunk)
    np.testing.assert_allclose(y, want_y, **TOL)
    np.testing.assert_allclose(S, want_s[:, -1] if rows is None
                               else want_s[:, rows], **TOL)


def test_two_chunks_carry_the_state_a_chunk_and_steps_agree():
    """A prompt's first chunk, its second from the first's state, then
    ticks: what the engine's two programs do to one slot."""
    i = _inputs(4)
    want_y, want_s = _by_hand(**i)
    cut = lambda a, s: a[:, s] if a.ndim > 1 and a.shape[1] == T else a
    part = lambda s, S: ssm.ssd(cut(i["x"], s), cut(i["dt"], s), i["A"],
                                cut(i["Bm"], s), cut(i["Cm"], s), i["D"], S)
    y0, S = part(slice(0, 16), jnp.asarray(i["S"]))
    y1, S = part(slice(16, 30), S)
    np.testing.assert_allclose(np.concatenate([y0, y1], 1), want_y[:, :30],
                               **TOL)
    for t in range(30, T):
        y, S = ssm.ssm_step(i["x"][:, t], i["dt"][:, t], i["A"],
                            i["Bm"][:, t], i["Cm"][:, t], i["D"], S)
        np.testing.assert_allclose(y, want_y[:, t], **TOL)
    np.testing.assert_allclose(S, want_s[:, -1], **TOL)


def test_rows_after_the_asked_one_do_not_move_the_state():
    """A padded chunk: whatever the rows past the last valid one hold,
    the state after that row is the same."""
    i = _inputs(5)
    rows = jnp.asarray([20], jnp.int32)
    clean = ssm.ssd_chunk(i["x"], i["dt"], i["A"], i["Bm"], i["Cm"], i["D"],
                          i["S"], rows)[1]
    junk = {k: np.array(v) for k, v in i.items()}
    for k in ("x", "dt", "Bm", "Cm"):
        junk[k][:, 21:] = 1e3
    other = ssm.ssd_chunk(junk["x"], junk["dt"], junk["A"], junk["Bm"],
                          junk["Cm"], junk["D"], junk["S"], rows)[1]
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(other))


def test_fast_decay_does_not_overflow_above_the_diagonal():
    i = _inputs(6)
    i["dt"] = np.full_like(i["dt"], 40.0)       # exp(+40 * 16 * rows) above it
    y, S = ssm.ssd_chunk(i["x"], i["dt"], i["A"], i["Bm"], i["Cm"], i["D"],
                         i["S"], jnp.asarray([T - 1], jnp.int32))
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(S)).all()


def test_bf16_activations_keep_an_f32_state():
    i = _inputs(7)
    lo = lambda a: jnp.asarray(a, jnp.bfloat16)
    y, S = ssm.ssd(lo(i["x"]), i["dt"], i["A"], lo(i["Bm"]), lo(i["Cm"]),
                   i["D"], i["S"])
    assert y.dtype == jnp.bfloat16 and S.dtype == jnp.float32
    y1, S1 = ssm.ssm_step(lo(i["x"][:, 0]), i["dt"][:, 0], i["A"],
                          lo(i["Bm"][:, 0]), lo(i["Cm"][:, 0]), i["D"], S)
    assert y1.dtype == jnp.bfloat16 and S1.dtype == jnp.float32
    want_s = _by_hand(**i)[1][:, -1]
    err = np.linalg.norm(np.asarray(S) - want_s) / np.linalg.norm(want_s)
    assert err < 2e-2, err


# -- the convolution and its window ------------------------------------------

K, C = 4, 6


def _conv_by_hand(u, w, b):
    u = np.asarray(u, np.float64)
    ext = np.concatenate([np.zeros((B, K - 1, C)), u], 1)
    out = sum(w[j] * ext[:, j:j + u.shape[1]] for j in range(K)) + b
    return out / (1 + np.exp(-out))


def _conv_inputs(seed=8):
    r = np.random.default_rng(seed)
    f = lambda *s: r.standard_normal(s).astype(np.float32)
    return f(B, T, C), f(K, C), f(C)


def test_conv_from_zeros_is_the_plain_causal_convolution():
    u, w, b = _conv_inputs()
    out, win = ssm.causal_conv(u, jnp.zeros((B, K - 1, C)), w, b)
    np.testing.assert_allclose(out, _conv_by_hand(u, w, b), **TOL)
    np.testing.assert_array_equal(np.asarray(win), u[:, -(K - 1):])


@pytest.mark.parametrize("cut", [1, 2, 3, 20])
def test_conv_window_carries_across_a_split(cut):
    """Also where the first part is shorter than the window."""
    u, w, b = _conv_inputs(9)
    want = _conv_by_hand(u, w, b)
    a, win = ssm.causal_conv(u[:, :cut], jnp.zeros((B, K - 1, C)), w, b)
    c, win = ssm.causal_conv(u[:, cut:], win, w, b)
    np.testing.assert_allclose(np.concatenate([a, c], 1), want, **TOL)
    np.testing.assert_array_equal(np.asarray(win), u[:, -(K - 1):])


def test_conv_window_after_asked_rows():
    u, w, b = _conv_inputs(10)
    rows = jnp.asarray([0, 1, 9, T - 1], jnp.int32)
    _, wins = ssm.causal_conv(u, jnp.zeros((B, K - 1, C)), w, b, rows)
    ext = np.concatenate([np.zeros((B, K - 1, C), np.float32), u], 1)
    for j, r in enumerate([0, 1, 9, T - 1]):
        np.testing.assert_array_equal(np.asarray(wins[:, j]),
                                      ext[:, r + 1:r + K])


def test_the_two_forms_lower_under_jit_with_traced_rows():
    i = _inputs(11)
    f = jax.jit(lambda rows: ssm.ssd(i["x"], i["dt"], i["A"], i["Bm"],
                                     i["Cm"], i["D"], i["S"], rows, 16))
    _, S = f(jnp.asarray([7, 30], jnp.int32))
    np.testing.assert_allclose(S, _by_hand(**i)[1][:, [7, 30]], **TOL)
