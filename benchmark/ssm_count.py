"""What the state-space (Mamba-2) layers of a configuration have to
move and compute, from the source's keys, and which device ops do it.

Per mamba layer, with `d_inner = mamba_n_heads x mamba_d_head`, N =
`mamba_d_state`, `conv_dim = d_inner + 2 N` (one group), K =
`mamba_d_conv`: a slot carries the recurrent state S (`heads x d_head x
N`, f32) and the convolution's window (`(K - 1) x conv_dim`, bf16).  A
decode tick reads and writes both once for every running slot: that is
the least its state update can move, and `ssm_state_roofline` is that
over the HBM peak in the time of the ops that did it.

An op "is the state-space layer's" when its HLO text names a tensor of
a shape only that layer has: `in_proj`'s `d_inner + conv_dim + heads`
columns, the `conv_dim`-wide rows before and after the convolution, the
`d_inner`-wide gated norm, `(.., heads, d_head)` rows of the scan's
input, a `(.., T, T, heads)` decay matrix of the chunked scan, a state
`(.., heads, d_head, N)`, or `out_proj`'s `(d_inner, hidden)` weight:
what the scopes `ssm.in_proj`, `ssm.conv`, `ssm.scan` / `ssm.step`,
`ssm.norm` and `ssm.out_proj` lower to.  It "updates the slots' state"
when it names the per-slot state itself, `(slots, heads, d_head, N)`:
the fusions of `ssm.step` in a tick, and the one-slot reads and in-place
writes of a prefill chunk, which are small beside them and make the
roofline read low, never high.
"""

from __future__ import annotations

import re

_SHAPE = re.compile(r"\[([0-9,]+)\]")


def widths(c: dict):
    """(d_inner, conv_dim, in_proj's columns, heads, d_head, N)."""
    heads, dh, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    di = heads * dh
    conv = di + 2 * c["mamba_n_groups"] * n
    return di, conv, di + conv + heads, heads, dh, n


def mamba_layers(c: dict) -> int:
    return c["layer_types"][:c["num_hidden_layers"]].count("mamba")


def state_bytes_per_slot(c: dict, window_bytes: int = 2) -> int:
    """Bytes of state one slot carries over all the mamba layers: S in
    f32 and the convolution's window as served (bf16)."""
    di, conv, _, heads, dh, n = widths(c)
    return mamba_layers(c) * (heads * dh * n * 4
                              + (c["mamba_d_conv"] - 1) * conv * window_bytes)


def tick_state_bytes(c: dict, slots: int) -> int:
    """What one decode tick's state updates read and write for `slots`
    running slots: each one's state once each way."""
    return 2 * slots * state_bytes_per_slot(c)


def scan_flops(c: dict, rows: int, states: int = 2) -> int:
    """FLOPs of the chunked scan over `rows` rows of one sequence, all
    mamba layers: the decay-weighted C.B products, the quadratic form,
    the entry state's part, and `states` states out."""
    _, _, _, heads, dh, n = widths(c)
    per_layer = (2 * rows * rows * n + 2 * rows * rows * heads * dh
                 + (1 + states) * 2 * rows * heads * dh * n)
    return mamba_layers(c) * per_layer


def expert_keys(c: dict) -> dict:
    """`c` under the keys `moe_count` reads: the experts HELD here are
    the stacks a program streams."""
    return {"num_experts": c["num_local_experts"],
            "hidden_size": c["hidden_size"],
            "moe_intermediate_size": c["intermediate_size"],
            "num_hidden_layers": c["num_hidden_layers"]}


def _dims(op_name: str):
    return [tuple(map(int, m.split(","))) for m in _SHAPE.findall(op_name)
            if m[-1] != ","]


def is_ssm_tensor(dims, c: dict) -> bool:
    di, conv, cols, heads, dh, n = widths(c)
    dims = tuple(dims)
    return dims[-1] in (cols, conv, di) \
        or dims[-2:] == (heads, dh) or dims[-2:] == (di, c["hidden_size"]) \
        or dims[-3:] == (heads, dh, n) \
        or (len(dims) >= 3 and dims[-1] == heads and dims[-2] == dims[-3])


def is_slot_state(dims, c: dict, slots: int) -> bool:
    _, _, _, heads, dh, n = widths(c)
    return tuple(dims) == (slots, heads, dh, n)


def ssm_op_seconds(ops: dict, c: dict) -> float:
    """Seconds of `trace_reduce.reduce(...)["ops"]` ({name: (seconds,
    count)}) spent in the state-space layers' ops."""
    return sum(sec for name, (sec, _) in ops.items()
               if any(is_ssm_tensor(d, c) for d in _dims(name)))


def state_update_seconds(ops: dict, c: dict, slots: int) -> float:
    """Seconds spent in ops that name the per-slot state."""
    return sum(sec for name, (sec, _) in ops.items()
               if any(is_slot_state(d, c, slots) for d in _dims(name)))


def state_roofline(nbytes: int, seconds: float, hbm_bytes_per_s: float):
    """Share (%) of the HBM peak that moving `nbytes` of state reached
    in `seconds` of the ops that moved them; None without either."""
    if not nbytes or not seconds:
        return None
    return 100.0 * nbytes / (hbm_bytes_per_s * seconds)
