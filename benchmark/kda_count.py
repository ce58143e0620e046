"""What the Kimi delta attention (KDA) layers of a configuration have to
move and compute, from the source's keys, and which device ops do it.

Per KDA layer a slot carries the state S, `num_attention_heads` heads of
`head_dim` x `head_dim` in f32.  A decode tick updates it through one
kernel a layer (`ops/kda.py::kda_decode`, found by that name): each
running slot's state read once and written once, beside the row's query,
key, value, decay and write strength (f32, one row each) and its output.
`state_bytes_per_slot` is the state, from the configuration; the program
counts the running slots a tick (`snapshot()["ssm_state_bytes"]`, which
moves each running slot's state and windows once each way); and
`decode_bytes` adds the vectors.

A prefill chunk runs its rows through the chunked form (`kda_chunked`,
chunks of 64 rows, sub-chunks of 16): per 64-row chunk and head the
decayed products of keys with keys and queries with keys (2 x 2 C^2 d),
the unit-triangular solve for the written values (2 C^2 d), the entry
state's part and the next state (3 x 2 C d^2) and the within-chunk output
(2 C^2 d): `prefill_flops`.  What it must move at least is its inputs and
output in f32 and the states in and out: `prefill_bytes`.

The ops of a layer are found by their scope, `kda` > `kda.proj`,
`kda.conv`, `kda.prefill` / `kda.decode`, `kda.out`.  A device op in the
profile carries its instruction's name and no scope, so `op_scopes`
reads each instruction's scope from the compiled program's own text (its
`op_name` metadata) and `scope_seconds` joins the two: an op belongs to
the program whose execution on the trace's module line holds it, and to
the scope its instruction was traced under there.  No op is found by
its shape.
"""

from __future__ import annotations

import bisect
import re

#: the decode kernel's name (ops/kda.py)
KERNEL = "kda_decode"
#: rows of a chunk of the chunked form (models/ling.py, LingConfig)
CHUNK = 64


def kda_layers(c: dict) -> int:
    g = c["layer_group_size"]
    return sum((i + 1) % g != 0 for i in range(c["num_hidden_layers"]))


def state_bytes_per_slot(c: dict) -> int:
    """f32 S of every KDA layer of one slot."""
    return kda_layers(c) * c["num_attention_heads"] * c["head_dim"] ** 2 * 4


def decode_bytes(c: dict, state_bytes: int) -> float:
    """What the decode kernels move for `state_bytes` of state read and
    written: per head and slot, 2 d^2 f32 of state beside 6 d of vectors
    (q, k, beta k, exp(g), v in; o out)."""
    return state_bytes * (1 + 3 / c["head_dim"])


def prefill_flops(c: dict, rows: int) -> int:
    """FLOPs of the chunked form over `rows` rows, all KDA layers."""
    d = c["head_dim"]
    per_row = 8 * CHUNK * d + 6 * d * d
    return kda_layers(c) * c["num_attention_heads"] * per_row * rows


def prefill_bytes(c: dict, rows: int, calls: int) -> int:
    """Bytes the chunked form must move over `rows` rows in `calls`
    calls (one a prefill chunk), all KDA layers: q, k, v, g and o in f32
    and beta a row; the state in, out and at the two rows a paged engine
    asks for, a call."""
    H, d = c["num_attention_heads"], c["head_dim"]
    return kda_layers(c) * (rows * (5 * H * d + H) * 4
                            + calls * 4 * H * d * d * 4)


def decode_seconds(ops: dict) -> float:
    """Seconds of `trace_reduce.reduce(...)["ops"]` ({name: (seconds,
    count)}) in the decode kernel."""
    return sum(sec for name, (sec, _) in ops.items() if KERNEL in name)


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: the scope path it was traced under} of one
    compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _instruction(op_name: str) -> str:
    """`%fusion.12 = f32[..] fusion(..)` -> `fusion.12`."""
    return op_name.split("=")[0].strip().lstrip("%")


def scope_seconds(profile, scopes: dict, plane="/device:TPU:0") -> dict:
    """{"kda": s, "kda.prefill": s, "kda.decode": s}: device seconds of
    the ops traced under the KDA layers' scopes (the decode kernel by
    its name as well).  `scopes`: {module name as the trace's module
    line has it (`jit_prefill_chunk`): `op_scopes` of its program}.  {}
    where no op is found (a program without the scopes)."""
    ops, modules = [], []
    for p in profile.planes:
        if p.name != plane:
            continue
        for ln in p.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in ln.events]
            if ln.name == "XLA Ops":
                ops = events
            elif ln.name == "XLA Modules":
                modules = sorted(events)
    starts = [s for s, _, _ in modules]
    out = {"kda": 0.0, "kda.prefill": 0.0, "kda.decode": 0.0}
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        held = i >= 0 and modules[i][1] >= e
        program = modules[i][2].split("(")[0] if held else ""
        scope = scopes.get(program, {}).get(_instruction(name), "")
        kernel = KERNEL in name
        if "kda" not in scope.split("/") and not kernel:
            continue
        sec = (e - s) * 1e-9
        out["kda"] += sec
        if "kda.prefill" in scope:
            out["kda.prefill"] += sec
        if "kda.decode" in scope or kernel:
            out["kda.decode"] += sec
    return out if out["kda"] else {}


def roofline(amount: float, seconds: float, peak_per_s: float):
    """Share (%) of a peak that `amount` (bytes or FLOPs) reached in
    `seconds`; None without either."""
    if not amount or not seconds:
        return None
    return 100.0 * amount / (peak_per_s * seconds)
