"""What the expert layer of a mixture-of-experts configuration has to
move, computed from the source's keys, and which device ops move it.

The experts of one layer are three stacked matrices, `(E, D, H)` for
the gate and the up projection and `(E, H, D)` for the down projection
(E experts, hidden size D, expert width H).  At a few rows an expert a
dispatch, a program that routes without drops streams all of them once
a layer: that is the least it can read, and the roofline below is that
over the HBM peak.  An op "streams expert weights" when its HLO text
names an operand or a result of such a stack's shape, whole or a slice
of its leading (expert) axis: the matmuls, and the copies, layout
changes and prefetches of the stacks the compiler puts around them.
"""

from __future__ import annotations

import re

_SHAPE = re.compile(r"\[([0-9,]+)\]")


def expert_bytes_per_layer(c: dict, bytes_per_param: int = 2) -> int:
    """Bytes of one layer's stacked expert weights as served (bf16)."""
    return (3 * c["num_experts"] * c["hidden_size"]
            * c["moe_intermediate_size"] * bytes_per_param)


def is_expert_stack(dims, c: dict) -> bool:
    """True for `(e, D, H)` or `(e, H, D)` with 1 <= e <= E, and for
    one expert's bare `(D, H)` / `(H, D)` matrix."""
    d, h = c["hidden_size"], c["moe_intermediate_size"]
    dims = tuple(dims)
    if dims[-2:] not in ((d, h), (h, d)):
        return False
    return len(dims) == 2 or (len(dims) == 3
                              and 1 <= dims[0] <= c["num_experts"])


def streams_expert_weights(op_name: str, c: dict) -> bool:
    """Does the op's HLO text name a tensor of an expert stack's shape?"""
    return any(is_expert_stack(map(int, m.split(",")), c)
               for m in _SHAPE.findall(op_name) if m[-1] != ",")


def expert_op_seconds(ops: dict, c: dict) -> float:
    """Seconds of `trace_reduce.reduce(...)["ops"]` ({name: (seconds,
    count)}) spent in ops that stream expert weights."""
    return sum(sec for name, (sec, _) in ops.items()
               if streams_expert_weights(name, c))


def expert_roofline(executions: int, seconds: float, c: dict,
                    hbm_bytes_per_s: float):
    """Share (%) of the HBM peak that `executions` program runs, each
    streaming every layer's stacks once, reached in `seconds` of the
    ops that stream them; None when there is nothing to divide."""
    if not executions or not seconds:
        return None
    least = executions * c["num_hidden_layers"] * expert_bytes_per_layer(c)
    return 100.0 * least / (hbm_bytes_per_s * seconds)
