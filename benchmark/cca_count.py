"""What the CCA layer of a configuration adds beside plain attention,
computed from the source's keys, and which device ops do it.

CCA (`singa_tpu/ops/cca.py`) mixes the packed latents `[q~ ; k~]`, C =
(query heads + KV heads) x head_dim channels wide, along time: a
depthwise convolution of `cca_time0` taps, one of `cca_time1` taps
grouped by head (a `(heads, head_dim, head_dim)` kernel a tap), the
q-k mean and a norm; and it carries a side state of S = pad x C +
KV heads / 2 x head_dim values a layer (pad = cca_time0 - 1 +
cca_time1 - 1), per slot and per KV block.  An op "does CCA mixing"
when its HLO text names a tensor whose last dimension is C or S (the
packed latent, the depthwise kernel, a state array) or whose last three
are the grouped kernel's: the fusions that the scopes `attn.cca.mix`
and `attn.cca.state` lower to.  The projections into the latent, the
rotary embedding and the attention itself read the heads apart
(`..., heads, head_dim`) and are not counted.
"""

from __future__ import annotations

import re

_SHAPE = re.compile(r"\[([0-9,]+)\]")


def widths(c: dict):
    """(C, S, heads, head_dim) for the source's keys in `c`."""
    heads = c["num_attention_heads"] + c["num_key_value_heads"]
    d = c["head_dim"]
    pad = (c["cca_time0"] - 1) + (c["cca_time1"] - 1)
    return (heads * d, pad * heads * d + c["num_key_value_heads"] // 2 * d,
            heads, d)


def is_cca_tensor(dims, c: dict) -> bool:
    """True for `(..., C)` and `(..., S)`, and for the grouped kernel
    `(heads, d, d)` with or without its leading tap axis."""
    width, state, heads, d = widths(c)
    dims = tuple(dims)
    return dims[-1] in (width, state) or (
        len(dims) in (3, 4) and dims[-3:] == (heads, d, d))


def does_cca_mixing(op_name: str, c: dict) -> bool:
    """Does the op's HLO text name a tensor of one of those shapes?"""
    return any(is_cca_tensor(map(int, m.split(",")), c)
               for m in _SHAPE.findall(op_name) if m[-1] != ",")


def cca_op_seconds(ops: dict, c: dict) -> float:
    """Seconds of `trace_reduce.reduce(...)["ops"]` ({name: (seconds,
    count)}) spent in ops that do CCA mixing."""
    return sum(sec for name, (sec, _) in ops.items()
               if does_cca_mixing(name, c))
