"""The one traffic generator: reads a cell's `traffic` block (data) and
makes the requests from `--seed`.

Every seed gets the same work.  The lengths of one round (each
stream's r-th request) are the `clients` quantile midpoints of the
cell's distribution, dealt to the streams by a permutation that depends
on the round alone; so any stretch of a run holds the same mix of
prompt and output lengths, and the schedule of sizes is the same for
every seed.  The seed chooses the token values (and, in the runner, the
weights) and which client takes which stream, that is, the order in
which the streams are first submitted.  Sizes that followed the seed
moved the tokens per second by 2.5% and the TTFT tail by 7% between
seeds (PERF.md, PR 24), which would have hidden any loss smaller.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(n: int, spec: dict) -> np.ndarray:
    """The n quantile midpoints of the clipped distribution `spec`
    ({"dist": "lognormal", "median", "sigma", "lo", "hi"}), as ints."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    raw = [math.exp(math.log(spec["median"]) + spec["sigma"] * v) for v in z]
    return np.clip(np.rint(raw), spec["lo"], spec["hi"]).astype(np.int64)


def client_stream(t: dict, vocab: int, seed: int, client: int):
    """Endless iterator of (prompt ids, max_new_tokens) for one client of
    the traffic block `t`.  Every prompt is a tenant's `prefix_len`-token
    system prompt plus a private suffix, and a stream keeps its tenant.
    A function of (t, vocab, seed, client) alone."""
    n = t["clients"]
    stream = (client + seed) % n
    tenant = stream % t["tenants"]
    prefix = np.random.default_rng([seed, 1, tenant]).integers(
        0, vocab, t["prefix_len"])
    prompts, outputs = quantile_lengths(n, t["prompt"]), \
        quantile_lengths(n, t["output"])
    tokens = np.random.default_rng([seed, 2, stream])
    r = 0
    while True:
        deal = np.random.default_rng([3, r])
        p = int(prompts[deal.permutation(n)[stream]])
        o = int(outputs[deal.permutation(n)[stream]])
        suffix = tokens.integers(0, vocab, p)
        yield np.concatenate([prefix, suffix]).astype(np.int32), o
        r += 1
