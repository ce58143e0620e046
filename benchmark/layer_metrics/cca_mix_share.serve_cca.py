"""Share of the device's busy time spent in the ops that CCA adds
beside plain attention (`cca_count.does_cca_mixing`: the q-k mean, the
two convolutions and the norm over the 1280-wide packed latent, and the
reads and writes of the side state per slot and per KV block), from the
trace's op line: what the scopes `attn.cca.mix` and `attn.cca.state`
lower to.  A run without the configuration's keys has nothing to read:
`None`."""

import cca_count

META = {"layer": "cca attention", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-reason-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("moe_config")
    if not trace or not c or "cca_time0" not in c:
        return None
    seconds = cca_count.cca_op_seconds(trace["ops"], c)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
