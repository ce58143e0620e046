"""Share of the device's busy time spent in the state-space layers'
ops (`ssm_count.ssm_op_seconds`: projections in and out, the
convolution, the chunked scan of a prefill chunk, the state update of
a tick, the gated norm), from the trace's op line: what the scopes
under `ssm` lower to, recognised by the shapes only those layers have.
A run without the configuration's keys has nothing to read: `None`."""

import ssm_count

META = {"layer": "state-space layer", "unit": "%",
        "moves": "serve_tokens_per_s", "cells": ["serve-rag-closed"]}


def compute(run):
    trace, c = run.get("trace"), run.get("ssm_config")
    if not trace or not c:
        return None
    seconds = ssm_count.ssm_op_seconds(trace["ops"], c)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
