"""Time in the Pallas paged-attention custom calls of `decode_paged`
(one a layer a tick) over the device's busy time, from the trace's op
line.  0 where decode attention still walks a gathered view: nothing
else in a serve program is a custom call."""

import trace_reduce

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "cells": ["serve-chat-closed", "serve-code-closed",
                  "serve-reason-closed"]}


def compute(run):
    return trace_reduce.op_share(run["trace"], trace_reduce.FLASH_OPS)
