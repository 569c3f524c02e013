"""Fused root: device ms per traced query in the fused 3-way sweep modules,
all recovery rounds together."""

import trace_reduce


def read(run):
    return trace_reduce.layer_ms_per_request(run, "fused root")
