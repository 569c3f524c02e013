"""Binary steps: device ms per traced query in the modules that
``layers.json`` puts in the layer "binary steps"."""

import trace_reduce


def read(run):
    return trace_reduce.layer_ms_per_request(run, "binary steps")
