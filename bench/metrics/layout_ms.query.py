"""Partition and layout: device ms per traced query in the modules that
``layers.json`` puts in the layer "partition and layout" (sorts, composite
bucket ids, bucket gathers, radix histograms)."""

import trace_reduce


def read(run):
    return trace_reduce.layer_ms_per_request(run, "partition and layout")
