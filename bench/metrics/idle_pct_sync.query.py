"""Host-device boundary: 100 x device idle time in the traced window while
the serving thread's innermost program span is a ``repro.sync.*`` read,
over the window."""

import program_spans


def read(run):
    return program_spans.idle_pct(program_spans.traced(run), "sync")
