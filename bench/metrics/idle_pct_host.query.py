"""Device: 100 x device idle time in the traced window while the serving
thread is inside a request but under no ``repro.sync.*`` read (planning,
host counts and masks, dispatch), over the window."""

import program_spans


def read(run):
    return program_spans.idle_pct(program_spans.traced(run), "host")
