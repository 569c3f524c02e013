"""Service: mean wait of the traced requests from admission to the start
of their execution, in ms (the ``queued_us`` of each
``repro.service.request`` span in the window)."""

import program_spans


def read(run):
    return program_spans.queue_ms(program_spans.traced(run))
