"""Recovery rounds: device self ms per traced request of the operations
that start inside a re-run round (``repro.recovery.round`` with round >= 1)
or a residual mask (``repro.recovery.residual``)."""

import program_spans


def read(run):
    return program_spans.recovery_ms(program_spans.traced(run))
