"""Seconds from process start to the first measured request: generating the
tables, putting them on the chip, loading or compiling the programs, and the
warm-up requests of the cell's own shapes."""


def read(run):
    return run.setup_s
