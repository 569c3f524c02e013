"""Device idle share of the traced head of a query window, in percent:
100 x (1 - device busy / traced window)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
