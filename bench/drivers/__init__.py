"""Traffic drivers: one module per traffic ``op``.

A cell's traffic file (``traffic/<traffic>.json``) names its driver by its
``op`` key, and ``run.py`` loads ``drivers/<op>.py`` once, before any
work; an ``op`` with no module here ends the run with the list of the
drivers present.  A new loop is a new module here and a traffic file that
names it: ``run.py`` needs no edit.

A driver owns what differs between traffics: the relations and their
capacities, the service and any mesh, the loop, and the reference answer
for each request.  ``run.py`` keeps what every cell shares: the cell, the
devices, the compile cache and its counter, the tables made from the seed
(stream ``harness.STREAM_DATA``), the tracer, the trace reduction, the
metric readers, the peak memory and the result line.

A driver module defines four functions:

``open(cell, devices, tables, seed) -> state``
    Build the relations from ``tables`` (the generator's host arrays), the
    service, and warm up every shape the window will use.  It runs inside
    ``setup_s``, which ends when it returns.  Where it raises, it leaves
    nothing running.
``window(state, seconds, tracer) -> list[harness.Req]``
    Drive the traffic for ``seconds``: call ``tracer.start`` at the first
    send, wrap each request in ``tracer.span(<name>)`` and call
    ``tracer.after`` at each answer; the request in flight at the deadline
    completes and counts.
``close(state)``
    Stop what ``open`` started.  ``run.py`` calls it in its ``finally``,
    after the window and the peak memory's read.
``check(state, ref, reqs) -> {name: {"value": number, "limit": number}}``
    Compare the window's answers with the configuration's plain reference
    module ``ref``, once the service is closed.  The run is correct where
    every value is at most its limit; the result line ends in this dict.

A driver imports ``harness`` for the record of a request, the log and the
seeded streams, and never ``run``.  Metric readers see the requests
(``run.requests``): what a driver puts in a request's ``result`` is what
they can read of it.
"""
