"""Program spans on the profiler's clock, and the one door from device to host.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``.  The profiler records it on the host timeline of the
same trace (``.xplane.pb``) as the device operations, so a span and the
device ops it waits on or dispatches share one clock.  With no profiler
running nothing is recorded: there is no switch and no exporter.  Span
arguments are host ints and strings already at hand; none reads a device
value.

``to_host(what, x)`` is the one function through which a device value
becomes a host value on the query path.  It opens ``sync.<what>`` with the
number of elements read, allows its own device-to-host transfer (so a
process may run under ``jax_transfer_guard_device_to_host="disallow"`` and
catch every other read), and returns a numpy array, or a Python number
for a scalar.  A change that removes a host read deletes its ``to_host`` call.
"""

from __future__ import annotations

import jax
import numpy as np

PREFIX = "repro."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A ``repro.<name>`` span with host-value arguments."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def to_host(what: str, x):
    """Read device value(s) ``x`` (an array or a tuple of arrays) on the
    host under a ``sync.<what>`` span: a Python number for a scalar, else
    a numpy array (a tuple of scalars reads as a 1-d array)."""
    n = sum(v.size for v in x) if isinstance(x, tuple) else x.size
    with span("sync." + what, n=n), \
            jax.transfer_guard_device_to_host("allow"):
        out = np.asarray(x)
    return out.item() if out.ndim == 0 else out
