"""What ``run.py`` and the traffic drivers under ``drivers/`` both use: the
log, the seeded random streams, a request's record and the tracer.

A plain module, so that a driver never imports ``run``: under the
benchmark command ``run.py`` is ``__main__``, and ``import run`` would load
a second copy of it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import pathlib
import shutil
import sys

import numpy as np

TRACE_SECONDS = 5.0      # profiled head of a --trace 1 window
STREAM_DATA = 0          # the tables; a driver draws its traffic from 1 up


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), stream])


@dataclasses.dataclass
class Req:
    """One request of the window: when it was sent and answered (host
    clock), its answer or its error, and whether the profiler ran."""

    t0: float
    t1: float | None = None
    result: object = None
    error: str | None = None
    traced: bool = False


class Tracer:
    """Profiles the window from its start until the first request that
    ends ``TRACE_SECONDS`` in; requests begun before then carry a
    ``bench.`` span."""

    def __init__(self, log_dir: pathlib.Path | None):
        self.log_dir = log_dir
        self.on = False
        self.until = 0.0

    def start(self, t0: float) -> None:
        if self.log_dir is None:
            return
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        self.log_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.log_dir))
        self.on = True
        self.until = t0 + TRACE_SECONDS

    def span(self, name: str):
        """A ``bench.<name>`` span while the profiler runs, else nothing."""
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def after(self, now: float) -> None:
        """Stop once a request has ended past the traced head."""
        if not self.on or now < self.until:
            return
        import jax
        jax.profiler.stop_trace()
        self.on = False

    def close(self) -> None:
        self.after(math.inf)
