"""Read the program's own spans (``repro.*``, ``src/repro/core/spans.py``)
from a run's profiler trace, beside the device operations on its clock.

    python3 bench/program_spans.py <cell>      # report on bench/traces/<cell>

The program records, on the thread that serves requests, a
``repro.service.request`` span per request (``queued_us``: admission to
start) and inside it the planning, plan-step and recovery spans and a
``repro.sync.<what>`` span at every read of a device value on the host.
The device operations are those of ``trace_reduce.read_events``, and the
window is the one ``trace_reduce`` reduces: from the first ``bench.``
span's start to the last one's end, on the first device.

Each instant of the serving thread has an innermost ``repro.`` span (or
none).  Device idle time in the window is split by it: under a ``sync``
span the device waits on a host read; inside a request but under no
``sync`` span the host is planning, counting or dispatching; outside every
request is the service's hand-off and the client.  A device operation
belongs to the span in which it starts.

A trace with no request span (a program without these spans) reads as
nothing: every metric returns None.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import pathlib
import sys
import warnings

BENCH = pathlib.Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

PREFIX = "repro."
REQUEST = "repro.service.request"
SYNC = "repro.sync."
ROUND = "repro.recovery.round"
RESIDUAL = "repro.recovery.residual"


@dataclasses.dataclass
class PSpan:
    name: str
    start: int
    end: int
    args: dict
    thread: tuple


@dataclasses.dataclass
class Segment:
    """A stretch of the serving thread with one innermost span."""
    start: int
    end: int
    name: str | None            # innermost repro. span, None outside all
    in_request: bool


@dataclasses.dataclass
class Traced:
    window: tuple[int, int]
    requests: list[PSpan]       # request spans inside the window
    spans: list[PSpan]          # the serving thread's spans, start order
    ops: list                   # first device's ops, clipped to the window
    self_s: list[float]         # each op's self seconds
    idle: list[tuple[int, int]]  # first device's idle stretches
    segments: list[Segment]

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def read_spans(path: str) -> list[PSpan]:
    """Every ``repro.`` event on the host threads of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out = []
    with warnings.catch_warnings():
        # event stats are a builtin type without __module__ (jax 0.9)
        warnings.simplefilter("ignore", DeprecationWarning)
        for p, plane in enumerate(ProfileData.from_file(path).planes):
            if plane.name.startswith("/device:"):
                continue
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        s = int(e.start_ns)
                        out.append(PSpan(e.name, s, s + int(e.duration_ns),
                                         dict(e.stats), (p, k)))
    return out


def segments(spans: list[PSpan], w0: int, w1: int) -> list[Segment]:
    """Innermost span of one thread over [w0, w1), from its nested
    spans."""
    out: list[Segment] = []
    stack: list[PSpan] = []
    t = w0

    def emit(t1):
        if t1 > t:
            top = stack[-1] if stack else None
            out.append(Segment(t, min(t1, w1), top and top.name,
                               any(s.name == REQUEST for s in stack)))

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)) + [None]:
        nxt = w1 if sp is None else max(sp.start, w0)
        while stack and stack[-1].end <= nxt:
            emit(stack[-1].end)
            t = max(t, stack.pop().end)
        emit(nxt)
        t = max(t, nxt)
        if sp is None or t >= w1:
            break
        stack.append(sp)
    return [g for g in out if g.end > g.start]


def build(ops, bench_spans, spans: list[PSpan]) -> Traced | None:
    """The window's requests, the serving thread's timeline and the first
    device's ops and idle stretches; None without a request span."""
    if not bench_spans:
        return None
    w0 = min(s.start for s in bench_spans)
    w1 = max(s.end for s in bench_spans)
    requests = sorted((s for s in spans if s.name == REQUEST
                       and w0 <= s.start and s.end <= w1),
                      key=lambda s: s.start)
    if not requests:
        return None
    thread = requests[0].thread
    mine = [s for s in spans if s.thread == thread]
    inside = [dataclasses.replace(o, start=max(o.start, w0),
                                  end=min(o.end, w1))
              for o in ops if o.end > w0 and o.start < w1]
    dev = min((o.device for o in inside), default=None)
    dev_ops = sorted((o for o in inside if o.device == dev),
                     key=lambda o: o.start)
    busy = trace_reduce._union([(o.start, o.end) for o in dev_ops])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    return Traced((w0, w1), requests, sorted(mine, key=lambda s: s.start),
                  dev_ops, trace_reduce._self_times(dev_ops), idle,
                  segments(mine, w0, w1))


_CACHE: dict[str, Traced | None] = {}


def traced(run) -> Traced | None:
    """The parsed trace of a ``--trace 1`` run, once per trace file."""
    if run.trace is None:
        return None
    try:
        path = trace_reduce.find_xplane(str(BENCH / "traces" / run.cell.name))
    except FileNotFoundError:
        return None
    if path not in _CACHE:
        ops, bench_spans = trace_reduce.read_events(path, allow_cpu=True)
        _CACHE[path] = build(ops, bench_spans, read_spans(path))
    return _CACHE[path]


# --------------------------------------------------------------------------
# what the metrics read
# --------------------------------------------------------------------------

def _overlap(a0, a1, b0, b1) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def idle_by_segment(t: Traced):
    """(segment, idle ns) for every segment the device idles in."""
    starts = [g.start for g in t.segments]
    for s, e in t.idle:
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(t.segments) and t.segments[i].start < e:
            g = t.segments[i]
            ns = _overlap(s, e, g.start, g.end)
            if ns:
                yield g, ns
            i += 1


def idle_kind(g: Segment) -> str:
    """``sync``, ``host`` (in a request, not in a sync) or ``outside``."""
    if not g.in_request:
        return "outside"
    return "sync" if g.name.startswith(SYNC) else "host"


def idle_pct(t: Traced | None, kind: str) -> float | None:
    """100 x device idle of one kind over the window."""
    if t is None:
        return None
    ns = sum(n for g, n in idle_by_segment(t) if idle_kind(g) == kind)
    return 100.0 * ns / t.window_ns


def _in_requests(t: Traced, x: int) -> bool:
    return any(r.start <= x < r.end for r in t.requests)


def host_syncs(t: Traced | None) -> float | None:
    if t is None:
        return None
    n = sum(1 for s in t.spans
            if s.name.startswith(SYNC) and _in_requests(t, s.start))
    return n / len(t.requests)


def queue_ms(t: Traced | None) -> float | None:
    if t is None:
        return None
    return sum(r.args.get("queued_us", 0) for r in t.requests) \
        / len(t.requests) / 1e3


def _recovery(sp: PSpan) -> bool:
    return (sp.name == RESIDUAL
            or (sp.name == ROUND and int(sp.args.get("round", 0)) >= 1))


def recovery_ms(t: Traced | None) -> float | None:
    """Device self ms per request of the ops that start in a re-run round
    or a residual mask."""
    if t is None:
        return None
    spans = [s for s in t.spans if _recovery(s)]
    sec = sum(sec for o, sec in zip(t.ops, t.self_s)
              if any(s.start <= o.start < s.end for s in spans))
    return 1e3 * sec / len(t.requests)


# --------------------------------------------------------------------------
# the report behind PERF.md's tables
# --------------------------------------------------------------------------

def _by_round(t: Traced) -> dict[str, float]:
    """Device self ms per request by the round or residual span an op
    starts in (``outside rounds`` for the rest)."""
    spans = [s for s in t.spans if s.name in (ROUND, RESIDUAL)]
    out: dict[str, float] = {}
    for o, sec in zip(t.ops, t.self_s):
        sp = next((s for s in spans if s.start <= o.start < s.end), None)
        key = ("outside rounds" if sp is None else
               f"{sp.name.rsplit('.', 1)[1]} {sp.args.get('round')}")
        out[key] = out.get(key, 0.0) + 1e3 * sec / len(t.requests)
    return out


def report(t: Traced, layers: dict, top: int = 10,
           slack_ns: int = 50_000) -> dict:
    n = len(t.requests)
    syncs: dict[str, int] = {}
    for s in t.spans:
        if s.name.startswith(SYNC) and _in_requests(t, s.start):
            syncs[s.name] = syncs.get(s.name, 0) + 1
    idle_ms: dict[str, float] = {}
    for g, ns in idle_by_segment(t):
        key = g.name if g.in_request else "outside requests"
        idle_ms[key] = idle_ms.get(key, 0.0) + ns / 1e6 / n
    in_req = sum(v for k, v in idle_ms.items() if k != "outside requests")
    bare = idle_ms.get(REQUEST, 0.0)
    # clock check: a host read of the fused counts ends after the fused
    # root ops dispatched before it
    root = [o for o in t.ops
            if trace_reduce.layer_of(o.module, layers) == "fused root"]
    worst = None
    for s in t.spans:
        if s.name != SYNC + "counts":
            continue
        before = [o.end for o in root if o.start < s.end]
        if before:
            late = max(before) - s.end
            worst = late if worst is None else max(worst, late)
    gaps = []
    for s, e in sorted(t.idle, key=lambda iv: iv[0] - iv[1])[:top]:
        seg = [(g, _overlap(s, e, g.start, g.end)) for g in t.segments]
        g = max(seg, key=lambda x: x[1], default=(None, 0))[0]
        name = ("outside requests" if g is None or not g.in_request
                else g.name)
        gaps.append([name, (e - s) / 1e6])
    return {
        "requests": n, "window_ms": t.window_ns / 1e6,
        "spans_per_request": sum(1 for s in t.spans
                                 if _in_requests(t, s.start)) / n,
        "queue_ms": queue_ms(t), "host_syncs": host_syncs(t),
        "syncs_per_request": {k: v / n for k, v in syncs.items()},
        "idle_ms_per_request_by_innermost_span": idle_ms,
        "idle_pct_sync": idle_pct(t, "sync"),
        "idle_pct_host": idle_pct(t, "host"),
        "idle_pct_outside": idle_pct(t, "outside"),
        "named_share_of_request_idle": (1 - bare / in_req) if in_req else None,
        "recovery_ms": recovery_ms(t),
        "device_ms_per_request_by_round": _by_round(t),
        "counts_sync_latest_root_end_past_sync_end_us": (
            None if worst is None else worst / 1e3),
        "clock_check_ok": worst is not None and worst <= slack_ns,
        "longest_idle_gaps_ms": gaps,
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = trace_reduce.find_xplane(str(BENCH / "traces" / args[0]))
    ops, bench_spans = trace_reduce.read_events(path, allow_cpu=True)
    t = build(ops, bench_spans, read_spans(path))
    if t is None:
        print(f"{path}: no request span in the window", file=sys.stderr)
        return 1
    layers = trace_reduce.load_layers(BENCH / "layers.json")
    print(json.dumps(report(t, layers)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
