"""Reduce a JAX profiler trace to device busy time, device time by layer,
and the breakdown a run prints.

Device operations are the events of each device plane's ``XLA Ops`` line
(``/device:TPU:<k>``).  A ``while`` op's event encloses the events of the
ops in its body, so each operation is given its self time: its interval
less those of the operations nested in it.  A trace with no such event is
refused.  Only where the caller says the run is on the CPU
(``allow_cpu``), which has no device plane, do the events on host threads
that carry an ``hlo_module`` stat stand in for the operations, so that the
reduction can be checked without a chip.  An operation's module is its
``hlo_module`` stat, or else the ``XLA Modules`` event it starts in;
:func:`module_key` turns ``jit_stable_order(12)`` into ``stable_order``,
the name that ``layers.json`` maps to a layer.

Inside the traced window (the benchmark's own request spans), busy time is
the union of the operation intervals, averaged over the devices, and the
idle share is 1 - busy / window; device time by module and by layer is the
sum of self times, so the layers add up to the busy time.  An idle gap is a
stretch of the window with no operation on the device, named by the
benchmark span it falls in and the modules on either side of it.
"""

from __future__ import annotations

import bisect
import dataclasses
import fnmatch
import glob
import json
import os
import re
import warnings

SPAN_PREFIX = "bench."


def module_key(name: str) -> str:
    """``jit_stable_order(12)`` / ``jit__gather_core.3`` -> bare name."""
    name = re.sub(r"\(\d+\)$", "", name)
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def load_layers(path: str) -> dict[str, list[str]]:
    with open(path) as f:
        return json.load(f)["layers"]


def layer_of(module: str, layers: dict[str, list[str]]) -> str:
    for layer, patterns in layers.items():
        if any(fnmatch.fnmatchcase(module, p) for p in patterns):
            return layer
    return "other"


@dataclasses.dataclass
class Op:
    device: str
    name: str
    module: str
    start: int
    end: int


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Reduced:
    window_s: float                      # traced window, host clock
    busy_s: float                        # union of op intervals, per device
    module_s: dict[str, float]           # device seconds by module
    layer_s: dict[str, float]            # device seconds by layer
    top_ops: list[list]                  # [[module:op, seconds], ...]
    idle_gaps: list[list]                # [[label, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def read_events(path: str, allow_cpu: bool = False
                ) -> tuple[list[Op], list[Span]]:
    """Device operations and the benchmark's spans from one ``.xplane.pb``.

    Raises ``ValueError`` where the trace holds no device ``XLA Ops``
    event, unless ``allow_cpu``: then host-thread events stand in."""
    from jax.profiler import ProfileData
    with warnings.catch_warnings():
        # event stats are a builtin type without __module__ (jax 0.9)
        warnings.simplefilter("ignore", DeprecationWarning)
        ops, host_ops, spans = _read_events(ProfileData.from_file(path))
    if ops:
        return ops, spans
    if allow_cpu and host_ops:
        return host_ops, spans
    raise ValueError(f"{path}: no device XLA Ops event in the trace")


def _read_events(pd) -> tuple[list[Op], list[Op], list[Span]]:
    ops: list[Op] = []
    spans: list[Span] = []
    host_ops: list[Op] = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:") and "TPU" in plane.name
        lines = {line.name: line for line in plane.lines}
        if device and "XLA Ops" in lines:
            mod_line = lines["XLA Modules"].events if "XLA Modules" in lines \
                else ()
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           module_key(e.name)) for e in mod_line)
            starts = [m[0] for m in mods]
            for e in lines["XLA Ops"].events:
                s, d = int(e.start_ns), int(e.duration_ns)
                mod = _stats(e).get("hlo_module")
                if mod is None:
                    i = bisect.bisect_right(starts, s) - 1
                    mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
                ops.append(Op(plane.name, e.name.split(" = ")[0],
                              module_key(str(mod)), s, s + d))
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    s = int(e.start_ns)
                    spans.append(Span(e.name, s, s + int(e.duration_ns)))
                    continue
                st = _stats(e)
                if "hlo_module" in st and e.duration_ns > 0:
                    s = int(e.start_ns)
                    host_ops.append(Op("cpu", str(st.get("hlo_op", e.name)),
                                       module_key(str(st["hlo_module"])),
                                       s, s + int(e.duration_ns)))
    return ops, host_ops, spans


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(ops: list[Op]) -> list[float]:
    """Seconds of each op (clipped intervals, one device) not covered by
    an op that starts inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    self_ns = [ops[i].end - ops[i].start for i in range(len(ops))]
    stack: list[int] = []
    for i in order:
        op = ops[i]
        while stack and ops[stack[-1]].end <= op.start:
            stack.pop()
        if stack:
            parent = ops[stack[-1]]
            self_ns[stack[-1]] -= min(op.end, parent.end) - op.start
        stack.append(i)
    return [max(0, ns) / 1e9 for ns in self_ns]


def reduce(ops: list[Op], spans: list[Span],
           layers: dict[str, list[str]], top: int = 10) -> Reduced:
    """Per-layer device time, busy time and the breakdown, over the window
    from the first benchmark span's start to the last one's end.  Raises
    ``ValueError`` where no span or no operation falls in the window."""
    if not spans:
        raise ValueError("the trace holds no benchmark span")
    w0 = min(s.start for s in spans)
    w1 = max(s.end for s in spans)
    inside = [dataclasses.replace(o, start=max(o.start, w0),
                                  end=min(o.end, w1))
              for o in ops if o.end > w0 and o.start < w1]
    if not inside:
        raise ValueError("no device operation falls inside the traced window")
    devices = sorted({o.device for o in inside})
    module_s: dict[str, float] = {}
    op_s: dict[str, float] = {}
    busy_ns = 0
    gaps: list[list] = []
    for dev in devices:
        mine = [o for o in inside if o.device == dev]
        for o, sec in zip(mine, _self_times(mine)):
            module_s[o.module] = module_s.get(o.module, 0.0) + sec
            key = f"{o.module}:{o.name}"
            op_s[key] = op_s.get(key, 0.0) + sec
        merged = _union([(o.start, o.end) for o in mine])
        busy_ns += sum(e - s for s, e in merged)
        if dev == devices[0]:
            gaps = _gaps(merged, mine, spans, w0, w1)
    layer_s: dict[str, float] = {}
    for mod, sec in module_s.items():
        layer = layer_of(mod, layers)
        layer_s[layer] = layer_s.get(layer, 0.0) + sec
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / len(devices) / 1e9,
        module_s=module_s, layer_s=layer_s,
        top_ops=[[k, v] for k, v in sorted(op_s.items(),
                                           key=lambda kv: -kv[1])[:top]],
        idle_gaps=gaps[:top])


def _gaps(merged, ops, spans, w0, w1) -> list[list]:
    """Idle stretches of one device, each named by the span it starts in
    and the modules before and after it."""
    by_end = sorted(ops, key=lambda o: o.end)
    ends = [o.end for o in by_end]
    by_start = sorted(ops, key=lambda o: o.start)
    starts = [o.start for o in by_start]
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    out = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        i = bisect.bisect_right(ends, s) - 1
        j = bisect.bisect_left(starts, e)
        before = by_end[i].module if i >= 0 else "start"
        after = by_start[j].module if j < len(by_start) else "end"
        span = next((sp.name for sp in spans if sp.start <= s < sp.end),
                    "between requests")
        out.append([f"{span}: {before} -> {after}", (e - s) / 1e9])
    return out


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def layer_ms_per_request(run, layer: str) -> float | None:
    """Device ms per traced request in ``layer``; None where the trace
    holds no module of that layer."""
    if run.trace is None or not run.traced:
        return None
    sec = run.trace.layer_s.get(layer)
    if not sec:
        return None
    return 1e3 * sec / len(run.traced)
