#!/usr/bin/env python3
"""Chip benchmark of the multiway join service: one cell of BENCHMARK.json.

    python bench/run.py --workload kron.fofof --seed 7 --seconds 30 --trace 0

A cell names a configuration (``bench/configs/<config>.json``: its sizes,
the generator module under ``bench/gen/``, its queries, its guarantee and
its plain reference under ``bench/refs/``) and a traffic mix
(``bench/traffic/<traffic>.json``), whose ``op`` names the driver
``bench/drivers/<op>.py`` (the interface is in ``drivers/__init__.py``).
A run makes the tables from ``--seed`` on the host; the driver puts them
on the chip, starts the service and warms up the cell's own shapes, which
ends the set-up.  The driver then drives the service for ``--seconds``:
the request in flight at the deadline completes and counts.  Once the
service is stopped the driver compares every answer of the window with
the reference.

``--trace 0`` prints the cell's end-to-end metrics.  ``--trace 1`` profiles
the first seconds of the window and prints the cell's per-layer metrics,
each read by ``bench/metrics/<name>.py`` from the results and the reduced
trace (``bench/trace_reduce.py``).  The numbers compared with the reference, each
with its limit, are the last lines on standard error and the last key of
the result, which is the last line on standard output.  The number of
compilations inside the window is printed on an earlier line.

Without a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402
from harness import STREAM_DATA, Req, Tracer, log, rng_for  # noqa: E402

TRAFFIC = BENCH / "traffic"
DRIVERS = BENCH / "drivers"


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file under ``bench/`` by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# the cell
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _for_cell(metrics: list[dict], name: str) -> list[dict]:
    return [m for m in metrics if name in m.get("workloads", (name,))]


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(BENCH / "configs" / f"{w['config']}.json"),
                traffic=load_json(TRAFFIC / f"{w['traffic']}.json"),
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))


# --------------------------------------------------------------------------
# device, compile cache, compile counter
# --------------------------------------------------------------------------

def devices(chips: int, allow_cpu: bool = False) -> list:
    import jax
    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``
    (the program's own default), at a fixed path so every run of the
    checkout after the first finds its programs there."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Compiles:
    """Programs built (``backend_compile_duration`` events, which also fire
    for a program read back from the persistent cache) and persistent-cache
    loads, from ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring
        self.built: list[str] = []
        self.loaded = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.built.append(str(kw.get("fun_name", "?")))

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def snapshot(self) -> tuple[int, int]:
        return len(self.built), self.loaded


# --------------------------------------------------------------------------
# data, reference and driver
# --------------------------------------------------------------------------

def generator(cfg: dict):
    return load_module(BENCH / "gen" / f"{cfg['generator']}.py")


def reference(cfg: dict):
    return load_module(BENCH / "refs" / f"{cfg.get('reference', 'acyclic')}.py")


def load_driver(op: str):
    """``drivers/<op>.py``; an ``op`` with no driver ends the run."""
    path = DRIVERS / f"{op}.py"
    if not path.is_file():
        present = sorted(p.stem for p in DRIVERS.glob("*.py")
                         if p.stem != "__init__")
        raise SystemExit(f"unknown traffic op {op!r}; drivers present in "
                         f"{DRIVERS}: {present}")
    return load_module(path)


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers see."""

    cell: Cell
    setup_s: float
    window_s: float                   # first send to last answer
    requests: list[Req]
    base_rows: dict[str, int]         # rows of each table when set up
    peaks: dict | None                # this device's row of peaks.json
    trace: trace_reduce.Reduced | None = None

    @property
    def answered(self) -> list[Req]:
        return [r for r in self.requests if r.t1 is not None and not r.error]

    @property
    def traced(self) -> list[Req]:
        return [r for r in self.answered if r.traced]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             allow_cpu: bool = False, t_start: float | None = None) -> dict:
    """Set up, run the window, check every answer; return the result
    line as a dict.  Raises :class:`NoDevice` before any work."""
    t_start = T_START if t_start is None else t_start
    driver = load_driver(cell.traffic["op"])
    devs = devices(cell.chips, allow_cpu)

    log(f"compile cache: {enable_compile_cache()}")
    compiles = Compiles()
    peaks_all = load_json(BENCH / "peaks.json")["devices"]
    kind = devs[0].device_kind
    if kind not in peaks_all and not allow_cpu:
        raise SystemExit(f"bench/peaks.json has no row for {kind!r}")
    cfg = cell.config
    gen, ref = generator(cfg), reference(cfg)
    tables = gen.make(cfg, rng_for(seed, STREAM_DATA))
    base_rows = {n: len(next(iter(t.values()))) for n, t in tables.items()}
    tracer = Tracer(BENCH / "traces" / cell.name if trace else None)
    state = driver.open(cell, devs, tables, seed)
    try:
        setup_s = time.perf_counter() - t_start
        c0 = compiles.snapshot()
        reqs = driver.window(state, seconds, tracer)
        c1 = compiles.snapshot()
        built, loaded = c1[0] - c0[0], c1[1] - c0[1]
        log(f"window: {len(reqs)} requests; compilations inside the window "
            f"{built - loaded}, programs read from the persistent cache "
            f"inside the window {loaded}")
        if built:
            log(f"programs built inside the window: "
                f"{sorted(set(compiles.built[c0[0]:]))}")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    finally:
        tracer.close()
        driver.close(state)
    done = [r for r in reqs if r.t1 is not None]
    window_s = max(r.t1 for r in done) - min(r.t0 for r in reqs)
    run = Run(cell=cell, setup_s=setup_s, window_s=window_s,
              requests=reqs, base_rows=base_rows,
              peaks=peaks_all.get(kind))
    if trace:
        path = trace_reduce.find_xplane(str(BENCH / "traces" / cell.name))
        ops, spans = trace_reduce.read_events(path, allow_cpu)
        run.trace = trace_reduce.reduce(
            ops, spans, trace_reduce.load_layers(BENCH / "layers.json"))

    # -- the check, once the window has closed and the peak is read --------
    checks = driver.check(state, ref, reqs)
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    metrics = cell.per_layer if trace else cell.end_to_end
    values = {}
    for m in metrics:
        v = read_metric(m["name"], run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct and run.answered),
           "attempted": len(reqs),
           "failed": len(reqs) - len(run.answered),
           "metrics": values, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = checks
    return out


def read_metric(name: str, run: Run):
    return load_module(BENCH / "metrics" / f"{name}.py").read(run)


# --------------------------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoDevice as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
