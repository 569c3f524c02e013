"""The traffic-driver seam of ``run.py``: a traffic file's ``op`` names the
driver module, an unknown one ends the run before any work, and a new
traffic with its own loop and check runs through ``run_cell`` when only a
driver and a traffic file are added."""

import json
import time

import pytest

import run as bench

SEED = 2**31 + 23

# A test-only driver: each request sums one column of a table on the
# device, and the check compares every sum with numpy's.  ``skew`` alters
# each answer where it is produced.
PROBE = '''
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import Req


def open(cell, devices, tables, seed):
    t = cell.traffic
    host = tables[t["table"]][t["column"]]
    state = {"host": host, "skew": t["skew"], "open": True,
             "col": jax.device_put(host, devices[0]),
             "sum": jax.jit(lambda x: jnp.sum(x, dtype=jnp.int32))}
    state["sum"](state["col"]).block_until_ready()
    return state


def window(state, seconds, tracer):
    start = time.perf_counter()
    tracer.start(start)
    out = []
    while not out or time.perf_counter() < start + seconds:
        req = Req(t0=time.perf_counter(), traced=tracer.on)
        with tracer.span("sum"):
            req.result = int(state["sum"](state["col"])) + state["skew"]
        req.t1 = time.perf_counter()
        out.append(req)
        tracer.after(req.t1)
    tracer.close()
    return out


def close(state):
    state["open"] = False


def check(state, ref, reqs):
    want = int(np.sum(state["host"], dtype=np.int64))
    return {"sum_gap": {"value": max(abs(r.result - want) for r in reqs),
                        "limit": 0},
            "checked_open": {"value": int(state["open"]), "limit": 0}}
'''


@pytest.fixture
def probe_cell(tmp_path, monkeypatch, tiny_cell):
    """A cell ``ssb.probe`` whose traffic file and driver exist only in
    ``tmp_path``, which the lookups are pointed at."""
    tiny = tiny_cell("ssb.star5").config
    (tmp_path / "drivers").mkdir()
    (tmp_path / "drivers" / "probe.py").write_text(PROBE)
    (tmp_path / "traffic").mkdir()
    monkeypatch.setattr(bench, "DRIVERS", tmp_path / "drivers")
    monkeypatch.setattr(bench, "TRAFFIC", tmp_path / "traffic")
    spec = bench.load_json(bench.ROOT / "BENCHMARK.json")
    spec["workloads"].append({"name": "ssb.probe", "config": "ssb",
                              "traffic": "probe", "chips": 1})

    def make(skew: int):
        (tmp_path / "traffic" / "probe.json").write_text(json.dumps(
            {"op": "probe", "table": "lineorder", "column": "lo_quantity",
             "skew": skew}))
        cell = bench.load_cell("ssb.probe", spec)
        cell.config.update(tiny)
        return cell
    return make


@pytest.mark.parametrize("present", ["query", "probe"])
def test_unknown_op_ends_the_run_before_any_work(present, request,
                                                 tiny_cell, monkeypatch):
    """The message lists the drivers present: the repo's, or only the
    test driver where the lookup points at its directory."""
    def work(*_a, **_kw):
        raise AssertionError("work began")
    monkeypatch.setattr(bench, "devices", work)
    monkeypatch.setattr(bench, "generator", work)
    if present == "probe":
        cell = request.getfixturevalue("probe_cell")(0)
    else:
        cell = tiny_cell("ssb.star5")
    cell.traffic["op"] = "replay"
    with pytest.raises(SystemExit, match=rf"'replay'.*\['{present}'\]"):
        bench.run_cell(cell, SEED, 1.0, False, allow_cpu=True)


@pytest.mark.parametrize("skew", [0, 1])
def test_new_traffic_runs_through_run_cell(probe_cell, skew):
    cell = probe_cell(skew)
    out = bench.run_cell(cell, SEED, 0.5, False, allow_cpu=True,
                         t_start=time.perf_counter())
    assert list(out)[-1] == "checks"
    assert out["checks"] == {"sum_gap": {"value": skew, "limit": 0},
                             "checked_open": {"value": 0, "limit": 0}}
    assert out["correct"] is (skew == 0)
    assert out["failed"] == 0 and out["attempted"] >= 1
    # the cell's end-to-end metrics: query_s lists its cells, setup_s all
    assert set(out["metrics"]) == {"setup_s"}
