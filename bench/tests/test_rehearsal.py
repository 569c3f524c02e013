"""Every traffic loop at a tiny size through the served path on the CPU,
by the harness's own functions (the command itself refuses a CPU)."""

import time

import pytest

import run as bench

SEED = 2**31 + 11
CELLS = ["kron.fofof", "ssb.star5"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_on_the_served_path(tiny_cell, name, trace):
    cell = tiny_cell(name)
    out = bench.run_cell(cell, SEED, 2.0, bool(trace), allow_cpu=True,
                         t_start=time.perf_counter())
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    wanted = {m["name"] for m in (cell.per_layer if trace
                                  else cell.end_to_end)}
    # the roofline needs the chip's peaks, which the CPU has not
    assert set(out["metrics"]) == wanted - {"root_roofline.query"}
    assert all(v["value"] > 0 or k.startswith("idle_pct")
               for k, v in out["metrics"].items())
    if trace:
        dev = out["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert 0 < len(out["breakdown"]["device_ops"]) <= 10
        assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_no_tpu_no_result(capsys):
    rc = bench.main(["--workload", "kron.fofof", "--seed", "1",
                     "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""
