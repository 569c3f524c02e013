"""The control (the reference with the configuration's guarantee broken) in
the program's place fails the run's comparison, at a size a test run can
hold; ``bench/control.py`` reads it at the cells' own sizes."""

import pytest

import control
import run as bench
from harness import Req

SMALL = {"kron.fofof": {"scale": 11}, "ssb.star5": {"sf": 0.01}}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_fails_the_comparison(name, seed):
    cell = bench.load_cell(name)
    cell.config.update(SMALL[name])
    reading = control.control_gap(cell, seed)
    assert reading["count_gap"] > 0

    class _Res:
        count = reading["control_count"]
    cfg = cell.config
    qspec = cfg["queries"][cell.traffic["query"]]
    tables = bench.generator(cfg).make(
        cfg, bench.rng_for(seed, bench.STREAM_DATA))
    query = bench.load_driver("query")
    state = query.State(svc=None, query=None, tables=tables, qspec=qspec)
    checks = query.check(state, bench.reference(cfg),
                         [Req(t0=0.0, t1=1.0, result=_Res())])
    assert checks["count_gap"]["value"] > checks["count_gap"]["limit"]
