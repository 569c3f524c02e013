"""A run with the timed path broken underneath comes out not correct: once
for each fault a cell can have.  One chip, so no exchange between chips
can be left out."""

import dataclasses
import time

import pytest

import run as bench
from repro.core.relation import Relation
from repro.core.session import JoinSession

SEED = 7


def _run(cell):
    return bench.run_cell(cell, SEED, 1.0, False, allow_cpu=True,
                          t_start=time.perf_counter())


@pytest.mark.parametrize("name", ["kron.fofof", "ssb.star5"])
def test_query_answer_altered(tiny_cell, monkeypatch, name):
    orig = JoinSession.execute

    def off_by_one(self, *a, **kw):
        res = orig(self, *a, **kw)
        return dataclasses.replace(res, count=res.count + 1)
    monkeypatch.setattr(JoinSession, "execute", off_by_one)
    out = _run(tiny_cell(name))
    assert out["correct"] is False
    assert out["checks"]["count_gap"]["value"] == 1


@pytest.mark.parametrize("name", ["kron.fofof", "ssb.star5"])
def test_half_of_each_input_left_out(tiny_cell, monkeypatch, name):
    orig = Relation.from_arrays.__func__

    def half(cls, capacity=None, **cols):
        return orig(cls, capacity,
                    **{c: v[: len(v) // 2] for c, v in cols.items()})
    monkeypatch.setattr(Relation, "from_arrays", classmethod(half))
    out = _run(tiny_cell(name))
    assert out["correct"] is False
    assert out["checks"]["count_gap"]["value"] > 0


def test_query_answer_never_comes(tiny_cell, monkeypatch):
    def lost(self, *a, **kw):
        raise RuntimeError("answer lost")
    monkeypatch.setattr(JoinSession, "execute", lost)
    cell = tiny_cell("ssb.star5")
    cell.traffic["warmup"] = 0
    out = _run(cell)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] > 0
    assert out["checks"]["unanswered"]["value"] == out["attempted"]
