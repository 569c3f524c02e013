"""Program spans (``core.spans``) over recorded traces of tiny served
queries: the request / plan / recovery-round structure, host reads that
repeat exactly, and nothing recorded with no profiler running."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_rel, skewed_keys
from repro.core.query import Query
from repro.core.relation import Relation
from repro.core.spans import to_host
from repro.launch.join_service import JoinService

MAX_ROUNDS = 2


def _skewed_linear(rng, n=600, d=60):
    """Linear 3-way query whose hub key overflows its bucket, so recovery
    runs more than one round (served with the fused 3-way root forced)."""
    r = Relation.from_arrays(a=rng.integers(0, d, n).astype(np.int32),
                             b=skewed_keys(rng, n, d, 0.5))
    s = Relation.from_arrays(b=skewed_keys(rng, n, d, 0.5),
                             c=rng.integers(0, d, n).astype(np.int32))
    t, _ = make_rel(rng, n, ("c", "e"), d)
    return Query({"R": r, "S": s, "T": t},
                 [("R.b", "S.b"), ("S.c", "T.c")])


def _serve(svc, queries):
    futs = [svc.submit("t", q, strategy="3way") for q in queries]
    svc.run_until_idle()
    return [f.result() for f in futs]


def _within(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def _per_request(spans):
    """(request span, spans inside it) per served request."""
    reqs = [sp for sp in spans if sp.name == "repro.service.request"]
    return [(rq, [sp for sp in spans if sp is not rq and _within(sp, rq)])
            for rq in reqs]


@pytest.fixture
def served(rng, record_spans):
    """Two identical skewed queries through the service, traced after a
    warm-up query (so both are plan-cache hits)."""
    q = _skewed_linear(rng)
    svc = JoinService(max_queue=8, wave_size=1, m_budget=64,
                      max_rounds=MAX_ROUNDS)
    _serve(svc, [q])
    results, spans = record_spans(lambda: _serve(svc, [q, q]))
    return q, svc, results, spans


def test_each_request_holds_one_plan_span(served):
    _, _, results, spans = served
    per_req = _per_request(spans)
    assert len(per_req) == len(results) == 2
    assert [rq.args["req"] for rq, _ in per_req] == [1, 2]
    for rq, inner in per_req:
        plans = [sp for sp in inner if sp.name == "repro.session.plan"]
        assert len(plans) == 1
        assert plans[0].args["cache_hit"] == 1
        assert rq.args["queued_us"] >= 0


def test_recovery_round_spans_match_rounds(served):
    _, _, results, spans = served
    for (_, inner), res in zip(_per_request(spans), results):
        rounds = [sp for sp in inner if sp.name == "repro.recovery.round"]
        assert res.rounds > 1
        assert len(rounds) == res.rounds
        assert [sp.args["round"] for sp in rounds] == list(range(res.rounds))
        final_ran = res.rounds == MAX_ROUNDS + 1
        assert rounds[-1].args["final"] == int(final_ran)
        assert all(sp.args["final"] == 0 for sp in rounds[:-1])
        residuals = [sp for sp in inner
                     if sp.name == "repro.recovery.residual"]
        assert len(residuals) == res.rounds - 1


def test_final_round_is_marked(rng, record_spans):
    """With one round before the exact-sized one, a query whose first
    round overflows runs the final round, and its span says so."""
    q = _skewed_linear(rng)
    svc = JoinService(max_queue=4, wave_size=1, m_budget=64, max_rounds=1)
    (res,), spans = record_spans(lambda: _serve(svc, [q]))
    rounds = [sp for sp in spans if sp.name == "repro.recovery.round"]
    assert res.rounds == 2 == len(rounds)
    assert [sp.args["final"] for sp in rounds] == [0, 1]


def test_host_reads_repeat_exactly(served):
    _, _, _, spans = served
    names = [[sp.name for sp in inner if sp.name.startswith("repro.sync.")]
             for _, inner in _per_request(spans)]
    assert names[0] == names[1]
    assert {"repro.sync.rows", "repro.sync.hist", "repro.sync.counts",
            "repro.sync.cells"} <= set(names[0])


def test_no_profiler_records_nothing(served, record_spans):
    q, svc, results, _ = served
    assert not jax.profiler.TraceAnnotation.is_enabled()
    (plain,) = _serve(svc, [q])
    assert int(plain.count) == int(results[0].count)
    assert plain.rounds == results[0].rounds
    # a trace started afterwards holds none of that query's spans
    _, spans = record_spans(lambda: None)
    assert spans == []


def test_to_host_shapes():
    assert to_host("rows", jnp.int32(7)) == 7
    assert isinstance(to_host("rows", jnp.int32(7)), int)
    pair = to_host("rows", (jnp.int32(2), jnp.int32(5)))
    assert isinstance(pair, np.ndarray) and pair.tolist() == [2, 5]
    arr = to_host("hist", jnp.arange(4))
    assert isinstance(arr, np.ndarray) and arr.tolist() == [0, 1, 2, 3]
