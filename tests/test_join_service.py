"""JoinService: admission/backpressure, wave batching, tenancy, metrics.

The service is the async front end over ``JoinSession`` — requests go
through a bounded queue (full → ``ServiceOverloaded``), waves group plain
executes per tenant on one session (shared plan cache), ingest
requests drive standing-query delta plans synchronously, and per-tenant
power-of-two histograms export latency/rounds/tuples_read.
"""

import numpy as np
import pytest

from repro.core.query import Query
from repro.core.relation import Relation
from repro.core.session import JoinSession
from repro.launch.join_service import JoinService, ServiceOverloaded, _Hist


def _mk(rng, n, d, cols):
    return Relation.from_arrays(
        **{c: rng.integers(0, d, n).astype(np.int32) for c in cols})


def _linear_query(rng, n=400, d=80):
    r = _mk(rng, n, d, ("a", "b"))
    s = _mk(rng, n, d, ("b", "c"))
    t = _mk(rng, n, d, ("c", "e"))
    return Query({"R": r, "S": s, "T": t},
                 [("R.b", "S.b"), ("S.c", "T.c")]), (r, s, t)


# --------------------------------------------------------------------------
# histogram format
# --------------------------------------------------------------------------

def test_hist_pow2_buckets():
    h = _Hist()
    for v in (0, 1, 2, 3, 4, 1000):
        h.record(v)
    out = h.export()
    assert out["count"] == 6 and out["sum"] == 1010
    # 0 → "0"; 1 → 2^0; 2 → 2^1; 3,4 → 2^2; 1000 → 2^10
    assert out["buckets"] == {"0": 1, "2^0": 1, "2^1": 1, "2^2": 2,
                              "2^10": 1}


# --------------------------------------------------------------------------
# admission + backpressure
# --------------------------------------------------------------------------

def test_bounded_queue_backpressure(rng):
    q, _ = _linear_query(rng, n=120, d=30)
    svc = JoinService(max_queue=2, wave_size=4, m_budget=64)
    svc.submit("a", q)
    svc.submit("a", q)
    with pytest.raises(ServiceOverloaded):
        svc.submit("a", q)
    assert svc.rejected == 1
    # draining the queue restores admission
    assert svc.run_until_idle() == 2
    fut = svc.submit("a", q)
    svc.run_until_idle()
    assert int(fut.result().count) >= 0


def test_wave_batches_and_plan_cache_share(rng):
    q, _ = _linear_query(rng, n=200, d=40)
    svc = JoinService(max_queue=16, wave_size=4, m_budget=64)
    futs = [svc.submit("a", q) for _ in range(6)]
    served = svc.run_until_idle()
    assert served == 6
    assert svc.waves == 2          # 4 + 2
    counts = {int(f.result().count) for f in futs}
    assert len(counts) == 1        # identical query, identical answer
    m = svc.metrics()
    # repeated identical queries hit the tenant session's plan cache
    assert m["tenants"]["a"]["plan_cache"]["hits"] >= 4
    assert m["tenants"]["a"]["latency_us"]["count"] == 6


def test_per_tenant_sessions_and_metrics(rng):
    qa, _ = _linear_query(rng, n=150, d=30)
    qb, _ = _linear_query(rng, n=150, d=30)
    svc = JoinService(max_queue=8, wave_size=8, m_budget=64)
    fa = svc.submit("alice", qa)
    fb = svc.submit("bob", qb)
    svc.run_until_idle()
    fa.result(), fb.result()
    m = svc.metrics()
    assert set(m["tenants"]) == {"alice", "bob"}
    for t in m["tenants"].values():
        assert t["latency_us"]["count"] == 1
        assert t["rounds"]["count"] == 1
        assert t["tuples_read"]["count"] == 1


# --------------------------------------------------------------------------
# standing queries through the service
# --------------------------------------------------------------------------

def test_service_watch_ingest_snapshot_roundtrip(rng):
    q, (r, s, t) = _linear_query(rng, n=300, d=60)
    svc = JoinService(max_queue=16, wave_size=4, m_budget=128)
    hf = svc.watch("a", q)
    svc.run_until_idle()
    sq = hf.result()
    for i in range(3):
        fut = svc.ingest("a", s, {
            "b": rng.integers(0, 60, 20).astype(np.int32),
            "c": rng.integers(0, 60, 20).astype(np.int32)})
        svc.run_until_idle()
        assert fut.result() == 20
        assert not sq.delta_rounds[-1].overflowed
    sf = svc.snapshot("a", sq)
    svc.run_until_idle()
    snap = sf.result()
    assert int(snap.count) == int(JoinSession(m_budget=128).execute(q).count)
    sq.close()


def test_service_errors_propagate_to_future(rng):
    svc = JoinService(max_queue=4, wave_size=4, m_budget=64)
    bad = _mk(rng, 50, 10, ("a", "b"))
    fut = svc.ingest("a", bad, {"wrong": np.arange(3, dtype=np.int32)})
    svc.run_until_idle()
    with pytest.raises(ValueError, match="schema"):
        fut.result()


def test_background_thread_start_stop(rng):
    q, _ = _linear_query(rng, n=120, d=30)
    svc = JoinService(max_queue=8, wave_size=4, m_budget=64)
    svc.start()
    try:
        fut = svc.submit("a", q)
        res = fut.result(timeout=300)
        assert not bool(res.overflowed)
    finally:
        svc.stop()
