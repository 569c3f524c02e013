"""The program-span reader: the idle split, the queue mean and recovery
attribution on hand-built intervals, and the split against the trace
reduction's idle share on a trace recorded on the CPU."""

import jax
import numpy as np
import pytest

import program_spans as ps
import trace_reduce as tr

LAYERS = {"fused root": ["fused_count3_*"]}


def _sp(name, start, end, thread=(0, 0), **args):
    return ps.PSpan(f"repro.{name}", start, end, args, thread)


@pytest.fixture
def hand_built():
    """Window [0, 1000]; two requests on the serving thread, one with a
    re-run round; a read on another thread that must not count."""
    spans = [
        _sp("service.request", 100, 600, req=0, queued_us=10),
        _sp("session.plan", 100, 150, cache_hit=1),
        _sp("recovery.round", 150, 400, round=0, final=0),
        _sp("sync.counts", 300, 400, n=4),
        _sp("recovery.residual", 400, 450, round=0),
        _sp("recovery.round", 450, 600, round=1, final=0),
        _sp("sync.hist", 500, 550, n=8),
        _sp("service.request", 700, 950, req=1, queued_us=30),
        _sp("sync.rows", 700, 720, n=3),
        _sp("sync.rows", 600, 700, thread=(0, 1), n=1),
    ]
    ops = [tr.Op("d", "a", "x", 0, 50),
           tr.Op("d", "b", "fused_count3_linear", 160, 300),
           tr.Op("d", "c", "x", 410, 440),
           tr.Op("d", "e", "fused_count3_linear", 460, 500),
           tr.Op("d", "f", "x", 730, 900)]
    return ps.build(ops, [tr.Span("bench.query", 0, 1000)], spans)


def test_idle_splits_into_sync_host_and_outside(hand_built):
    t = hand_built
    assert t.idle == [(50, 160), (300, 410), (440, 460), (500, 730),
                      (900, 1000)]
    # sync: counts 100 + hist 50 + rows 20 (the other thread's read is
    # not the serving thread's); host: plan 50, rounds 10 + 10 + 50,
    # residual 10 + 10, bare request 10 + 50; outside: 50 + 100 + 50
    assert ps.idle_pct(t, "sync") == pytest.approx(17.0)
    assert ps.idle_pct(t, "host") == pytest.approx(20.0)
    assert ps.idle_pct(t, "outside") == pytest.approx(20.0)
    idle = sum(e - s for s, e in t.idle)
    assert sum(ps.idle_pct(t, k) for k in ("sync", "host", "outside")) \
        == pytest.approx(100.0 * idle / t.window_ns)


def test_queue_mean_and_sync_count(hand_built):
    assert ps.queue_ms(hand_built) == pytest.approx(0.020)
    assert ps.host_syncs(hand_built) == pytest.approx(1.5)


def test_recovery_is_attributed_by_op_start(hand_built):
    # ops c (in the residual, 30 ns) and e (in round 1, 40 ns); op b starts
    # in round 0 and is not a re-run
    assert ps.recovery_ms(hand_built) == pytest.approx(1e3 * 70e-9 / 2)


def test_report_names_gaps_and_checks_the_clock(hand_built):
    rep = ps.report(hand_built, LAYERS)
    assert rep["syncs_per_request"] == {"repro.sync.counts": 0.5,
                                        "repro.sync.hist": 0.5,
                                        "repro.sync.rows": 0.5}
    # the counts read [300, 400] ends 100 ns after the last fused op
    # started before its end (b, [160, 300])
    assert rep["clock_check_ok"] is True
    assert rep["counts_sync_latest_root_end_past_sync_end_us"] == \
        pytest.approx(-0.1)
    # the longest gap, [500, 730], lies mostly between the two requests
    assert rep["longest_idle_gaps_ms"][0] == ["outside requests",
                                              pytest.approx(230e-6)]
    assert ["repro.sync.counts", pytest.approx(110e-6)] in \
        rep["longest_idle_gaps_ms"]
    assert rep["device_ms_per_request_by_round"] == pytest.approx({
        "outside rounds": 1e3 * 220e-9 / 2, "round 0": 1e3 * 140e-9 / 2,
        "residual 0": 1e3 * 30e-9 / 2, "round 1": 1e3 * 40e-9 / 2})
    assert rep["named_share_of_request_idle"] == pytest.approx(
        1 - 60 / 370)


def test_no_request_span_reads_nothing():
    ops = [tr.Op("d", "a", "x", 0, 50)]
    assert ps.build(ops, [tr.Span("bench.query", 0, 100)], []) is None
    for read in (ps.queue_ms, ps.host_syncs, ps.recovery_ms):
        assert read(None) is None
    assert ps.idle_pct(None, "sync") is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two served requests of a skewed linear query, each in a
    ``bench.query`` span, with the pump on its own thread."""
    from repro.core import Query, Relation
    from repro.launch.join_service import JoinService
    rng = np.random.default_rng(7)
    n, d = 2000, 50
    hub = np.where(rng.random(n) < 0.5, 1, rng.integers(0, d, n))
    r = Relation.from_arrays(a=rng.integers(0, d, n), b=hub)
    s = Relation.from_arrays(b=np.roll(hub, 7), c=rng.integers(0, d, n))
    t = Relation.from_arrays(c=rng.integers(0, d, n), e=rng.integers(0, d, n))
    q = Query({"R": r, "S": s, "T": t}, [("R.b", "S.b"), ("S.c", "T.c")])
    svc = JoinService(max_queue=4, wave_size=1, m_budget=128)
    svc.start()
    log_dir = tmp_path_factory.mktemp("spans")
    try:
        svc.submit("t", q, strategy="3way").result()
        jax.profiler.start_trace(str(log_dir))
        results = []
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.query"):
                results.append(svc.submit("t", q, strategy="3way").result())
        jax.profiler.stop_trace()
    finally:
        svc.stop()
    return tr.find_xplane(str(log_dir)), results


def test_split_is_within_the_idle_share(recorded):
    path, results = recorded
    ops, bench_spans = tr.read_events(path, allow_cpu=True)
    red = tr.reduce(ops, bench_spans, LAYERS)
    t = ps.build(ops, bench_spans, ps.read_spans(path))
    assert len(t.requests) == 2
    split = ps.idle_pct(t, "sync") + ps.idle_pct(t, "host")
    assert 0 < split <= 100.0 * red.idle_share + 1e-9
    assert ps.host_syncs(t) == int(ps.host_syncs(t)) > 0
    assert results[0].rounds > 1 and ps.recovery_ms(t) > 0
    assert ps.queue_ms(t) >= 0
