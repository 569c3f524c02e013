"""Standing queries + ingest: delta execution == from-scratch, exactly.

Tentpole property: for any query kind and any append schedule,
``watch(q); append*(deltas); snapshot()`` equals executing the final state
from scratch — with ``overflowed == False`` on every delta round.  Also
covers the ingest API itself (append is THE mutation point; direct array
mutation raises; versions bump; sketches update incrementally) and the
plan-cache drift behavior under incremental sketch updates (±5% absorbs
into delta execution, a ≥4x resize re-plans + refreshes).
"""

import dataclasses
from collections import defaultdict

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import make_rel, skewed_keys
from repro.core import sketches
from repro.core.query import Query
from repro.core.relation import Relation
from repro.core.session import JoinSession, QueryResult
from repro.core.streaming import (
    StandingQuery, mask_to_families, touched_families)


# --------------------------------------------------------------------------
# oracles (independent of the engine)
# --------------------------------------------------------------------------

def _np_cols(rel, cols):
    ok = np.asarray(rel.valid)
    return {c: np.asarray(rel.col(c))[ok] for c in cols}


def oracle_linear(r, s, t):
    rd, sd, td = (_np_cols(r, ("b",)), _np_cols(s, ("b", "c")),
                  _np_cols(t, ("c",)))
    rb = defaultdict(int)
    for v in rd["b"].tolist():
        rb[v] += 1
    tc = defaultdict(int)
    for v in td["c"].tolist():
        tc[v] += 1
    return sum(rb.get(b, 0) * tc.get(c, 0)
               for b, c in zip(sd["b"].tolist(), sd["c"].tolist()))


def oracle_cyclic(r, s, t):
    rd = _np_cols(r, ("a", "b"))
    sd = _np_cols(s, ("b", "c"))
    td = _np_cols(t, ("c", "a"))
    sc = defaultdict(list)
    for b, c in zip(sd["b"].tolist(), sd["c"].tolist()):
        sc[b].append(c)
    ta = defaultdict(int)
    for c, a in zip(td["c"].tolist(), td["a"].tolist()):
        ta[(c, a)] += 1
    total = 0
    for a, b in zip(rd["a"].tolist(), rd["b"].tolist()):
        for c in sc.get(b, ()):
            total += ta.get((c, a), 0)
    return total


def oracle_star(f, d1, d2):
    fd = _np_cols(f, ("a", "b"))
    c1 = defaultdict(int)
    for v in _np_cols(d1, ("a",))["a"].tolist():
        c1[v] += 1
    c2 = defaultdict(int)
    for v in _np_cols(d2, ("b",))["b"].tolist():
        c2[v] += 1
    return sum(c1.get(a, 0) * c2.get(b, 0)
               for a, b in zip(fd["a"].tolist(), fd["b"].tolist()))


# --------------------------------------------------------------------------
# ingest API: append is THE mutation point
# --------------------------------------------------------------------------

def test_append_is_only_mutation_point(rng):
    rel, _ = make_rel(rng, 50, ("a", "b"), 10)
    with pytest.raises(TypeError):
        rel.columns["a"] = jnp.zeros(50, jnp.int32)
    with pytest.raises(TypeError):
        del rel.columns["a"]
    with pytest.raises(dataclasses.FrozenInstanceError):
        rel.valid = jnp.zeros(50, bool)


def test_append_schema_and_shape_checks(rng):
    rel, _ = make_rel(rng, 20, ("a", "b"), 10)
    with pytest.raises(ValueError, match="schema"):
        rel.append(a=np.arange(3, dtype=np.int32))
    with pytest.raises(ValueError, match="ragged"):
        rel.append(a=np.arange(3, dtype=np.int32),
                   b=np.arange(4, dtype=np.int32))


def test_append_versions_capacity_and_rows(rng):
    rel, data = make_rel(rng, 60, ("a", "b"), 10)
    assert rel.version == 0
    delta = rel.append(a=np.arange(5, dtype=np.int32),
                       b=np.arange(5, dtype=np.int32))
    assert rel.version == 1
    assert int(delta.n) == 5
    assert int(rel.n) == 65
    # capacity grows along power-of-two buckets
    assert rel.capacity == 128
    # live rows keep the original data then the delta, as a valid prefix
    a = np.asarray(rel.col("a"))[np.asarray(rel.valid)]
    np.testing.assert_array_equal(a[:60], data["a"])
    np.testing.assert_array_equal(a[60:], np.arange(5))
    # in-bucket appends do not re-grow
    rel.append(a=np.arange(3, dtype=np.int32),
               b=np.arange(3, dtype=np.int32))
    assert rel.capacity == 128 and rel.version == 2


def test_append_updates_sketches_incrementally(rng):
    rel, _ = make_rel(rng, 200, ("a", "b"), 64)
    before = rel.distinct_sketch("a")          # force + cache
    new = rng.integers(64, 128, 40).astype(np.int32)
    rel.append(a=new, b=rng.integers(0, 64, 40).astype(np.int32))
    got = rel.distinct_sketch("a")
    want = sketches.add(sketches.empty(), rel.col("a"), rel.valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the incremental update actually changed the registers
    assert not np.array_equal(np.asarray(before), np.asarray(got))


def test_append_observers_fire_and_unregister(rng):
    rel, _ = make_rel(rng, 30, ("a", "b"), 10)
    seen = []
    cb = lambda r, d: seen.append(int(d.n))  # noqa: E731
    rel.on_append(cb)
    rel.append(a=np.arange(4, dtype=np.int32),
               b=np.arange(4, dtype=np.int32))
    assert seen == [4]
    rel.remove_on_append(cb)
    rel.append(a=np.arange(2, dtype=np.int32),
               b=np.arange(2, dtype=np.int32))
    assert seen == [4]


# --------------------------------------------------------------------------
# family masking is exact
# --------------------------------------------------------------------------

def test_family_mask_keeps_all_possible_matches(rng):
    rel, rd = make_rel(rng, 500, ("b", "c"), 120)
    delta = Relation.from_arrays(b=rng.integers(0, 30, 16).astype(np.int32),
                                 c=rng.integers(0, 30, 16).astype(np.int32))
    touched = touched_families(delta, "b")
    masked = mask_to_families(rel, "b", touched)
    kept = set(np.asarray(masked.col("b"))[np.asarray(masked.valid)]
               .tolist())
    # every row whose key occurs in the delta must survive the mask
    for v in np.asarray(delta.col("b")).tolist():
        rows = np.asarray(rel.col("b"))[np.asarray(rel.valid)] == v
        if rows.any():
            assert v in kept
    assert int(masked.n) <= int(rel.n)


# --------------------------------------------------------------------------
# tentpole property: snapshot == from-scratch across kinds
# --------------------------------------------------------------------------

def _mk(rng, n, d, cols):
    return Relation.from_arrays(
        **{c: rng.integers(0, d, n).astype(np.int32) for c in cols})


@settings(deadline=None, max_examples=6)
@given(kind=st.sampled_from(["linear", "cyclic", "star"]),
       seed=st.integers(0, 2**31 - 1),
       n_deltas=st.integers(1, 3))
def test_standing_query_matches_from_scratch(kind, seed, n_deltas):
    rng = np.random.default_rng(seed)
    n, d = 400, 80
    if kind == "linear":
        rels = {"R": _mk(rng, n, d, ("a", "b")),
                "S": _mk(rng, n, d, ("b", "c")),
                "T": _mk(rng, n, d, ("c", "e"))}
        preds = [("R.b", "S.b"), ("S.c", "T.c")]
        oracle = lambda: oracle_linear(rels["R"], rels["S"], rels["T"])  # noqa: E731
    elif kind == "cyclic":
        rels = {"R": _mk(rng, n, d, ("a", "b")),
                "S": _mk(rng, n, d, ("b", "c")),
                "T": _mk(rng, n, d, ("c", "a"))}
        preds = [("R.b", "S.b"), ("S.c", "T.c"), ("T.a", "R.a")]
        oracle = lambda: oracle_cyclic(rels["R"], rels["S"], rels["T"])  # noqa: E731
    else:
        rels = {"F": _mk(rng, 4 * n, d, ("a", "b")),
                "D1": _mk(rng, d, d, ("a", "x")),
                "D2": _mk(rng, d, d, ("b", "y"))}
        preds = [("F.a", "D1.a"), ("F.b", "D2.b")]
        oracle = lambda: oracle_star(rels["F"], rels["D1"], rels["D2"])  # noqa: E731
    q = Query(rels, preds)
    sess = JoinSession(m_budget=128)
    sq = sess.watch(q)
    assert sq.count == oracle()
    names = list(rels)
    for i in range(n_deltas):
        name = names[int(rng.integers(0, len(names)))]
        rel = rels[name]
        k = int(rng.integers(1, 60))
        rel.append(**{c: rng.integers(0, d, k).astype(np.int32)
                      for c in rel.columns})
        assert not sq.delta_rounds[-1].overflowed
    snap = sq.snapshot()
    assert isinstance(snap, QueryResult)
    assert int(snap.count) == oracle()
    assert int(JoinSession(m_budget=128).execute(q).count) == oracle()
    assert not bool(snap.overflowed)
    sq.close()


def test_standing_query_adversarial_skew_delta(rng):
    """A delta that is one giant heavy hitter: the per-round recovery
    contract must hold (overflowed False, exact count)."""
    n, d = 600, 100
    R = _mk(rng, n, d, ("a", "b"))
    S = _mk(rng, n, d, ("b", "c"))
    T = _mk(rng, n, d, ("c", "e"))
    q = Query({"R": R, "S": S, "T": T}, [("R.b", "S.b"), ("S.c", "T.c")])
    sq = JoinSession(m_budget=128).watch(q)
    S.append(b=skewed_keys(rng, 80, d, 0.9),
             c=skewed_keys(rng, 80, d, 0.9, 2))
    rec = sq.delta_rounds[-1]
    assert not rec.overflowed
    assert int(sq.snapshot().count) == oracle_linear(R, S, T)
    sq.close()


def test_standing_query_cascade_merges_intermediates(rng):
    """Forced-cascade plans keep the binary %i intermediates resident and
    append-merge each delta's contribution instead of recomputing."""
    n, d = 500, 90
    R = _mk(rng, n, d, ("a", "b"))
    S = _mk(rng, n, d, ("b", "c"))
    T = _mk(rng, n, d, ("c", "e"))
    q = Query({"R": R, "S": S, "T": T}, [("R.b", "S.b"), ("S.c", "T.c")])
    sq = JoinSession(m_budget=128).watch(q, strategy="cascade")
    assert sq._intermediates            # cascade materialized %i0
    resident = next(iter(sq._intermediates.values()))
    rows0 = int(resident.n)
    R.append(a=rng.integers(0, d, 40).astype(np.int32),
             b=rng.integers(0, d, 40).astype(np.int32))
    assert not sq.delta_rounds[-1].replanned
    assert int(resident.n) >= rows0     # merged, not rebuilt
    assert int(sq.snapshot().count) == oracle_linear(R, S, T)
    sq.close()


def test_standing_query_4way_chain(rng):
    n, d = 400, 80
    rels = {"A": _mk(rng, n, d, ("a", "b")), "B": _mk(rng, n, d, ("b", "c")),
            "C": _mk(rng, n, d, ("c", "e")), "D": _mk(rng, n, d, ("e", "f"))}
    q = Query(rels, [("A.b", "B.b"), ("B.c", "C.c"), ("C.e", "D.e")])
    sq = JoinSession(m_budget=128).watch(q)
    for name in ("A", "C", "D"):
        rels[name].append(**{c: rng.integers(0, d, 30).astype(np.int32)
                             for c in rels[name].columns})
    assert int(sq.snapshot().count) == int(
        JoinSession(m_budget=128).execute(q).count)
    sq.close()


def test_aliased_relation_falls_back_to_refresh(rng):
    """One object bound under two names: the single-occurrence delta rule
    does not apply, so the standing query must full-refresh (exactly)."""
    n, d = 300, 60
    X = _mk(rng, n, d, ("a", "b"))
    Y = _mk(rng, n, d, ("b", "a"))
    q = Query({"P": X, "Q": Y, "P2": X}, [("P.b", "Q.b"), ("Q.a", "P2.a")])
    sq = JoinSession(m_budget=128).watch(q)
    X.append(a=rng.integers(0, d, 25).astype(np.int32),
             b=rng.integers(0, d, 25).astype(np.int32))
    assert sq.delta_rounds[-1].replanned      # refresh path taken
    assert int(sq.snapshot().count) == int(
        JoinSession(m_budget=128).execute(q).count)
    sq.close()


# --------------------------------------------------------------------------
# drift: small deltas keep the plan, big resizes re-plan + refresh
# --------------------------------------------------------------------------

def test_small_drift_keeps_plan_big_drift_replans(rng):
    n, d = 1000, 150
    R = _mk(rng, n, d, ("a", "b"))
    S = _mk(rng, n, d, ("b", "c"))
    T = _mk(rng, n, d, ("c", "e"))
    q = Query({"R": R, "S": S, "T": T}, [("R.b", "S.b"), ("S.c", "T.c")])
    sess = JoinSession(m_budget=128)
    sq = sess.watch(q)
    plan0 = sq._plan
    # ±5%-scale delta: same log-bucketed cache key, no re-plan
    R.append(a=rng.integers(0, d, 30).astype(np.int32),
             b=rng.integers(0, d, 30).astype(np.int32))
    assert not sq.delta_rounds[-1].replanned
    assert sq._plan is plan0
    # ≥4x growth in one relation: key moves, session re-plans, the
    # standing query refreshes off the fresh plan
    k = 4 * n
    T.append(c=rng.integers(0, d, k).astype(np.int32),
             e=rng.integers(0, d, k).astype(np.int32))
    assert sq.delta_rounds[-1].replanned
    assert sq._plan is not plan0
    assert int(sq.snapshot().count) == oracle_linear(R, S, T)
    sq.close()


def test_drift_replan_uses_incremental_sketches(rng):
    """After heavy ingest the re-plan sees fresh FM distinct estimates
    without any host scan: the incrementally-updated sketch equals a
    from-scratch rebuild, so the session's cards/d estimates agree."""
    rel, _ = make_rel(rng, 400, ("a", "b"), 50)
    rel.distinct_sketch("a")
    rel.append(a=rng.integers(50, 400, 1600).astype(np.int32),
               b=rng.integers(0, 50, 1600).astype(np.int32))
    est_inc = rel.distinct_estimate("a")
    rebuilt = int(round(float(sketches.fm_estimate(sketches.add(
        sketches.empty(), rel.col("a"), rel.valid)))))
    assert est_inc == max(1, min(rebuilt, rel.capacity))


# --------------------------------------------------------------------------
# unbounded accumulation stays int64-exact
# --------------------------------------------------------------------------

def test_totals_accumulate_in_python_ints(rng):
    n, d = 300, 40
    R = _mk(rng, n, d, ("a", "b"))
    S = _mk(rng, n, d, ("b", "c"))
    T = _mk(rng, n, d, ("c", "e"))
    q = Query({"R": R, "S": S, "T": T}, [("R.b", "S.b"), ("S.c", "T.c")])
    sq = JoinSession(m_budget=128).watch(q)
    # simulate a long-lived standing query whose accumulated totals have
    # outgrown int32: the int64-typed snapshot must carry them exactly
    sq._tuples += 2**40
    snap = sq.snapshot()
    assert np.asarray(snap.tuples_read).dtype == np.int64
    assert int(snap.tuples_read) > 2**40
    sq.close()


def test_watch_requires_session():
    rng = np.random.default_rng(0)
    R = _mk(rng, 100, 20, ("a", "b"))
    S = _mk(rng, 100, 20, ("b", "c"))
    T = _mk(rng, 100, 20, ("c", "e"))
    q = Query({"R": R, "S": S, "T": T}, [("R.b", "S.b"), ("S.c", "T.c")])
    sq = JoinSession(m_budget=64).watch(q)
    assert isinstance(sq, StandingQuery)
    sq.close()
    # closed handles ignore further ingest
    before = len(sq.delta_rounds)
    R.append(a=np.arange(5, dtype=np.int32), b=np.arange(5, dtype=np.int32))
    assert len(sq.delta_rounds) == before


@pytest.mark.parametrize("small", ["f1", "f3"])
def test_delta_cascade_materializes_the_delta_side(rng, small):
    """A delta in either endpoint of a linear standing query materializes
    Δ ⋈ f2, never the full f1 ⋈ f2 (which at 16M edges per relation is
    256M rows and exhausts a 16 GB device)."""
    from repro.core import planner
    rels = {nm: make_rel(rng, 400, ("src", "dst"), 50)[0]
            for nm in ("f1", "f2", "f3")}
    q = Query(rels, [("f1.dst", "f2.src"), ("f2.dst", "f3.src")])
    cards = {"f1": 400, "f2": 400, "f3": 400, small: 5}
    qp = planner.plan_query(q, cards, m_budget=64, strategy="cascade")
    first = qp.steps[0]
    assert first.op == "binary" and not first.aggregate
    assert set(first.inputs) == {small, "f2"}
    assert int(JoinSession(m_budget=64).execute(q, strategy="cascade").count) \
        == int(JoinSession(m_budget=64).execute(q, strategy="3way").count)
