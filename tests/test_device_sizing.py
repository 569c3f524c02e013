"""Device-resident plan execution: sizing, staged pipeline, arena, per-R.

Covers the scan-free executor tentpole: the device-side sorted-key
histogram (``binary_join.exact_join_count``) matches the host np.unique
oracle — including join cardinalities past 2^31, where the two-limb
reduction must stay exact with x64 off; the staged stage/gather pipeline
reproduces ``join_materialize`` column-for-column; a warm ``execute_plan``
provably performs ZERO host ``np.unique`` calls; the refcounting buffer
arena stays correct when an intermediate feeds multiple consumers; and
N-way per-R group counts (the per_r-through-the-plan-IR satellite) match
the weight-backflow oracle from either end of a 4-chain.
"""

from collections import defaultdict

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import make_rel, skewed_keys
from repro.core import binary_join, plan_ir
from repro.core.query import Predicate, Query
from repro.core.relation import Relation
from repro.core.session import JoinSession


# --------------------------------------------------------------------------
# device sizing vs the np.unique oracle
# --------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_a=st.integers(1, 300),
       n_b=st.integers(1, 300), d=st.integers(1, 40), skew=st.booleans())
def test_exact_join_count_matches_host_oracle(seed, n_a, n_b, d, skew):
    rng = np.random.default_rng(seed)

    def keys(n):
        if skew:
            return skewed_keys(rng, n, d, 0.4)
        return rng.integers(0, d, n).astype(np.int32)

    a = Relation.from_arrays(capacity=n_a + 7, b=keys(n_a))
    b = Relation.from_arrays(capacity=n_b + 3, b=keys(n_b))
    got = binary_join.exact_join_count(a, "b", b, "b")
    assert got == binary_join.host_join_count(a, "b", b, "b")


def test_exact_join_count_empty_and_disjoint(rng):
    a = Relation.from_arrays(b=np.arange(10, dtype=np.int32))
    empty = a.mask_where(np.zeros(10, bool))
    assert binary_join.exact_join_count(a, "b", empty, "b") == 0
    assert binary_join.exact_join_count(empty, "b", a, "b") == 0
    c = Relation.from_arrays(b=np.arange(100, 110, dtype=np.int32))
    assert binary_join.exact_join_count(a, "b", c, "b") == 0


def test_exact_join_count_past_int32(rng):
    """50k x 50k rows on one key: 2.5e9 matches > 2^31 — the two-limb
    reduction must stay exact with x64 disabled framework-wide."""
    n = 50_000
    a = Relation.from_arrays(b=np.full(n, 7, np.int32))
    b = Relation.from_arrays(b=np.full(n, 7, np.int32))
    got = binary_join.exact_join_count(a, "b", b, "b")
    assert got == n * n
    assert got > 2**31
    # mixed load: the heavy key rides with ordinary ones
    extra = np.concatenate([np.full(n, 7, np.int32),
                            np.arange(1000, dtype=np.int32)])
    c = Relation.from_arrays(b=extra)
    assert (binary_join.exact_join_count(a, "b", c, "b")
            == binary_join.host_join_count(a, "b", c, "b"))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_a=st.integers(1, 200),
       n_b=st.integers(1, 200), d=st.integers(1, 25))
def test_staged_pipeline_matches_join_materialize(seed, n_a, n_b, d):
    """stage_join + gather_staged == join_materialize, column for column
    (same build-sorted slot order), at the executor's bucketed capacity."""
    rng = np.random.default_rng(seed)
    a, _ = make_rel(rng, n_a, ("a", "b"), d)
    b, _ = make_rel(rng, n_b, ("b", "c"), d)
    st_ = binary_join.stage_join(a, b, build_key="b", probe_key="b")
    total = binary_join.staged_total(st_)
    assert total == binary_join.host_join_count(a, "b", b, "b")
    cap = binary_join.bucket_capacity(total)
    assert cap >= total
    got = binary_join.gather_staged(st_, b, cap)
    want = binary_join.join_materialize(a, "b", b, "b", cap)
    assert not bool(want.overflowed)
    assert int(want.total) == total
    np.testing.assert_array_equal(np.asarray(got.valid),
                                  np.asarray(want.rel.valid))
    for name in got.columns:
        np.testing.assert_array_equal(np.asarray(got.col(name)),
                                      np.asarray(want.rel.col(name)))


def test_gather_staged_donates_the_staged_buffers(rng):
    """The gather donates its staged buffers on every backend: where XLA
    can reuse them (a build side as long as the output, the pow2 shape of
    a chained intermediate) they are consumed, the probe side never is,
    and the materialized output is intact."""
    def rel(cols):
        return Relation.from_arrays(
            capacity=64, **{c: rng.integers(0, 100, 50) for c in cols})

    a, b = rel(("a", "b")), rel(("b", "c"))
    st_ = binary_join.stage_join(a, b, build_key="b", probe_key="b")
    total = binary_join.staged_total(st_)
    cap = binary_join.bucket_capacity(total)
    assert cap == a.capacity
    got = binary_join.gather_staged(st_, b, cap)
    assert any(x.is_deleted() for x in jax.tree.leaves(st_.sorted_build))
    assert not any(x.is_deleted() for x in jax.tree.leaves(b))
    want = binary_join.join_materialize(a, "b", b, "b", cap)
    for name in got.columns:
        np.testing.assert_array_equal(np.asarray(got.col(name)),
                                      np.asarray(want.rel.col(name)))


def test_bucket_capacity_is_log_bucketed():
    assert binary_join.bucket_capacity(0) == 64
    assert binary_join.bucket_capacity(100) == binary_join.bucket_capacity(120)
    for total in (63, 1000, 5000, 123457):
        cap = binary_join.bucket_capacity(total)
        assert cap >= total + 8 and cap <= 4 * max(total, 32)
        assert cap & (cap - 1) == 0          # power of two


# --------------------------------------------------------------------------
# regression: a warm execute_plan never touches host np.unique
# --------------------------------------------------------------------------

def test_warm_execute_plan_is_scan_free(rng, monkeypatch):
    """Acceptance: after one warm-up execution, neither re-planning (FM
    sketches) nor re-execution (staged device pipeline) may call host
    np.unique — the count is monkeypatch-enforced at zero."""
    rels = [make_rel(rng, 800, (c1, c2), 80)[0]
            for c1, c2 in (("a", "b"), ("b", "c"), ("c", "d"), ("d", "e"))]
    names = [f"r{i}" for i in range(1, 5)]
    q = Query(dict(zip(names, rels)),
              [("r1.b", "r2.b"), ("r2.c", "r3.c"), ("r3.d", "r4.d")])
    sess = JoinSession(m_budget=128)
    cold = sess.execute(q)                     # compile + plan warm-up
    calls = {"n": 0}
    real_unique = np.unique

    def counting_unique(*args, **kw):
        calls["n"] += 1
        return real_unique(*args, **kw)

    monkeypatch.setattr(np, "unique", counting_unique)
    warm = sess.execute(q)
    assert warm.cache_hit
    assert int(warm.count) == int(cold.count)
    assert calls["n"] == 0, (
        f"warm execute_plan made {calls['n']} host np.unique calls")


# --------------------------------------------------------------------------
# buffer arena: multi-consumer intermediates; plan-step spans
# --------------------------------------------------------------------------

def _oracle_pairs(keys_a, keys_b) -> int:
    cnt = defaultdict(int)
    for v in keys_b:
        cnt[int(v)] += 1
    return sum(cnt.get(int(v), 0) for v in keys_a)


def test_arena_keeps_multi_consumer_intermediate(rng):
    """A hand-built DAG where %i0 feeds BOTH %i1 and the root: the
    refcounting arena must keep %i0 alive until its second consumer has
    captured it (and the count must match brute force)."""
    r, rd = make_rel(rng, 60, ("a", "b"), 8)
    s, sd = make_rel(rng, 70, ("b", "c"), 8)
    t, td = make_rel(rng, 50, ("c", "d"), 8)
    steps = (
        plan_ir.PlanStep(
            op="binary", out="%i0", inputs=("r", "s"),
            preds=(Predicate(("r", "r.b"), ("s", "s.b")),),
            aggregate=False,
            project=((("b", "r.b"), ("a", "r.a")),
                     (("b", "s.b"), ("c", "s.c")))),
        plan_ir.PlanStep(
            op="binary", out="%i1", inputs=("%i0", "t"),
            preds=(Predicate(("%i0", "s.c"), ("t", "t.c")),),
            aggregate=False,
            project=((("s.c", "s.c"), ("r.a", "r.a")),
                     (("c", "t.c"), ("d", "t.d")))),
        plan_ir.PlanStep(
            op="binary", out=plan_ir.COUNT, inputs=("%i1", "%i0"),
            preds=(Predicate(("%i1", "r.a"), ("%i0", "r.a")),),
            aggregate=True),
    )
    qp = plan_ir.QueryPlan(steps=steps, n_relations=3, kind="binary",
                           strategy="cascade")
    res = plan_ir.execute_plan(qp, {"r": r, "s": s, "t": t})
    # oracle: i0 = r x s on b; i1 = i0 x t on c; root = |i1 x i0 on r.a|
    i0 = [(int(a), int(c)) for a, b in zip(rd["a"], rd["b"])
          for b2, c in zip(sd["b"], sd["c"]) if int(b) == int(b2)]
    i1_a = [a for a, c in i0 for c2 in td["c"].tolist() if c == int(c2)]
    want = _oracle_pairs(i1_a, [a for a, _ in i0])
    assert int(res.count) == want
    assert not res.overflowed


def test_plan_steps_record_one_span_each(rng, record_spans):
    """Each step of the 4-relation cascade runs under one
    ``repro.plan.step`` span, in plan order, naming its op and output;
    recording the spans leaves the count as it was."""
    rels = [make_rel(rng, 500, (c1, c2), 50)[0]
            for c1, c2 in (("a", "b"), ("b", "c"), ("c", "d"), ("d", "e"))]
    names = [f"r{i}" for i in range(1, 5)]
    q = Query(dict(zip(names, rels)),
              [("r1.b", "r2.b"), ("r2.c", "r3.c"), ("r3.d", "r4.d")])
    sess = JoinSession(m_budget=128)
    qp = sess.execute(q, strategy="cascade").plan
    fast = plan_ir.execute_plan(qp, dict(q.relations))
    traced, spans = record_spans(
        lambda: plan_ir.execute_plan(qp, dict(q.relations)))
    steps = [sp.args for sp in spans if sp.name == "repro.plan.step"]
    assert [(a["i"], a["op"], a["out"]) for a in steps] == [
        (i, st.op, st.out) for i, st in enumerate(qp.steps)]
    assert len(qp.steps) >= 3
    assert int(traced.count) == int(fast.count)
    assert sum(s.tuples_read for s in traced.step_stats) == traced.tuples_read


# --------------------------------------------------------------------------
# per-R through the plan IR (N-way satellite + 3-rel compatibility)
# --------------------------------------------------------------------------

def _chain4(rng, n=300, d=25):
    cols = [("a", "b"), ("b", "c"), ("c", "d"), ("d", "e")]
    rels, raw = [], []
    for c1, c2 in cols:
        rel, rd = make_rel(rng, n, (c1, c2), d)
        rels.append(rel)
        raw.append(rd)
    names = [f"r{i}" for i in range(1, 5)]
    q = Query(dict(zip(names, rels)),
              [("r1.b", "r2.b"), ("r2.c", "r3.c"), ("r3.d", "r4.d")])
    return q, raw


def _backflow_weights(raw, join_cols):
    """Per-row join-output counts of the FIRST relation of a chain.
    ``join_cols``: the shared column of each edge, front to back."""
    w = np.ones(len(next(iter(raw[-1].values()))), np.int64)
    for i in range(len(raw) - 1, 0, -1):
        key = join_cols[i - 1]
        cnt = defaultdict(int)
        for k, wv in zip(raw[i][key].tolist(), w.tolist()):
            cnt[k] += wv
        w = np.array([cnt.get(k, 0) for k in raw[i - 1][key].tolist()],
                     np.int64)
    return w


def _group(keys, weights):
    out = defaultdict(int)
    for k, w in zip(keys, weights):
        if w:
            out[int(k)] += int(w)
    return dict(out)


def test_nway_per_r_matches_backflow_oracle(rng):
    """per_r on a 4-chain, pinned at BOTH leaves: group-by of the per-R
    (keys, counts) equals the weight-backflow oracle, and COUNT equals the
    full join cardinality."""
    q, raw = _chain4(rng)
    sess = JoinSession(m_budget=128)

    res = sess.execute(q, per_r="r1", key_col="a")
    assert not res.overflowed
    assert res.plan.root.per_r_key == "a"
    assert dict(res.plan.root.roles)["r"] == "r1"
    w1 = _backflow_weights(raw, ["b", "c", "d"])
    got = _group(np.asarray(res.per_r.keys)[np.asarray(res.per_r.valid)],
                 np.asarray(res.per_r.counts)[np.asarray(res.per_r.valid)])
    assert got == _group(raw[0]["a"], w1)
    assert int(res.count) == int(w1.sum())

    # pin the other leaf: the planner must keep its edge uncontracted and
    # swap it into role r
    res4 = sess.execute(q, per_r="r4", key_col="e")
    assert dict(res4.plan.root.roles)["r"] == "r4"
    w4 = _backflow_weights(raw[::-1], ["d", "c", "b"])
    got4 = _group(
        np.asarray(res4.per_r.keys)[np.asarray(res4.per_r.valid)],
        np.asarray(res4.per_r.counts)[np.asarray(res4.per_r.valid)])
    assert got4 == _group(raw[3]["e"], w4)
    assert int(res4.count) == int(w1.sum())

    # per_r=True defaults to the first-declared leaf (r1)
    res_def = sess.execute(q, per_r=True, key_col="a")
    assert dict(res_def.plan.root.roles)["r"] == "r1"
    assert _group(
        np.asarray(res_def.per_r.keys)[np.asarray(res_def.per_r.valid)],
        np.asarray(res_def.per_r.counts)[np.asarray(res_def.per_r.valid)]
    ) == got


def test_per_r_pin_validation(rng):
    q, _ = _chain4(rng, n=80, d=10)
    sess = JoinSession(m_budget=64)
    with pytest.raises(ValueError, match="leaf"):
        sess.execute(q, per_r="r2", key_col="b")   # interior relation
    with pytest.raises(ValueError, match="key column"):
        sess.execute(q, per_r="r1", key_col="zz")
    with pytest.raises(ValueError, match="not one of"):
        sess.execute(q, per_r="zzz")
    with pytest.raises(ValueError, match="cascade"):
        sess.execute(q, per_r=True, strategy="cascade")
    r, _ = make_rel(rng, 50, ("a", "b"), 8)
    s, _ = make_rel(rng, 50, ("b", "c"), 8)
    q2 = Query({"r": r, "s": s}, [("r.b", "s.b")])
    with pytest.raises(ValueError, match="3-way root"):
        sess.execute(q2, per_r=True)


def test_per_r_centre_pin_rejected(rng):
    r, _ = make_rel(rng, 60, ("a", "b"), 10)
    s, _ = make_rel(rng, 60, ("b", "c"), 10)
    t, _ = make_rel(rng, 60, ("c", "d"), 10)
    q = Query({"r": r, "s": s, "t": t}, [("r.b", "s.b"), ("s.c", "t.c")])
    with pytest.raises(ValueError, match="centre"):
        JoinSession(m_budget=64).execute(q, per_r="s", key_col="b")


def test_3rel_per_r_t_endpoint_swaps_roles(rng):
    """Pinning the t-side endpoint of a 3-relation path swaps the linear
    roles (the path is symmetric) and still matches the oracle."""
    r, rd = make_rel(rng, 120, ("a", "b"), 20)
    s, sd = make_rel(rng, 130, ("b", "c"), 20)
    t, td = make_rel(rng, 110, ("c", "d"), 20)
    q = Query({"r": r, "s": s, "t": t}, [("r.b", "s.b"), ("s.c", "t.c")])
    res = JoinSession(m_budget=64).execute(q, per_r="t", key_col="d")
    assert dict(res.plan.root.roles)["r"] == "t"
    raw = [td, sd, rd]
    w = _backflow_weights(raw, ["c", "b"])
    got = _group(np.asarray(res.per_r.keys)[np.asarray(res.per_r.valid)],
                 np.asarray(res.per_r.counts)[np.asarray(res.per_r.valid)])
    assert got == _group(td["d"], w)
