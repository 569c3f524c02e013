"""MultiwayJoinEngine: fused sweeps vs scan drivers vs kernels/ref.py,
plus the skew-recovery guarantee (exact counts, no residual overflow)."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from conftest import (make_rel, oracle_cyclic3_count, oracle_linear3_count,
                      oracle_linear3_per_r, skewed_keys)
from repro.core import cyclic3, engine, linear3, planner, reference, star3
from repro.core.relation import Relation
from repro.kernels import ops as kops


def _ref_linear_count(rb, sb, sc, tc) -> int:
    """Single-bucket kernels/ref.py oracle (everything in one PMU)."""
    c = kops.bucket_count3_linear(
        jnp.asarray(rb)[None, :], jnp.ones((1, len(rb)), bool),
        jnp.asarray(sb)[None, :], jnp.asarray(sc)[None, :],
        jnp.ones((1, len(sb)), bool),
        jnp.asarray(tc)[None, :], jnp.ones((1, len(tc)), bool))
    return int(c[0])


def _ref_cyclic_count(ra, rb, sb, sc, tc, ta) -> int:
    c = kops.bucket_count3_cyclic(
        jnp.asarray(ra)[None, :], jnp.asarray(rb)[None, :],
        jnp.ones((1, len(ra)), bool),
        jnp.asarray(sb)[None, :], jnp.asarray(sc)[None, :],
        jnp.ones((1, len(sb)), bool),
        jnp.asarray(tc)[None, :], jnp.asarray(ta)[None, :],
        jnp.ones((1, len(tc)), bool))
    return int(c[0])


def _skewed(rng, n, d, heavy_frac, heavy_key=1):
    return skewed_keys(rng, n, d, heavy_frac, heavy_key)


# --------------------------------------------------------------------------
# fused sweep == scan driver (same plan, same layouts)
# --------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), d=st.integers(3, 80),
       u=st.sampled_from([2, 4, 8]))
def test_linear_fused_matches_scan(seed, d, u):
    rng = np.random.default_rng(seed)
    r, rd = make_rel(rng, 150, ("a", "b"), d)
    s, sd = make_rel(rng, 180, ("b", "c"), d)
    t, td = make_rel(rng, 160, ("c", "d"), d)
    plan = linear3.default_plan(150, 180, 160, m_budget=64, u=u)
    res_scan, grown = reference.linear3_count_auto(r, s, t, plan)
    res_fused = engine.linear3_count_fused(r, s, t, grown)
    assert int(res_fused.count) == int(res_scan.count)
    assert not bool(res_fused.overflowed)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), d=st.integers(3, 60))
def test_cyclic_fused_matches_scan(seed, d):
    rng = np.random.default_rng(seed)
    r, _ = make_rel(rng, 140, ("a", "b"), d)
    s, _ = make_rel(rng, 150, ("b", "c"), d)
    t, _ = make_rel(rng, 130, ("c", "a"), d)
    plan = cyclic3.default_plan(140, 150, 130, m_budget=64, uh=4, ug=2)
    res_scan, grown = reference.cyclic3_count_auto(r, s, t, plan)
    res_fused = engine.cyclic3_count_fused(r, s, t, grown)
    assert int(res_fused.count) == int(res_scan.count)
    assert not bool(res_fused.overflowed)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), d=st.integers(3, 60),
       chunks=st.sampled_from([1, 2, 4]))
def test_star_fused_matches_scan(seed, d, chunks):
    rng = np.random.default_rng(seed)
    r, _ = make_rel(rng, 60, ("a", "b"), d)
    s, _ = make_rel(rng, 400, ("b", "c"), d)
    t, _ = make_rel(rng, 70, ("c", "d"), d)
    plan = star3.default_plan(60, 400, 70, uh=4, ug=4, chunks=chunks)
    res_scan, grown = reference.star3_count_auto(r, s, t, plan)
    res_fused = engine.star3_count_fused(r, s, t, grown)
    assert int(res_fused.count) == int(res_scan.count)
    assert not bool(res_fused.overflowed)


def test_fused_pallas_kernels_match_jnp(rng):
    """The fused Pallas grid kernels (interpret mode) and the fused jnp
    paths are the same function."""
    r, _ = make_rel(rng, 120, ("a", "b"), 30)
    s, _ = make_rel(rng, 140, ("b", "c"), 30)
    t, _ = make_rel(rng, 130, ("c", "d"), 30)
    plan = linear3.default_plan(120, 140, 130, m_budget=48, u=4, slack=4.0)
    rg, sg, tg = engine.linear3_layouts(r, s, t, plan)
    a = kops.fused_count3_linear(rg.columns["b"], rg.valid, sg.columns["b"],
                                 sg.columns["c"], sg.valid, tg.columns["c"],
                                 tg.valid, use_kernel=False)
    b = kops.fused_count3_linear(rg.columns["b"], rg.valid, sg.columns["b"],
                                 sg.columns["c"], sg.valid, tg.columns["c"],
                                 tg.valid, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pa = kops.fused_per_r_counts(rg.columns["b"], rg.valid, sg.columns["b"],
                                 sg.columns["c"], sg.valid, tg.columns["c"],
                                 tg.valid, use_kernel=False)
    pb = kops.fused_per_r_counts(rg.columns["b"], rg.valid, sg.columns["b"],
                                 sg.columns["c"], sg.valid, tg.columns["c"],
                                 tg.valid, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


# --------------------------------------------------------------------------
# skew recovery: adversarial keys, exact counts, overflowed == False
# --------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       heavy_frac=st.sampled_from([0.3, 0.5, 0.7]),
       d=st.integers(8, 60))
def test_linear_skew_recovery_exact(seed, heavy_frac, d):
    """A heavy-hitter join key overflows any uniform plan (one bucket must
    hold every copy); the engine must still return the kernels/ref.py
    reference count exactly, with no residual overflow flag."""
    rng = np.random.default_rng(seed)
    rb = _skewed(rng, 200, d, heavy_frac)
    sb = _skewed(rng, 220, d, heavy_frac)
    sc = _skewed(rng, 220, d, heavy_frac, heavy_key=2)
    tc = _skewed(rng, 210, d, heavy_frac, heavy_key=2)
    r = Relation.from_arrays(a=rng.integers(0, 999, 200).astype(np.int32),
                             b=rb)
    s = Relation.from_arrays(b=sb, c=sc)
    t = Relation.from_arrays(c=tc,
                             d=rng.integers(0, 999, 210).astype(np.int32))
    want = _ref_linear_count(rb, sb, sc, tc)
    plan = linear3.default_plan(200, 220, 210, m_budget=64, u=4, slack=1.2)
    res = engine.MultiwayJoinEngine("linear").count(r, s, t, plan)
    assert int(res.count) == want
    assert not bool(res.overflowed)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       heavy_frac=st.sampled_from([0.3, 0.6]))
def test_cyclic_skew_recovery_exact(seed, heavy_frac):
    rng = np.random.default_rng(seed)
    ra, rb = _skewed(rng, 160, 30, heavy_frac), _skewed(rng, 160, 30,
                                                        heavy_frac, 3)
    sb, sc = _skewed(rng, 170, 30, heavy_frac, 3), _skewed(rng, 170, 30,
                                                           heavy_frac, 5)
    tc, ta = _skewed(rng, 150, 30, heavy_frac, 5), _skewed(rng, 150, 30,
                                                           heavy_frac)
    r = Relation.from_arrays(a=ra, b=rb)
    s = Relation.from_arrays(b=sb, c=sc)
    t = Relation.from_arrays(c=tc, a=ta)
    want = _ref_cyclic_count(ra, rb, sb, sc, tc, ta)
    plan = cyclic3.default_plan(160, 170, 150, m_budget=48, uh=2, ug=2,
                                slack=1.2)
    res = engine.MultiwayJoinEngine("cyclic").count(r, s, t, plan)
    assert int(res.count) == want
    assert not bool(res.overflowed)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       heavy_frac=st.sampled_from([0.4, 0.7]))
def test_star_skew_recovery_exact(seed, heavy_frac):
    """Skewed FACT keys: most of S routes to one PMU cell."""
    rng = np.random.default_rng(seed)
    r, rd = make_rel(rng, 60, ("a", "b"), 25)
    sb = _skewed(rng, 400, 25, heavy_frac, heavy_key=7)
    sc = _skewed(rng, 400, 25, heavy_frac, heavy_key=9)
    s = Relation.from_arrays(b=sb, c=sc)
    t, td = make_rel(rng, 70, ("c", "d"), 25)
    want = _ref_linear_count(rd["b"], sb, sc, td["c"])
    plan = star3.default_plan(60, 400, 70, uh=4, ug=4, chunks=2, slack=1.2)
    res = engine.MultiwayJoinEngine("star").count(r, s, t, plan)
    assert int(res.count) == want
    assert not bool(res.overflowed)


def test_linear_zipf_recovery_exact(rng):
    """The seed suite's zipf scenario, now recovered by the engine without
    whole-query capacity retries."""
    r, rd = make_rel(rng, 200, ("a", "b"), 50, zipf=1.4)
    s, sd = make_rel(rng, 220, ("b", "c"), 50, zipf=1.4)
    t, td = make_rel(rng, 210, ("c", "d"), 50, zipf=1.4)
    want = oracle_linear3_count(rd["b"], sd["b"], sd["c"], td["c"])
    plan = linear3.default_plan(200, 220, 210, m_budget=64, u=4, slack=1.2)
    res = engine.MultiwayJoinEngine("linear").count(r, s, t, plan)
    assert int(res.count) == want
    assert not bool(res.overflowed)


def test_per_r_skew_recovery_exact(rng):
    """Per-R aggregates survive recovery: group-by over the concatenated
    round outputs equals the oracle."""
    rb = _skewed(rng, 180, 40, 0.5)
    r = Relation.from_arrays(a=rng.integers(0, 99, 180).astype(np.int32),
                             b=rb)
    rd_a = np.asarray(r.col("a"))
    s, sd = make_rel(rng, 200, ("b", "c"), 40, zipf=1.3)
    t, td = make_rel(rng, 190, ("c", "d"), 40, zipf=1.3)
    plan = linear3.default_plan(180, 200, 190, m_budget=64, u=4, slack=1.2)
    res = engine.MultiwayJoinEngine("linear").per_r_counts(r, s, t, plan)
    assert not bool(res.overflowed)
    from collections import defaultdict
    got = defaultdict(int)
    for k, c, v in zip(np.asarray(res.keys), np.asarray(res.counts),
                       np.asarray(res.valid)):
        if v:
            got[int(k)] += int(c)
    per = oracle_linear3_per_r(rb, sd["b"], sd["c"], td["c"])
    want = defaultdict(int)
    for a, c in zip(rd_a, per):
        want[int(a)] += int(c)
    assert dict(got) == dict(want)


# --------------------------------------------------------------------------
# planner: executable engine plans
# --------------------------------------------------------------------------

def test_planner_engine_plan_runs(rng):
    r, rd = make_rel(rng, 150, ("a", "b"), 37)
    s, sd = make_rel(rng, 180, ("b", "c"), 37)
    t, td = make_rel(rng, 160, ("c", "d"), 37)
    want = oracle_linear3_count(rd["b"], sd["b"], sd["c"], td["c"])
    ep = planner.plan_step("linear", 150, 180, 160, 37, m_budget=48, u=4)
    assert ep.strategy in ("3way", "cascade")
    res = ep.run(r, s, t)
    assert int(res.count) == want


def test_planner_cyclic_always_3way(rng):
    r, rd = make_rel(rng, 140, ("a", "b"), 31)
    s, sd = make_rel(rng, 150, ("b", "c"), 31)
    t, td = make_rel(rng, 130, ("c", "a"), 31)
    want = oracle_cyclic3_count(rd["a"], rd["b"], sd["b"], sd["c"],
                                td["c"], td["a"])
    ep = planner.plan_step("cyclic", 140, 150, 130, 31, m_budget=64,
                           uh=4, ug=2)
    assert ep.strategy == "3way"
    res = ep.run(r, s, t)
    assert int(res.count) == want
    assert res.rounds >= 1


# --------------------------------------------------------------------------
# recovery-round contract: ONE hashing pass per relation per round
# --------------------------------------------------------------------------

def _probe_hashing(monkeypatch):
    """Count composite_ids invocations and raw hash_bucket evaluations.
    The hashing runs inside jitted programs, so the jit caches are cleared
    first: every pass is then traced, and counted, once."""
    from repro.core import hashing, partition
    jax.clear_caches()
    calls = {"composite": 0, "hash": 0}
    orig_ci = partition.composite_ids
    orig_hb = hashing.hash_bucket

    def ci(*a, **kw):
        calls["composite"] += 1
        return orig_ci(*a, **kw)

    def hb(*a, **kw):
        calls["hash"] += 1
        return orig_hb(*a, **kw)

    monkeypatch.setattr(partition, "composite_ids", ci)
    monkeypatch.setattr(hashing, "hash_bucket", hb)
    return calls


def test_one_hash_pass_per_relation_per_round(rng, monkeypatch):
    """Histograms, layouts and residual masks must all derive from a single
    composite_ids pass per relation per round (the recovery-round contract);
    hash_bucket runs once per spec level, never more."""
    levels = {"linear": 2 + 3 + 1, "cyclic": 4 + 3 + 3, "star": 1 + 2 + 1}
    for kind in ("linear", "cyclic", "star"):
        t_cols = ("c", "a") if kind == "cyclic" else ("c", "d")
        rb = _skewed(rng, 200, 30, 0.5)
        r = Relation.from_arrays(a=_skewed(rng, 200, 30, 0.5), b=rb)
        s = Relation.from_arrays(b=_skewed(rng, 220, 30, 0.5, 3),
                                 c=_skewed(rng, 220, 30, 0.5, 5))
        t = Relation.from_arrays(**{t_cols[0]: _skewed(rng, 210, 30, 0.5, 5),
                                    t_cols[1]: _skewed(rng, 210, 30, 0.5)})
        if kind == "linear":
            plan = linear3.default_plan(200, 220, 210, m_budget=64, u=4,
                                        slack=1.2)
        elif kind == "cyclic":
            plan = cyclic3.default_plan(200, 220, 210, m_budget=48, uh=2,
                                        ug=2, slack=1.2)
        else:
            plan = star3.default_plan(200, 220, 210, uh=4, ug=4, chunks=2,
                                      slack=1.2)
        calls = _probe_hashing(monkeypatch)
        res = engine.MultiwayJoinEngine(kind).count(r, s, t, plan)
        assert res.rounds > 1, f"{kind}: skew did not trigger recovery"
        assert calls["composite"] == 3 * res.rounds, (
            f"{kind}: {calls['composite']} composite passes over "
            f"{res.rounds} rounds — want exactly one per relation per round")
        assert calls["hash"] == levels[kind] * res.rounds, (
            f"{kind}: {calls['hash']} hash_bucket calls, want "
            f"{levels[kind]} per round x {res.rounds} rounds")
        monkeypatch.undo()


# --------------------------------------------------------------------------
# int64 totals: > 2^31 cardinality must not wrap
# --------------------------------------------------------------------------

def test_int64_total_over_2e31(rng):
    """Regression: EngineResult.count used to accumulate via jnp int32 and
    silently wrapped past 2^31.  A uniform d=64 self-join at n=22000 has
    ~2.6e9 results (each per-cell partial stays < 2^31 — the kernels' int32
    cell contract — but the total does not fit int32)."""
    n, d = 22000, 64
    rd = {c: rng.integers(0, d, n).astype(np.int32) for c in ("a", "b")}
    sd = {c: rng.integers(0, d, n).astype(np.int32) for c in ("b", "c")}
    td = {c: rng.integers(0, d, n).astype(np.int32) for c in ("c", "d")}
    r = Relation.from_arrays(**rd)
    s = Relation.from_arrays(**sd)
    t = Relation.from_arrays(**td)
    want = oracle_linear3_count(rd["b"], sd["b"], sd["c"], td["c"])
    assert want > 2**31, "shape no longer exercises the int64 regression"
    plan = linear3.default_plan(n, n, n, m_budget=4096, u=8)
    res = engine.MultiwayJoinEngine("linear").count(r, s, t, plan)
    assert int(res.count) == want
    assert np.asarray(res.count).dtype == np.int64
    assert not bool(res.overflowed)


def test_per_r_counts_are_int64(rng):
    r, rd = make_rel(rng, 120, ("a", "b"), 25)
    s, sd = make_rel(rng, 140, ("b", "c"), 25)
    t, td = make_rel(rng, 130, ("c", "d"), 25)
    plan = linear3.default_plan(120, 140, 130, m_budget=48, u=4)
    res = engine.MultiwayJoinEngine("linear").per_r_counts(r, s, t, plan)
    assert np.asarray(res.counts).dtype == np.int64


# --------------------------------------------------------------------------
# cyclic pair-index backend == all-pairs == Pallas kernels
# --------------------------------------------------------------------------

def test_cyclic_pairidx_matches_allpairs_and_kernels(rng):
    """The sorted (c, a)-pair-index backend is the same function as the
    all-pairs contraction, on both the jnp and the (interpret-mode) Pallas
    fused paths."""
    r, _ = make_rel(rng, 300, ("a", "b"), 40)
    s, _ = make_rel(rng, 320, ("b", "c"), 40)
    t, _ = make_rel(rng, 280, ("c", "a"), 40)
    plan = cyclic3.default_plan(300, 320, 280, m_budget=96, uh=4, ug=2,
                                slack=4.0)
    rg, sg, tg = engine.cyclic3_layouts(r, s, t, plan)
    args = (rg.columns["a"], rg.columns["b"], rg.valid, sg.columns["b"],
            sg.columns["c"], sg.valid, tg.columns["c"], tg.columns["a"],
            tg.valid)
    base = np.asarray(kops.fused_count3_cyclic(*args, pair_index=False))
    for kw in (dict(pair_index=True),
               dict(pair_index=True, use_kernel=True),
               dict(pair_index=False, use_kernel=True)):
        got = np.asarray(kops.fused_count3_cyclic(*args, **kw))
        np.testing.assert_array_equal(got, base, err_msg=str(kw))


def test_cyclic_fused_pairidx_matches_scan_driver(rng):
    r, _ = make_rel(rng, 400, ("a", "b"), 50)
    s, _ = make_rel(rng, 420, ("b", "c"), 50)
    t, _ = make_rel(rng, 380, ("c", "a"), 50)
    plan = cyclic3.default_plan(400, 420, 380, m_budget=96, uh=4, ug=2,
                                slack=4.0)
    res_scan, grown_plan = reference.cyclic3_count_auto(r, s, t, plan)
    res_pair = engine.cyclic3_count_fused(r, s, t, grown_plan,
                                          pair_index=True)
    assert int(res_pair.count) == int(res_scan.count)
