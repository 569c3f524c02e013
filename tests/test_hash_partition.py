"""Hashing + partitioning invariants (property-based)."""

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from conftest import make_rel
from repro.core import hashing, partition
from repro.core.relation import Relation


def test_mix32_avalanche():
    """Flipping one input bit flips ~half the output bits on average."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 2**31 - 1, size=2000).astype(np.int32))
    h0 = hashing.mix32(x, 0xABCD)
    flips = []
    for bit in [0, 7, 16, 30]:
        h1 = hashing.mix32(x ^ (1 << bit), 0xABCD)
        diff = np.asarray(h0 ^ h1).view(np.uint32)
        pop = np.unpackbits(diff.view(np.uint8)).sum() / diff.size
        flips.append(pop)
    assert all(12 < f < 20 for f in flips), flips  # ideal = 16


def test_hash_bucket_uniformity():
    rng = np.random.default_rng(1)
    keys = jnp.asarray(rng.integers(0, 2**31 - 1, size=65536).astype(np.int32))
    for nb in (7, 16, 64, 100):
        ids = np.asarray(hashing.hash_bucket(keys, nb, "H"))
        assert ids.min() >= 0 and ids.max() < nb
        counts = np.bincount(ids, minlength=nb)
        mean = 65536 / nb
        assert counts.max() < mean * 1.3 and counts.min() > mean * 0.7


def test_hash_families_independent():
    keys = jnp.arange(10000, dtype=jnp.int32)
    a = np.asarray(hashing.hash_bucket(keys, 16, "H"))
    b = np.asarray(hashing.hash_bucket(keys, 16, "h"))
    # correlation between families should be near zero
    joint = np.zeros((16, 16))
    for x, y in zip(a, b):
        joint[x, y] += 1
    expected = 10000 / 256
    chi2 = ((joint - expected) ** 2 / expected).sum()
    assert chi2 < 400  # dof=225, mean 225, generous bound


def test_salt_changes_assignment():
    keys = jnp.arange(4096, dtype=jnp.int32)
    a = np.asarray(hashing.hash_bucket(keys, 32, "H", salt=0))
    b = np.asarray(hashing.hash_bucket(keys, 32, "H", salt=1))
    assert (a != b).mean() > 0.9


def test_trailing_zeros_distribution():
    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.integers(0, 2**31 - 1, size=1 << 16).astype(np.int32))
    rho = np.asarray(hashing.hash_trailing_zeros(keys, 0))
    assert rho.min() >= 1
    # P(rho = k) = 2^-k
    frac1 = (rho == 1).mean()
    assert 0.47 < frac1 < 0.53


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 300), nb=st.integers(1, 32),
       seed=st.integers(0, 2**31 - 1))
def test_partition_sorted_invariants(n, nb, seed):
    rng = np.random.default_rng(seed)
    rel, data = make_rel(rng, n, ("k",), max(1, n // 2), cap_extra=seed % 7)
    sp = partition.partition_sorted(rel, "k", nb, fn="H")
    offs = np.asarray(sp.offsets)
    ids = np.asarray(sp.bucket_ids)
    keys = np.asarray(sp.rel.col("k"))
    valid = np.asarray(sp.rel.valid)
    # offsets are monotone and cover all valid rows
    assert (np.diff(offs) >= 0).all()
    assert offs[-1] == valid.sum()
    # rows within [offsets[i], offsets[i+1]) hash to bucket i
    for i in range(nb):
        seg = slice(offs[i], offs[i + 1])
        if offs[i + 1] > offs[i]:
            assert (ids[seg] == i).all()
            want = np.asarray(hashing.hash_bucket(
                jnp.asarray(keys[seg]), nb, "H"))
            assert (want == i).all()


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 300), nb=st.integers(1, 16),
       seed=st.integers(0, 2**31 - 1))
def test_bucketize_preserves_multiset(n, nb, seed):
    rng = np.random.default_rng(seed)
    rel, data = make_rel(rng, n, ("k", "v"), max(1, n // 3))
    cap = partition.suggest_capacity(n, nb, slack=4.0)
    b = partition.bucketize(rel, "k", nb, cap, fn="h")
    if bool(b.overflowed):
        return  # dropped rows allowed only when flagged
    got_k = np.asarray(b.columns["k"])[np.asarray(b.valid)]
    assert sorted(got_k.tolist()) == sorted(data["k"].tolist())
    # every row is in the bucket its key hashes to
    ids = np.asarray(hashing.hash_bucket(jnp.asarray(b.columns["k"]), nb, "h"))
    rows = np.broadcast_to(np.arange(nb)[:, None], ids.shape)
    v = np.asarray(b.valid)
    assert (ids[v] == rows[v]).all()
    # counts match histogram
    want_counts = np.bincount(
        np.asarray(hashing.hash_bucket(jnp.asarray(data["k"]), nb, "h")),
        minlength=nb)
    np.testing.assert_array_equal(np.asarray(b.counts), want_counts)


def test_bucketize_overflow_detection(rng):
    rel, _ = make_rel(rng, 100, ("k",), 1)  # all-equal keys -> one bucket
    b = partition.bucketize(rel, "k", 8, capacity=16, fn="h")
    assert bool(b.overflowed)
    assert int(np.asarray(b.counts).max()) == 100


def test_overflowed_bucket_keeps_the_first_rows_in_arrival_order(rng):
    rel = Relation.from_arrays(k=np.zeros(40, np.int32),
                               v=np.arange(40, dtype=np.int32))
    b = partition.bucketize(rel, "k", 4, capacity=16, fn="h")
    assert bool(b.overflowed)
    row = int(np.asarray(hashing.hash_bucket(jnp.zeros(1, jnp.int32), 4,
                                             "h"))[0])
    np.testing.assert_array_equal(np.asarray(b.columns["v"])[row],
                                  np.arange(16))
    assert np.asarray(b.valid).sum() == 16


def test_layouts_of_one_capacity_share_one_sort_program(rng):
    """A TPU sort takes 15-20 s to compile, so the sort behind every layout
    and binary-step build is one program keyed only by the key count:
    layouts that differ in buckets, capacity or columns reuse it."""
    import jax

    from repro.core import binary_join

    jax.clear_caches()
    a, _ = make_rel(rng, 200, ("k", "v"), 30)
    b, _ = make_rel(rng, 200, ("k", "w", "x"), 30)
    assert a.capacity == b.capacity
    partition.bucketize(a, "k", 8, 64, fn="h")
    partition.bucketize(b, "w", 16, 32, fn="g", salt=3)
    ids, nb = partition.composite_ids(a, [("k", 4, "H"), ("v", 4, "h")])
    partition.bucketize_by_ids(a, ids, nb, 32, (4, 4))
    binary_join.stage_join(b, a, build_key="k", probe_key="k")
    assert partition.stable_order._cache_size() == 1


def test_composite_ids_lexicographic(rng):
    rel, data = make_rel(rng, 64, ("x", "y"), 20)
    ids, total = partition.composite_ids(
        rel, [("x", 4, "H"), ("y", 8, "g")])
    assert total == 32
    hx = np.asarray(hashing.hash_bucket(jnp.asarray(data["x"]), 4, "H"))
    gy = np.asarray(hashing.hash_bucket(jnp.asarray(data["y"]), 8, "g"))
    np.testing.assert_array_equal(np.asarray(ids)[:64], hx * 8 + gy)


def test_composite_ids_int32_guard(rng):
    """Deep/wide specs whose flat id space exceeds int32 must fail loudly —
    a silent wrap would scatter rows into wrong buckets."""
    import pytest

    rel, _ = make_rel(rng, 16, ("x", "y"), 10)
    # 70000 * 70000 = 4.9e9 > 2^31 - 1
    with pytest.raises(ValueError, match="int32"):
        partition.composite_ids(rel, [("x", 70000, "H"), ("y", 70000, "g")])
    # a capacity blowing the flat slot space is caught too
    with pytest.raises(ValueError, match="int32"):
        partition.bucketize_by_ids(
            rel, jnp.zeros(16, jnp.int32), 70000, 70000, (70000,))
    # the boundary itself is fine
    ids, total = partition.composite_ids(rel, [("x", 46341, "H"),
                                               ("y", 46340, "g")])
    assert total == 46341 * 46340 <= 2**31 - 1


def test_sentinel_constant_unified():
    """ONE padding sentinel everywhere, side sentinels derived and distinct:
    no sentinel can equal a live key (>= -2^30) or another side's."""
    import inspect

    from repro.core.relation import SENTINEL, sentinel_fill
    from repro.kernels import ops

    assert inspect.signature(partition.bucketize).parameters[
        "sentinel"].default == SENTINEL
    assert inspect.signature(partition.bucketize_by_ids).parameters[
        "sentinel"].default == SENTINEL
    assert inspect.signature(sentinel_fill).parameters[
        "sentinel"].default == SENTINEL
    sents = set(ops._SENT.values()) | {SENTINEL, ops.SENT_BASE}
    assert len(sents) == len(ops._SENT) + 2          # all distinct
    assert all(s < -(2**30) for s in sents)          # below the key floor


def test_sentinel_rows_never_false_match(rng):
    """Invalid rows carrying ADVERSARIAL key values — another side's probe
    sentinel, the padding sentinel itself — must never join with anything:
    counts equal the oracle over valid rows only."""
    from conftest import oracle_linear3_count
    from repro.core import linear3, engine
    from repro.core.relation import SENTINEL
    from repro.kernels import ops as kops_

    n, d = 120, 20
    adversarial = np.asarray(
        [SENTINEL, kops_.SENT_BASE] + list(kops_._SENT.values()),
        np.int32)

    def poisoned(cols):
        """Relation with 24 invalid tail rows holding sentinel-ish keys."""
        rel = Relation.from_arrays(capacity=n + 24, **cols)
        poison = {
            k: jnp.asarray(np.concatenate(
                [np.asarray(v, np.int32),
                 np.resize(adversarial, 24)]))
            for k, v in cols.items()}
        return Relation(poison, rel.valid)

    rd = {c: rng.integers(0, d, n).astype(np.int32) for c in ("a", "b")}
    sd = {c: rng.integers(0, d, n).astype(np.int32) for c in ("b", "c")}
    td = {c: rng.integers(0, d, n).astype(np.int32) for c in ("c", "d")}
    r, s, t = poisoned(rd), poisoned(sd), poisoned(td)
    want = oracle_linear3_count(rd["b"], sd["b"], sd["c"], td["c"])

    plan = linear3.default_plan(n, n, n, m_budget=48, u=4, slack=4.0)
    res = engine.linear3_count_fused(r, s, t, plan)
    assert int(res.count) == want
    # the bucketized layouts pad dead slots with the canonical sentinel
    rg, sg, tg = engine.linear3_layouts(r, s, t, plan)
    dead = np.asarray(rg.columns["b"])[~np.asarray(rg.valid)]
    assert (dead == SENTINEL).all()
