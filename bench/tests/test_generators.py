"""Generators and the plain reference."""

import itertools

import numpy as np
import pytest

import run as bench
from gen import kron, ssb
from refs import acyclic


def _kron_cfg(scale=10):
    cfg = dict(bench.load_cell("kron.fofof").config)
    cfg["scale"] = scale
    return cfg


def test_kron_edges_symmetric_without_loops_or_duplicates():
    cfg = _kron_cfg()
    e = kron.make(cfg, np.random.default_rng(3))["edges"]
    src, dst = e["src"].astype(np.int64), e["dst"].astype(np.int64)
    n = 1 << cfg["scale"]
    assert src.max() < n and src.min() >= 0
    assert not np.any(src == dst)
    code = src * n + dst
    assert len(np.unique(code)) == len(code)
    assert set(code.tolist()) == set((dst * n + src).tolist())
    # at most both directions of every generated edge survive
    assert len(code) <= 2 * cfg["edge_factor"] * n
    assert len(code) > cfg["edge_factor"] * n


def test_kron_degree_skew_far_above_uniform():
    cfg = _kron_cfg(12)
    rng = np.random.default_rng(4)
    e = kron.make(cfg, rng)["edges"]
    n = 1 << cfg["scale"]
    deg = np.bincount(e["src"], minlength=n)
    ij = rng.integers(0, n, (2, cfg["edge_factor"] * n))
    u_src, _ = kron.symmetrize(ij, n)
    udeg = np.bincount(u_src, minlength=n)
    assert deg.max() / deg.mean() > 10 * (udeg.max() / udeg.mean())


def test_kron_seed_permutes_one_graph():
    cfg = _kron_cfg(8)
    a = kron.make(cfg, bench.rng_for(2**31 + 5, 0))["edges"]
    b = kron.make(cfg, bench.rng_for(2**31 + 5, 0))["edges"]
    c = kron.make(cfg, bench.rng_for(2**31 + 6, 0))["edges"]
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["src"], c["src"])
    n = 1 << cfg["scale"]

    def edge_set(e):
        return np.sort(e["src"].astype(np.int64) * n + e["dst"])
    assert np.array_equal(edge_set(a), edge_set(c))


def test_ssb_rows_and_key_ranges():
    cfg = {"sf": 0.01}
    t = ssb.make(cfg, np.random.default_rng(5))
    sizes = ssb.table_rows(0.01)
    assert sizes == {"lineorder": 60_000, "customer": 300, "supplier": 20,
                     "part": 2_000, "date": 2_556}
    for name, n in sizes.items():
        assert all(len(v) == n for v in t[name].values())
    # the spec's columns, every one
    assert {k: len(v) for k, v in t.items()} == {
        "lineorder": 17, "customer": 8, "supplier": 7, "part": 9, "date": 17}
    lo = t["lineorder"]
    assert lo["lo_custkey"].min() >= 1 and lo["lo_custkey"].max() <= 300
    assert lo["lo_suppkey"].min() >= 1 and lo["lo_suppkey"].max() <= 20
    assert lo["lo_partkey"].min() >= 1 and lo["lo_partkey"].max() <= 2_000
    d = t["date"]
    assert d["d_datekey"][0] == 19920101 and d["d_dayofweek"][0] == 3
    assert d["d_datekey"][59] == 19920229
    assert d["d_datekey"][-1] == 19981230
    assert lo["lo_orderdate"].min() >= 19920101
    assert lo["lo_orderdate"].max() <= 19980802
    assert np.isin(lo["lo_orderdate"], d["d_datekey"]).all()
    assert np.isin(lo["lo_commitdate"], d["d_datekey"]).all()
    for k in ("c_custkey", "s_suppkey", "p_partkey", "d_datekey"):
        tab = next(v for v in t.values() if k in v)
        assert np.array_equal(np.unique(tab[k]), np.sort(tab[k]))
    # orders of 1 to 7 lines share a customer and an order date
    key, line = lo["lo_orderkey"], lo["lo_linenumber"]
    assert np.all(np.diff(key) >= 0) and line.min() == 1 and line.max() == 7
    assert np.array_equal(line == 1, np.r_[True, np.diff(key) > 0])
    same = np.diff(key) == 0
    assert np.all(np.diff(lo["lo_custkey"])[same] == 0)
    assert np.all(np.diff(lo["lo_orderdate"])[same] == 0)
    assert np.all(lo["lo_revenue"] <= lo["lo_extendedprice"])


def _brute_count(tables, query):
    aliases = list(query["relations"])
    rows = [list(zip(*tables[query["relations"][a]].values()))
            for a in aliases]
    names = {a: list(tables[query["relations"][a]]) for a in aliases}
    total = 0
    for combo in itertools.product(*rows):
        env = {a: dict(zip(names[a], r)) for a, r in zip(aliases, combo)}
        if all(env[la][lc] == env[ra][rc]
               for (la, lc), (ra, rc) in
               ((left.split("."), right.split("."))
                for left, right in query["predicates"])):
            total += 1
    return total


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_matches_brute_force_on_the_cells_queries(seed):
    rng = np.random.default_rng(seed)
    graph = {"edges": {"src": rng.integers(0, 6, 25),
                       "dst": rng.integers(0, 6, 25)}}
    fofof = bench.load_cell("kron.fofof").config["queries"]["fofof"]
    assert acyclic.count(graph, fofof) == _brute_count(graph, fofof)
    star = bench.load_cell("ssb.star5").config["queries"]["star5"]
    k = {"c": 4, "s": 3, "p": 5, "d": 3}
    tabs = {
        "lineorder": {"lo_custkey": rng.integers(0, k["c"], 12),
                      "lo_suppkey": rng.integers(0, k["s"], 12),
                      "lo_partkey": rng.integers(0, k["p"], 12),
                      "lo_orderdate": rng.integers(0, k["d"], 12)},
        # dimension keys with repeats and gaps, so the check is not a
        # count of facts
        "customer": {"c_custkey": rng.integers(0, k["c"], 4)},
        "supplier": {"s_suppkey": rng.integers(0, k["s"], 3)},
        "part": {"p_partkey": rng.integers(0, k["p"], 4)},
        "date": {"d_datekey": rng.integers(0, k["d"], 3)},
    }
    assert acyclic.count(tabs, star) == _brute_count(tabs, star)


def test_controls_differ_from_the_reference():
    cfg = _kron_cfg(11)
    g = kron.make(cfg, np.random.default_rng(9))
    q = cfg["queries"]["fofof"]
    want = acyclic.count(g, q)
    assert want > 1 << 24
    assert acyclic.control(g, q, "float32") != want
    assert acyclic.control(g, q, "pow2_rows") != want
