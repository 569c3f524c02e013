"""Pallas kernels vs pure-jnp oracles — interpret=True sweeps over
shapes/dtypes.  Counts are integers, so checks are exact equality."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _mk(rng, b, c, d, side):
    keys = rng.integers(0, d, size=(b, c)).astype(np.int32)
    valid = rng.random((b, c)) < 0.85
    return jnp.asarray(keys), jnp.asarray(valid)


SHAPES = [(1, 128, 128, 128), (4, 128, 256, 128), (3, 256, 128, 384),
          (2, 384, 384, 256)]


@pytest.mark.parametrize("b,cr,cs,ct", SHAPES)
@pytest.mark.parametrize("d", [7, 1000])
def test_count3_linear_kernel(b, cr, cs, ct, d):
    rng = np.random.default_rng(b * 1000 + cr + d)
    rb, rv = _mk(rng, b, cr, d, "r")
    sb, sv = _mk(rng, b, cs, d, "s")
    sc = jnp.asarray(rng.integers(0, d, size=(b, cs)).astype(np.int32))
    tc, tv = _mk(rng, b, ct, d, "t")
    want = ops.bucket_count3_linear(rb, rv, sb, sc, sv, tc, tv,
                                    use_kernel=False)
    got = ops.bucket_count3_linear(rb, rv, sb, sc, sv, tc, tv,
                                   use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,cr,cs,ct", SHAPES[:2])
@pytest.mark.parametrize("d", [13, 400])
def test_per_r_counts_kernel(b, cr, cs, ct, d):
    rng = np.random.default_rng(cr + cs + d)
    rb, rv = _mk(rng, b, cr, d, "r")
    sb, sv = _mk(rng, b, cs, d, "s")
    sc = jnp.asarray(rng.integers(0, d, size=(b, cs)).astype(np.int32))
    tc, tv = _mk(rng, b, ct, d, "t")
    want = ops.bucket_per_r_counts(rb, rv, sb, sc, sv, tc, tv,
                                   use_kernel=False)
    got = ops.bucket_per_r_counts(rb, rv, sb, sc, sv, tc, tv,
                                  use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,cr,cs,ct", SHAPES[:2])
@pytest.mark.parametrize("d", [11, 333])
def test_count3_cyclic_kernel(b, cr, cs, ct, d):
    rng = np.random.default_rng(2 * cr + cs + d)
    ra, rv = _mk(rng, b, cr, d, "r")
    rb = jnp.asarray(rng.integers(0, d, size=(b, cr)).astype(np.int32))
    sb, sv = _mk(rng, b, cs, d, "s")
    sc = jnp.asarray(rng.integers(0, d, size=(b, cs)).astype(np.int32))
    tc, tv = _mk(rng, b, ct, d, "t")
    ta = jnp.asarray(rng.integers(0, d, size=(b, ct)).astype(np.int32))
    want = ops.bucket_count3_cyclic(ra, rb, rv, sb, sc, sv, tc, ta, tv,
                                    use_kernel=False)
    got = ops.bucket_count3_cyclic(ra, rb, rv, sb, sc, sv, tc, ta, tv,
                                   use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,ca,cb", [(1, 128, 128), (5, 256, 128), (2, 384, 512)])
@pytest.mark.parametrize("d", [5, 999])
def test_pair_count_kernel(b, ca, cb, d):
    rng = np.random.default_rng(ca + cb + d)
    ka, va = _mk(rng, b, ca, d, "a")
    kb, vb = _mk(rng, b, cb, d, "b")
    want = ops.bucket_pair_count(ka, va, kb, vb, use_kernel=False)
    got = ops.bucket_pair_count(ka, va, kb, vb, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,nb", [(1024, 16), (2048, 64), (4096, 128),
                                  (1000, 32)])
def test_radix_histogram_kernel(n, nb):
    rng = np.random.default_rng(n + nb)
    keys = jnp.asarray(rng.integers(0, 10000, size=n).astype(np.int32))
    valid = jnp.asarray(rng.random(n) < 0.9)
    want = ops.radix_histogram(keys, valid, n_buckets=nb, use_kernel=False)
    got = ops.radix_histogram(keys, valid, n_buckets=nb, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(want).sum()) == int(np.asarray(valid).sum())


def test_unaligned_capacity_padding():
    """ops.* pads non-128-multiple capacities with side sentinels; results
    must match the unpadded reference."""
    rng = np.random.default_rng(7)
    b, cr, cs, ct, d = 2, 100, 130, 70, 50
    rb, rv = _mk(rng, b, cr, d, "r")
    sb, sv = _mk(rng, b, cs, d, "s")
    sc = jnp.asarray(rng.integers(0, d, size=(b, cs)).astype(np.int32))
    tc, tv = _mk(rng, b, ct, d, "t")
    want = ops.bucket_count3_linear(rb, rv, sb, sc, sv, tc, tv,
                                    use_kernel=False)
    got = ops.bucket_count3_linear(rb, rv, sb, sc, sv, tc, tv,
                                   use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_end_to_end_linear3(rng):
    """Full Algorithm 1 with the Pallas kernel as the inner join."""
    from conftest import make_rel, oracle_linear3_count
    from repro.core import linear3, reference
    r, rd = make_rel(rng, 90, ("a", "b"), 25)
    s, sd = make_rel(rng, 100, ("b", "c"), 25)
    t, td = make_rel(rng, 95, ("c", "d"), 25)
    expect = oracle_linear3_count(rd["b"], sd["b"], sd["c"], td["c"])
    plan = linear3.default_plan(90, 100, 95, m_budget=48, u=2)
    res, _ = reference.linear3_count_auto(r, s, t, plan, use_kernel=True)
    assert int(res.count) == expect


def test_fm_registers_ref_matches_direct_sketch(rng):
    """kernels.ref.fm_registers (implicit-join sketch) must equal the sketch
    of the explicitly materialized joined (a, d) pairs."""
    from repro.core import sketches
    b, cr, cs, ct, d, K = 2, 24, 30, 26, 12, 16
    ra = jnp.asarray(rng.integers(0, d, (b, cr)).astype(np.int32))
    rb = jnp.asarray(rng.integers(0, d, (b, cr)).astype(np.int32))
    sb = jnp.asarray(rng.integers(0, d, (b, cs)).astype(np.int32))
    sc = jnp.asarray(rng.integers(0, d, (b, cs)).astype(np.int32))
    tc = jnp.asarray(rng.integers(0, d, (b, ct)).astype(np.int32))
    td = jnp.asarray(rng.integers(0, d, (b, ct)).astype(np.int32))
    got = ref.fm_registers(ra, rb, sb, sc, tc, td, K)
    # oracle: materialize joined (a,d) pairs per bucket, sketch them
    from repro.core import hashing
    for bi in range(b):
        pairs = set()
        for i in range(cr):
            for j in range(cs):
                if int(rb[bi, i]) == int(sb[bi, j]):
                    for k in range(ct):
                        if int(sc[bi, j]) == int(tc[bi, k]):
                            pairs.add((int(ra[bi, i]), int(td[bi, k])))
        if not pairs:
            np.testing.assert_array_equal(np.asarray(got[bi]), 0)
            continue
        pa = jnp.asarray([p[0] for p in pairs], dtype=jnp.int32)
        pd = jnp.asarray([p[1] for p in pairs], dtype=jnp.int32)
        key = (hashing.mix32(pa, 0x1B873593)
               ^ hashing.mix32(pd, 0xE6546B64)).astype(jnp.int32)
        want = sketches.add(sketches.empty(K), key,
                            jnp.ones(key.shape, bool))
        np.testing.assert_array_equal(np.asarray(got[bi]), np.asarray(want))


# --------------------------------------------------------------------------
# fused kernels: 8-row tiles, lane-dense count blocks, chunked streaming
# --------------------------------------------------------------------------

def _grid(rng, shape, d):
    keys = rng.integers(0, d, size=shape).astype(np.int32)
    return jnp.asarray(keys), jnp.asarray(rng.random(shape) < 0.85)


# PMU axes off the 8-row tile (padded with sentinel rows) and capacities
# past one 512-lane chunk (multi-step in-kernel loops, several S chunks)
FUSED_CASES = {
    "linear": dict(hp=2, u=5, gp=3, cr=600, cs=700, ct=1100),
    "per_r": dict(hp=1, u=11, gp=2, cr=530, cs=300, ct=260),
    "cyclic": dict(hp=2, gp=1, uh=3, ug=10, fp=2, cr=300, cs=520, ct=130),
    "star": dict(ch=2, uh=3, ug=9, cr=200, cs=600, ct=300),
}


@pytest.mark.parametrize("kind", sorted(FUSED_CASES))
def test_fused_kernel_tiling_matches_jnp(kind):
    """The Mosaic-tiled fused kernels (interpret mode) and the fused jnp
    paths are the same function at shapes that exercise row padding,
    chunk loops and the streamed S-chunk grid axis."""
    g = FUSED_CASES[kind]
    rng = np.random.default_rng(len(kind))
    d = 40
    if kind in ("linear", "per_r"):
        rb, rv = _grid(rng, (g["hp"], g["u"], g["cr"]), d)
        sb, sv = _grid(rng, (g["hp"], g["gp"], g["u"], g["cs"]), d)
        sc, _ = _grid(rng, sb.shape, d)
        tc, tv = _grid(rng, (g["gp"], g["ct"]), d)
        fn = (ops.fused_count3_linear if kind == "linear"
              else ops.fused_per_r_counts)
        args = (rb, rv, sb, sc, sv, tc, tv)
    elif kind == "cyclic":
        r_shape = (g["hp"], g["gp"], g["uh"], g["ug"], g["cr"])
        ra, rv = _grid(rng, r_shape, d)
        rb, _ = _grid(rng, r_shape, d)
        sb, sv = _grid(rng, (g["gp"], g["fp"], g["ug"], g["cs"]), d)
        sc, _ = _grid(rng, sb.shape, d)
        tc, tv = _grid(rng, (g["hp"], g["fp"], g["uh"], g["ct"]), d)
        ta, _ = _grid(rng, tc.shape, d)
        fn = functools.partial(ops.fused_count3_cyclic, pair_index=False)
        args = (ra, rb, rv, sb, sc, sv, tc, ta, tv)
    else:
        rb, rv = _grid(rng, (g["uh"], g["cr"]), d)
        sb, sv = _grid(rng, (g["ch"], g["uh"], g["ug"], g["cs"]), d)
        sc, _ = _grid(rng, sb.shape, d)
        tc, tv = _grid(rng, (g["ug"], g["ct"]), d)
        fn = ops.fused_count3_star
        args = (rb, rv, sb, sc, sv, tc, tv)
    want = np.asarray(fn(*args, use_kernel=False))
    got = np.asarray(fn(*args, use_kernel=True))
    assert got.shape == want.shape
    assert want.sum() > 0
    np.testing.assert_array_equal(got, want)


def test_scan_driver_kernels_refuse_compiled_dispatch(monkeypatch):
    """The per-bucket scan-driver kernels never reach Mosaic: with a TPU
    backend they raise instead of compiling an unaligned block spec."""
    rng = np.random.default_rng(0)
    ka, va = _mk(rng, 2, 128, 9, "a")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    with pytest.raises(NotImplementedError, match="interpret-only"):
        ops.bucket_pair_count(ka, va, ka, va, use_kernel=True)


def test_cyclic_kernel_names_the_compiled_swap(monkeypatch):
    assert ops.cyclic_kernel(False, True) == "jnp-pair-index"
    assert ops.cyclic_kernel(True, True) == "pallas-pair-index"
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    assert ops.cyclic_kernel(True, False) == "pallas-all-pairs"
    assert "swapped" in ops.cyclic_kernel(True, True)


def _cyclic_pairidx_temp_bytes(hp):
    import jax
    gp, uh, ug, fp, cr, cs, ct = 8, 8, 8, 8, 8, 56, 64
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    args = ([i32((hp, gp, uh, ug, cr))] * 2 + [i32((gp, fp, ug, cs))] * 2
            + [i32((hp, fp, uh, ct))] * 2)
    compiled = jax.jit(ops._fused_cyclic_pairidx_ref).lower(*args).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def test_cyclic_pairidx_ref_working_set_is_one_h_row():
    """The jnp pair-index triangle sweep builds its per-bucket (Ct, Cr)
    prefix tables one coarse H row at a time, so its working set does not
    grow with the H grid.  Built for the whole grid at once, the triangle
    query's plan over 200k edges needs ~13.8 GB of scratch on a 16 GB v5e."""
    one, eight = _cyclic_pairidx_temp_bytes(1), _cyclic_pairidx_temp_bytes(8)
    assert eight < 2 * one


# --------------------------------------------------------------------------
# fused jnp roots: the sort-merge bucket count
# --------------------------------------------------------------------------

_KEY_FLOOR = -(1 << 30)


def _multiplicity_rows(case, rng):
    """(table [B, Ct], probes [B, Cp]) int32 rows for one named case."""
    t_sent, p_sent = ops._SENT["t"], ops._SENT["s"]
    if case == "duplicates":
        return (rng.integers(0, 3, (4, 97)), rng.integers(0, 4, (4, 203)))
    if case == "all_sentinel":
        table = rng.integers(0, 5, (3, 40))
        probes = rng.integers(0, 5, (3, 60))
        table[1], probes[1] = t_sent, p_sent        # a wholly padded row
        table[2] = t_sent                           # nothing to match
        probes[0, ::2] = p_sent
        return table, probes
    if case == "ct1_cp1":
        return rng.integers(0, 2, (5, 1)), rng.integers(0, 2, (5, 1))
    if case == "ct1":
        return rng.integers(0, 3, (2, 1)), rng.integers(0, 3, (2, 33))
    if case == "cp1":
        return rng.integers(0, 3, (2, 45)), rng.integers(0, 3, (2, 1))
    if case == "all_match":
        table = np.full((3, 17), 7)
        table[:, ::3] = 9
        return table, rng.choice([7, 9], (3, 29))
    if case == "near_key_floor":
        table = _KEY_FLOOR + rng.integers(0, 6, (3, 50))
        probes = _KEY_FLOOR + rng.integers(0, 6, (3, 70))
        table[:, -5:], probes[:, -3:] = t_sent, p_sent
        return table, probes
    assert case == "odd_lengths"
    return (rng.integers(-50, 50, (7, 131)), rng.integers(-50, 50, (7, 389)))


@pytest.mark.parametrize("case", ["duplicates", "all_sentinel", "ct1_cp1",
                                  "ct1", "cp1", "all_match",
                                  "near_key_floor", "odd_lengths"])
def test_bucket_multiplicity_matches_numpy(case):
    """The fused roots' per-probe count equals the all-pairs compare over
    each bucket row, sentinels included (table and probe sentinels differ,
    so padded slots never match)."""
    rng = np.random.default_rng(sum(map(ord, case)))
    table, probes = (np.asarray(x, np.int32)
                     for x in _multiplicity_rows(case, rng))
    want = np.sum(table[:, None, :] == probes[:, :, None], axis=-1)
    got = np.asarray(ops._bucket_multiplicity(jnp.asarray(table),
                                              jnp.asarray(probes)))
    assert got.dtype == np.int32 and got.shape == probes.shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", ["linear", "star"])
def test_fused_jnp_roots_lower_without_gather_scatter_or_loop(kind):
    """The jnp fused roots count with sorts and scans alone: no gather,
    scatter or while loop, each of which costs the chip a memory round
    trip per element or per search step."""
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    b1 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bool_)
    if kind == "linear":
        fn, r, s, t = ops.fused_count3_linear, (2, 3, 40), (2, 3, 3, 24), (3, 56)
    else:
        fn, r, s, t = ops.fused_count3_star, (3, 40), (2, 3, 4, 24), (4, 56)
    text = fn.lower(i32(r), b1(r), i32(s), i32(s), b1(s), i32(t), b1(t),
                    use_kernel=False).as_text()
    assert "stablehlo.sort" in text
    assert not re.findall(r"stablehlo\.(gather|scatter|while)\b", text)
