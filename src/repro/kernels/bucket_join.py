"""Pallas TPU kernels for the per-bucket join inner loops.

This is the compute hot-spot the paper optimizes: once relations are radix
partitioned, each PMU (here: one VMEM-resident bucket triple per grid step)
joins tiny relations with all-pairs compares.  On Plasticine the compare is
a 16-lane SIMD loop in a PCU; on TPU we map it to:

* VPU 8×128 lanes for the equality matrices (branch-free compares on
  sentinel-masked keys), and
* the MXU for the contraction steps — per-key probe weights and the cyclic
  existence matrix are literally matmuls over 0/1 matrices
  (``count = Σ (M1ᵀ M2) ⊙ M3``).

Layout contract (enforced by ``ops.py``):
  - bucket grids ``[n_buckets, capacity]`` int32, capacity a multiple of 128
    (MXU/VPU lane alignment),
  - invalid slots pre-masked to per-side sentinels so cross-side equality of
    invalid slots is impossible and kernels stay mask-free,
  - per-bucket counts ≤ 2^24 so f32 accumulation is exact (bucket capacities
    are VMEM-bounded, far below this).

Grid: one program per bucket (the ``n_buckets`` grid dimension is
embarrassingly parallel — Plasticine's U-way PMU parallelism).  BlockSpecs
pin one bucket row of each operand in VMEM per step; Pallas double-buffers
the HBM→VMEM streams across grid steps, which is exactly the paper's
prefetch/double-buffering optimization (§6.2).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row(ref):
    """Load a (1, C) block as a (C,) vector."""
    return ref[0, :]


# --------------------------------------------------------------------------
# binary pair count
# --------------------------------------------------------------------------

def _pair_count_kernel(ka_ref, kb_ref, out_ref):
    ka = _row(ka_ref)
    kb = _row(kb_ref)
    m = (ka[:, None] == kb[None, :]).astype(jnp.float32)
    out_ref[0, 0] = jnp.sum(m)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_count(ka: jnp.ndarray, kb: jnp.ndarray, *, interpret: bool = True):
    b, ca = ka.shape
    _, cb = kb.shape
    out = pl.pallas_call(
        _pair_count_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, ca), lambda i: (i, 0)),
            pl.BlockSpec((1, cb), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        interpret=interpret,
    )(ka, kb)
    return out[:, 0].astype(jnp.int32)


# --------------------------------------------------------------------------
# linear 3-way count (Algorithm 1 inner join)
# --------------------------------------------------------------------------

def _count3_linear_kernel(rb_ref, sb_ref, sc_ref, tc_ref, out_ref):
    rb = _row(rb_ref)
    sb = _row(sb_ref)
    sc = _row(sc_ref)
    tc = _row(tc_ref)
    wr = jnp.sum((sb[:, None] == rb[None, :]).astype(jnp.float32), axis=1)
    wt = jnp.sum((sc[:, None] == tc[None, :]).astype(jnp.float32), axis=1)
    out_ref[0, 0] = jnp.sum(wr * wt)


@functools.partial(jax.jit, static_argnames=("interpret",))
def count3_linear(rb, sb, sc, tc, *, interpret: bool = True):
    b, cr = rb.shape
    _, cs = sb.shape
    _, ct = tc.shape
    out = pl.pallas_call(
        _count3_linear_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, cr), lambda i: (i, 0)),
            pl.BlockSpec((1, cs), lambda i: (i, 0)),
            pl.BlockSpec((1, cs), lambda i: (i, 0)),
            pl.BlockSpec((1, ct), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        interpret=interpret,
    )(rb, sb, sc, tc)
    return out[:, 0].astype(jnp.int32)


# --------------------------------------------------------------------------
# per-R-slot counts (Example 1 per-user aggregate) — MXU contraction
# --------------------------------------------------------------------------

def _per_r_kernel(rb_ref, sb_ref, sc_ref, tc_ref, out_ref):
    rb = _row(rb_ref)
    sb = _row(sb_ref)
    sc = _row(sc_ref)
    tc = _row(tc_ref)
    wt = jnp.sum((sc[:, None] == tc[None, :]).astype(jnp.float32), axis=1)
    m1 = (sb[:, None] == rb[None, :]).astype(jnp.float32)      # (Cs, Cr)
    # c[r] = Σ_s w_s · m1[s, r]  ==  (1, Cs) @ (Cs, Cr)  — MXU
    out_ref[0, :] = jnp.dot(wt[None, :], m1,
                            preferred_element_type=jnp.float32)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def per_r_counts(rb, sb, sc, tc, *, interpret: bool = True):
    b, cr = rb.shape
    _, cs = sb.shape
    _, ct = tc.shape
    out = pl.pallas_call(
        _per_r_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, cr), lambda i: (i, 0)),
            pl.BlockSpec((1, cs), lambda i: (i, 0)),
            pl.BlockSpec((1, cs), lambda i: (i, 0)),
            pl.BlockSpec((1, ct), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, cr), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, cr), jnp.float32),
        interpret=interpret,
    )(rb, sb, sc, tc)
    return out.astype(jnp.int32)


# --------------------------------------------------------------------------
# cyclic 3-way (triangle) count — two MXU matmuls per bucket triple
# --------------------------------------------------------------------------

def _count3_cyclic_kernel(ra_ref, rb_ref, sb_ref, sc_ref, tc_ref, ta_ref,
                          out_ref):
    ra = _row(ra_ref)
    rb = _row(rb_ref)
    sb = _row(sb_ref)
    sc = _row(sc_ref)
    tc = _row(tc_ref)
    ta = _row(ta_ref)
    m1 = (sb[:, None] == rb[None, :]).astype(jnp.float32)      # (Cs, Cr)
    m2 = (sc[:, None] == tc[None, :]).astype(jnp.float32)      # (Cs, Ct)
    p = jnp.dot(m1.T, m2, preferred_element_type=jnp.float32)  # (Cr, Ct)
    m3 = (ra[:, None] == ta[None, :]).astype(jnp.float32)      # (Cr, Ct)
    out_ref[0, 0] = jnp.sum(p * m3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def count3_cyclic(ra, rb, sb, sc, tc, ta, *, interpret: bool = True):
    b, cr = ra.shape
    _, cs = sb.shape
    _, ct = tc.shape
    out = pl.pallas_call(
        _count3_cyclic_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, cr), lambda i: (i, 0)),
            pl.BlockSpec((1, cr), lambda i: (i, 0)),
            pl.BlockSpec((1, cs), lambda i: (i, 0)),
            pl.BlockSpec((1, cs), lambda i: (i, 0)),
            pl.BlockSpec((1, ct), lambda i: (i, 0)),
            pl.BlockSpec((1, ct), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        interpret=interpret,
    )(ra, rb, sb, sc, tc, ta)
    return out[:, 0].astype(jnp.int32)


# ==========================================================================
# Fused partition-sweep kernels (engine hot path)
# ==========================================================================
#
# The kernels above join ONE bucket row per grid step; the drivers in
# core/{linear3,cyclic3,star3}.py sweep the coarse H(B)×g(C) partition grid
# with nested lax.scan loops, launching a fresh pallas_call per step.  That
# serializes the sweep and leaves the grid dimension — the paper's U-way PMU
# parallelism — idle between launches.
#
# The fused variants below put the WHOLE sweep into one pallas_call: the grid
# spans (coarse partitions × PMU buckets × streaming buckets) and BlockSpec
# index maps pick the partition row per program.  Consequences:
#   * one kernel launch per query instead of h_parts·g_parts of them,
#   * Pallas double-buffers the HBM→VMEM operand streams across the whole
#     sweep (the §6.2 prefetch optimization, now spanning partitions),
#   * operands whose index map ignores the innermost grid dim (e.g. the R
#     partition during the g(C) stream) stay resident in VMEM — the paper's
#     "R partition pinned on-chip" falls out of the revisiting rule.
#
# The streaming dimension is innermost and accumulates into a revisited
# output block (zeroed when its program_id is 0 — the standard matmul-K
# pattern), so outputs are per-PMU-bucket partials, summed by the caller.
#
# Accumulators are int32, NOT f32: a single per-bucket step stays within
# the ≤2^24 exact-f32 contract, but the fused kernels accumulate a whole
# partition's sweep into one output cell, which can exceed it.
#
# Tiling.  Mosaic compiles a block only if its last two dims are multiples
# of (8, 128) or equal the array's, so the fused kernels are laid out as:
#   * each program joins a TILE of 8 bucket rows (the sublanes of one int32
#     vreg) along the PMU axis (u for linear, ug for cyclic/star);
#     ``ops.py`` pads that axis to a multiple of 8 with sentinel rows and
#     every capacity to a multiple of 128 lanes,
#   * an operand shared by the whole tile (the broadcast T bucket of the
#     linear sweep, the R row of star, the T row of cyclic) enters as the
#     (1, C) block of a [..., 1, C] view,
#   * per-cell counts leave through a lane-dense (8, 128) block (row k holds
#     cell k, broadcast along lanes); the wrapper reads lane 0,
#   * linear / per-R / star stream the S capacity as the innermost grid
#     axis in chunks of at most 512 lanes, so VMEM holds one S chunk plus
#     the resident R and T rows; all compares run over chunks of at most
#     512 lanes inside the kernel (``_chunk``).
# Equality matrices put the streamed side on lanes and the other side on
# sublanes (a transposed (8, chunk) tile), so the big reductions are
# sublane adds.  The cyclic contraction Σ_s M1ᵀM2 is a bf16 MXU matmul of
# 0/1 matrices with f32 accumulation (exact: each entry ≤ Cs ≤ 2^24).


TILE = 8       # bucket rows per program: the sublanes of one int32 vreg
LANES = 128


def _chunk(c: int, cap: int = 512) -> int:
    """Lane chunk for streaming a capacity-``c`` row (``c`` a multiple of
    128): the largest power of two ≤ ``cap`` that divides it."""
    return math.gcd(c, cap)


def _lanes(j, ch: int):
    """The j-th ``ch``-lane chunk as a ref index."""
    if isinstance(j, int):
        return pl.ds(j * ch, ch)
    return pl.ds(pl.multiple_of(j * ch, ch), ch)


def _loop(n: int, body, init):
    """``fori_loop`` over ``n`` chunks; a single chunk is traced inline."""
    if n == 1:
        return body(0, init)
    return jax.lax.fori_loop(0, n, body, init)


def _put_row(acc, k: int, row):
    """acc + ``row`` broadcast into row k only."""
    rows = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    return acc + jnp.where(rows == k, row, 0)


def _tile_matches(load, n: int, s):
    """m[k, j] = #{c : X[k, c] == s[k, j]} for a per-row operand X (TILE,
    n) read chunk by chunk through ``load(lanes)``; s is (TILE, sch)."""
    ch = _chunk(n)

    def step(j, m):
        xt = load(_lanes(j, ch)).T                   # (ch, TILE)
        for k in range(TILE):
            hit = (xt[:, k:k + 1] == s[k:k + 1, :]).astype(jnp.int32)
            m = _put_row(m, k, jnp.sum(hit, axis=0, keepdims=True))
        return m

    return _loop(n // ch, step, jnp.zeros(s.shape, jnp.int32))


def _shared_matches(load, n: int, s):
    """m[k, j] = #{c : x[c] == s[k, j]} for one row x (1, n) shared by the
    whole tile, read chunk by chunk through ``load(lanes)``."""
    ch = _chunk(n)

    def step(j, m):
        xt = jnp.broadcast_to(load(_lanes(j, ch)), (TILE, ch)).T[:, :1]
        for k in range(TILE):
            hit = (xt == s[k:k + 1, :]).astype(jnp.int32)   # (ch, sch)
            m = _put_row(m, k, jnp.sum(hit, axis=0, keepdims=True))
        return m

    return _loop(n // ch, step, jnp.zeros(s.shape, jnp.int32))


def _vmem_params(*block_bytes):
    """Scoped-VMEM limit for the resident blocks (double-buffered) plus
    headroom for the chunked compare intermediates."""
    need = 2 * sum(block_bytes) + (24 << 20)
    return pltpu.CompilerParams(vmem_limit_bytes=int(min(max(need, 32 << 20),
                                                         100 << 20)))


def _rows_bytes(rows: int, c: int) -> int:
    """VMEM bytes of an int32 (rows, c) block (rows pad to 8 sublanes)."""
    return max(rows, TILE) * c * 4


def _first_step(*axes):
    cond = pl.program_id(axes[0]) == 0
    for a in axes[1:]:
        cond = cond & (pl.program_id(a) == 0)
    return cond


def _cell_counts(out, shape):
    """Lane 0 of the lane-dense (…, tiles, 8, 128) count blocks."""
    return out[..., 0].reshape(shape)


def _fused_linear_kernel(rb_ref, sb_ref, sc_ref, tc_ref, out_ref):
    """grid = (hp, u/8, gp, Cs/sch); g (T stream) and S chunks innermost.
    rb (1, 8, Cr) · sb/sc (1, 1, 8, sch) · tc (1, 1, Ct) · out (1, 1, 8, 128)."""
    @pl.when(_first_step(2, 3))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    sb = sb_ref[0, 0]
    sc = sc_ref[0, 0]
    wr = _tile_matches(lambda l: rb_ref[0, :, l], rb_ref.shape[-1], sb)
    wt = _shared_matches(lambda l: tc_ref[0, :, l], tc_ref.shape[-1], sc)
    c = jnp.sum(wr * wt, axis=1, keepdims=True)                 # (8, 1)
    out_ref[0, 0] += jnp.broadcast_to(c, (TILE, LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_count3_linear(rb, sb, sc, tc, *, interpret: bool = True):
    """Whole linear-3 sweep in one launch.

    rb: [hp, u, Cr], sb/sc: [hp, gp, u, Cs], tc: [gp, Ct] with u a multiple
    of 8 and capacities multiples of 128; returns per-(H, h) bucket counts
    [hp, u] int32.
    """
    hp, u, cr = rb.shape
    _, gp, _, cs = sb.shape
    _, ct = tc.shape
    sch = _chunk(cs)
    s_spec = pl.BlockSpec((1, 1, TILE, sch), lambda i, k, j, c: (i, j, k, c))
    out = pl.pallas_call(
        _fused_linear_kernel,
        grid=(hp, u // TILE, gp, cs // sch),
        in_specs=[
            pl.BlockSpec((1, TILE, cr), lambda i, k, j, c: (i, k, 0)),
            s_spec, s_spec,
            pl.BlockSpec((1, 1, ct), lambda i, k, j, c: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TILE, LANES),
                               lambda i, k, j, c: (i, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((hp, u // TILE, TILE, LANES),
                                       jnp.int32),
        compiler_params=_vmem_params(_rows_bytes(TILE, cr),
                                     _rows_bytes(1, ct),
                                     2 * _rows_bytes(TILE, sch)),
        interpret=interpret,
    )(rb, sb, sc, tc.reshape(gp, 1, ct))
    return _cell_counts(out, (hp, u))


def _fused_per_r_kernel(rb_ref, sb_ref, sc_ref, tc_ref, out_ref):
    """grid = (hp, u/8, gp, Cs/sch); per-R-slot counts, g and S chunks
    innermost.  rb (1, 8, Cr) · sb/sc (1, 1, 8, sch) · tc (1, 1, Ct) ·
    out (1, 8, Cr)."""
    @pl.when(_first_step(2, 3))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    sc = sc_ref[0, 0]
    wt = _shared_matches(lambda l: tc_ref[0, :, l], tc_ref.shape[-1], sc)
    sbt = sb_ref[0, 0].T                                        # (sch, 8)
    wtt = wt.T
    cr = rb_ref.shape[-1]
    ch = _chunk(cr)

    def step(j, carry):
        lanes = _lanes(j, ch)
        r = rb_ref[0, :, lanes]                                 # (8, ch)
        m = jnp.zeros((TILE, ch), jnp.int32)
        for k in range(TILE):
            hit = (sbt[:, k:k + 1] == r[k:k + 1, :]).astype(jnp.int32)
            m = _put_row(m, k, jnp.sum(hit * wtt[:, k:k + 1], axis=0,
                                       keepdims=True))
        out_ref[0, :, lanes] += m
        return carry

    _loop(cr // ch, step, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_per_r_counts(rb, sb, sc, tc, *, interpret: bool = True):
    """Per-R-slot counts for the whole sweep: returns [hp, u, Cr] int32
    (same layout contract as ``fused_count3_linear``)."""
    hp, u, cr = rb.shape
    _, gp, _, cs = sb.shape
    _, ct = tc.shape
    sch = _chunk(cs)
    s_spec = pl.BlockSpec((1, 1, TILE, sch), lambda i, k, j, c: (i, j, k, c))
    r_spec = pl.BlockSpec((1, TILE, cr), lambda i, k, j, c: (i, k, 0))
    return pl.pallas_call(
        _fused_per_r_kernel,
        grid=(hp, u // TILE, gp, cs // sch),
        in_specs=[r_spec, s_spec, s_spec,
                  pl.BlockSpec((1, 1, ct), lambda i, k, j, c: (j, 0, 0))],
        out_specs=r_spec,
        out_shape=jax.ShapeDtypeStruct((hp, u, cr), jnp.int32),
        compiler_params=_vmem_params(2 * _rows_bytes(TILE, cr),
                                     _rows_bytes(1, ct),
                                     2 * _rows_bytes(TILE, sch)),
        interpret=interpret,
    )(rb, sb, sc, tc.reshape(gp, 1, ct))


def _fused_cyclic_kernel(ra_ref, rb_ref, sb_ref, sc_ref, tc_ref, ta_ref,
                         out_ref):
    """grid = (hp, gp, uh, ug/8, fp);  f (C stream) innermost.
    ra/rb (1,1,1,8,Cr) · sb/sc (1,1,8,Cs) · tc/ta (1,1,1,1,Ct) ·
    out (1,1,1,1,8,128).  count_k = Σ (M1ᵀ M2) ⊙ M3 with M1ᵀ[r,s] =
    [rb=sb], M2[s,t] = [sc=tc], M3[r,t] = [ra=ta]."""
    @pl.when(pl.program_id(4) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    cr, cs, ct = ra_ref.shape[-1], sb_ref.shape[-1], tc_ref.shape[-1]
    rch, sch, tch = _chunk(cr), _chunk(cs), _chunk(ct)

    def r_step(i, acc):
        rl = _lanes(i, rch)
        rat = ra_ref[0, 0, 0, :, rl].T                          # (rch, 8)
        rbt = rb_ref[0, 0, 0, :, rl].T

        def t_step(j, acc):
            tl = _lanes(j, tch)
            tcr = tc_ref[0, 0, 0, :, tl]                        # (1, tch)
            tar = ta_ref[0, 0, 0, :, tl]
            for k in range(TILE):
                def s_step(m, p, k=k):
                    sl = _lanes(m, sch)
                    sbr = sb_ref[0, 0, k:k + 1, sl]             # (1, sch)
                    sct = sc_ref[0, 0, :, sl].T[:, k:k + 1]     # (sch, 1)
                    m1t = (rbt[:, k:k + 1] == sbr).astype(jnp.float32)
                    m2 = (sct == tcr).astype(jnp.float32)
                    return p + jnp.dot(m1t.astype(jnp.bfloat16),
                                       m2.astype(jnp.bfloat16),
                                       preferred_element_type=jnp.float32)

                p = _loop(cs // sch, s_step,
                          jnp.zeros((rch, tch), jnp.float32))
                m3 = rat[:, k:k + 1] == tar                     # (rch, tch)
                ck = jnp.sum(jnp.where(m3, p.astype(jnp.int32), 0),
                             axis=(0, 1), keepdims=True)
                acc = _put_row(acc, k, ck)
            return acc

        return _loop(ct // tch, t_step, acc)

    acc = _loop(cr // rch, r_step, jnp.zeros((TILE, 1), jnp.int32))
    out_ref[0, 0, 0, 0] += jnp.broadcast_to(acc, (TILE, LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_count3_cyclic(ra, rb, sb, sc, tc, ta, *, interpret: bool = True):
    """Whole cyclic (triangle) sweep in one launch.

    ra/rb: [hp, gp, uh, ug, Cr] — the (H(A), G(B)) coarse grid × PMU grid;
    sb/sc: [gp, fp, ug, Cs] — S broadcast down columns via the index map;
    tc/ta: [hp, fp, uh, Ct] — T broadcast across rows via the index map.
    ug a multiple of 8, capacities multiples of 128.
    returns per-cell counts [hp, gp, uh, ug] int32.
    """
    hp, gp, uh, ug, cr = ra.shape
    _, fp, _, cs = sb.shape
    _, _, _, ct = tc.shape
    r_spec = pl.BlockSpec((1, 1, 1, TILE, cr),
                          lambda i, j, a, b, f: (i, j, a, b, 0))
    s_spec = pl.BlockSpec((1, 1, TILE, cs), lambda i, j, a, b, f: (j, f, b, 0))
    t_spec = pl.BlockSpec((1, 1, 1, 1, ct),
                          lambda i, j, a, b, f: (i, f, a, 0, 0))
    out = pl.pallas_call(
        _fused_cyclic_kernel,
        grid=(hp, gp, uh, ug // TILE, fp),
        in_specs=[r_spec, r_spec, s_spec, s_spec, t_spec, t_spec],
        out_specs=pl.BlockSpec((1, 1, 1, 1, TILE, LANES),
                               lambda i, j, a, b, f: (i, j, a, b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((hp, gp, uh, ug // TILE, TILE, LANES),
                                       jnp.int32),
        compiler_params=_vmem_params(2 * _rows_bytes(TILE, cr),
                                     2 * _rows_bytes(TILE, cs),
                                     2 * _rows_bytes(1, ct)),
        interpret=interpret,
    )(ra, rb, sb, sc, tc.reshape(hp, fp, uh, 1, ct),
      ta.reshape(hp, fp, uh, 1, ct))
    return _cell_counts(out, (hp, gp, uh, ug))


def _fused_cyclic_pairidx_kernel(ra_ref, rb_ref, sb_ref, sc_ref, tcs_ref,
                                 tas_ref, out_ref):
    """grid = (hp, gp, uh, ug, fp); T arrives as a lex-sorted (c, a)-pair
    index and each S slot range-scans it (two searchsorted probes) instead
    of the all-pairs contraction.  The range sums come from a prefix-sum
    table over the sorted run — O(Ct·Cr + Cs·Cr) per step instead of
    O(Cs·Cr·Ct).  Binary-search gathers keep this kernel interpret-mode
    (CPU/XLA) territory; the all-pairs variant remains the MXU mapping."""
    @pl.when(pl.program_id(4) == 0)
    def _():
        out_ref[0, 0, 0, 0] = 0

    ra = ra_ref[0, 0, 0, 0, :]
    rb = rb_ref[0, 0, 0, 0, :]
    sb = sb_ref[0, 0, 0, :]
    sc = sc_ref[0, 0, 0, :]
    tcs = tcs_ref[0, 0, 0, :]
    tas = tas_ref[0, 0, 0, :]
    lo = jnp.searchsorted(tcs, sc, side="left")                # [Cs]
    hi = jnp.searchsorted(tcs, sc, side="right")               # [Cs]
    m3 = (tas[:, None] == ra[None, :]).astype(jnp.int32)       # (Ct, Cr)
    pre = jnp.pad(jnp.cumsum(m3, axis=0), ((1, 0), (0, 0)))    # (Ct+1, Cr)
    g = jnp.take(pre, hi, axis=0) - jnp.take(pre, lo, axis=0)  # (Cs, Cr)
    e = (sb[:, None] == rb[None, :]).astype(jnp.int32)         # (Cs, Cr)
    out_ref[0, 0, 0, 0] += jnp.sum(e * g)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_count3_cyclic_pairidx(ra, rb, sb, sc, tcs, tas, *,
                                interpret: bool = True):
    """Fused cyclic sweep over a sorted (c, a)-pair index of T.

    Same layout contract as ``fused_count3_cyclic`` except tcs/tas must be
    lex-sorted by (c, a) along the capacity axis (``ops.lex_sort_pairs``).
    returns per-cell counts [hp, gp, uh, ug] int32.
    """
    hp, gp, uh, ug, cr = ra.shape
    _, fp, _, cs = sb.shape
    _, _, _, ct = tcs.shape
    out = pl.pallas_call(
        _fused_cyclic_pairidx_kernel,
        grid=(hp, gp, uh, ug, fp),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, cr),
                         lambda i, j, a, b, f: (i, j, a, b, 0)),
            pl.BlockSpec((1, 1, 1, 1, cr),
                         lambda i, j, a, b, f: (i, j, a, b, 0)),
            pl.BlockSpec((1, 1, 1, cs), lambda i, j, a, b, f: (j, f, b, 0)),
            pl.BlockSpec((1, 1, 1, cs), lambda i, j, a, b, f: (j, f, b, 0)),
            pl.BlockSpec((1, 1, 1, ct), lambda i, j, a, b, f: (i, f, a, 0)),
            pl.BlockSpec((1, 1, 1, ct), lambda i, j, a, b, f: (i, f, a, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, 1),
                               lambda i, j, a, b, f: (i, j, a, b)),
        out_shape=jax.ShapeDtypeStruct((hp, gp, uh, ug), jnp.int32),
        interpret=interpret,
    )(ra, rb, sb, sc, tcs, tas)
    return out


def _fused_star_kernel(rb_ref, sb_ref, sc_ref, tc_ref, out_ref):
    """grid = (uh, ug/8, chunks, Cs/sch);  the S arrival-order stream and S
    chunks innermost.  rb (1, 1, Cr) · sb/sc (1, 1, 8, sch) · tc (8, Ct) ·
    out (1, 1, 8, 128)."""
    @pl.when(_first_step(2, 3))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    sb = sb_ref[0, 0]
    sc = sc_ref[0, 0]
    wr = _shared_matches(lambda l: rb_ref[0, :, l], rb_ref.shape[-1], sb)
    wt = _tile_matches(lambda l: tc_ref[:, l], tc_ref.shape[-1], sc)
    c = jnp.sum(wr * wt, axis=1, keepdims=True)                 # (8, 1)
    out_ref[0, 0] += jnp.broadcast_to(c, (TILE, LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_count3_star(rb, sb, sc, tc, *, interpret: bool = True):
    """Whole star sweep in one launch: R pinned by rows, T by cols, S
    streamed in chunks.

    rb: [uh, Cr], sb/sc: [chunks, uh, ug, Cs], tc: [ug, Ct] with ug a
    multiple of 8 and capacities multiples of 128;
    returns per-PMU counts [uh, ug] int32.
    """
    uh, cr = rb.shape
    ch, _, ug, cs = sb.shape
    _, ct = tc.shape
    sch = _chunk(cs)
    s_spec = pl.BlockSpec((1, 1, TILE, sch), lambda i, k, j, c: (j, i, k, c))
    out = pl.pallas_call(
        _fused_star_kernel,
        grid=(uh, ug // TILE, ch, cs // sch),
        in_specs=[
            pl.BlockSpec((1, 1, cr), lambda i, k, j, c: (i, 0, 0)),
            s_spec, s_spec,
            pl.BlockSpec((TILE, ct), lambda i, k, j, c: (k, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TILE, LANES),
                               lambda i, k, j, c: (i, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((uh, ug // TILE, TILE, LANES),
                                       jnp.int32),
        compiler_params=_vmem_params(_rows_bytes(1, cr),
                                     _rows_bytes(TILE, ct),
                                     2 * _rows_bytes(TILE, sch)),
        interpret=interpret,
    )(rb.reshape(uh, 1, cr), sb, sc, tc)
    return _cell_counts(out, (uh, ug))
