"""Unified multiway join engine: fused partition sweeps + skew recovery.

This is the execution layer the paper's numbers assume.  The per-algorithm
drivers in ``linear3.py`` / ``cyclic3.py`` / ``star3.py`` sweep the coarse
H(B)×g(C) partition grid with nested ``lax.scan`` loops, launching one
bucket-row kernel per step — the grid dimension (the paper's U-way PMU
parallelism, §4–§6) sits idle between launches.  The engine instead issues
ONE fused kernel per query (``kernels.ops.fused_*``): the Pallas grid spans
``(h_parts, u, g_parts)`` (resp. the cyclic/star equivalents), BlockSpec
index maps pick the partition row per program, and Pallas double-buffers the
HBM→VMEM operand streams across the whole sweep (§6.2 prefetching, now
spanning partitions rather than restarting per bucket row).

Skew recovery (paper §5's skew discussion, made correct-by-construction)
-----------------------------------------------------------------------
Fixed-capacity buckets overflow under key skew.  The scan drivers only
*flag* this; the ``core.reference`` baselines re-run the whole query with
grown capacities.  The engine recovers surgically instead via the shared round
engine in ``core.recovery``: exact coarse partitions keep their fused
partial counts, overflowed ones re-run with a salted hash and grown
capacities, and the final round is exact-histogram-sized so it cannot
overflow — ``overflowed == False`` is a postcondition.  Each round performs
exactly ONE hashing pass per relation (histograms, layouts and residual
masks all derive from one ``composite_ids`` call); see ``recovery``'s
docstring for the full contract and exactness argument.

The ``*_count_fused`` functions are single-pass and fully traceable (jit /
shard_map safe); ``MultiwayJoinEngine`` adds the host-side recovery loop.

The engine executes exactly one 3-relation step.  N-way queries reach it
through ``core.plan_ir``: the planner decomposes the predicate tree into
binary materialize steps feeding a fused 3-way root, and each ``fused3``
plan step runs through ``MultiwayJoinEngine.count`` — so the recovery
contract (one hashing pass per relation per round, exact partials kept,
``overflowed == False``) holds per step of a multi-step plan.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core import cyclic3, linear3, partition, recovery, star3
from repro.core.recovery import EngineResult, PerRResult  # noqa: F401  (re-export)
from repro.core.relation import Relation, live_rows
from repro.kernels import ops as kops


# ==========================================================================
# int64-exact traffic counters (without jax_enable_x64)
# ==========================================================================

_MASK15 = 0x7FFF
_MASK30 = (1 << 30) - 1


class Traffic64(NamedTuple):
    """A tuples-read total as two int32 limbs (lo < 2^30, hi = value >> 30).

    x64 stays off framework-wide, so a traced ``h_parts * t.n`` product
    must not be computed in int32 — large sweeps wrap (h_parts=1024 over a
    4M-row T is already 2^32).  Same trick as the psum limbs in
    ``distributed._round_sharded``; ``int()`` recombines host-side.
    """

    hi: jnp.ndarray              # () int32, units of 2^30
    lo: jnp.ndarray              # () int32, < 2^30

    def __int__(self) -> int:
        return (int(self.hi) << 30) + int(self.lo)


def traffic64(terms) -> Traffic64:
    """Σ k·n over ``(static int k, traced int32 scalar n)`` terms, exactly.

    Every intermediate product stays below 2^31: k splits statically into
    15-bit limbs, n dynamically (n < 2^31 ⇒ n >> 15 < 2^16), and carries
    propagate after each partial product.  Supports totals up to 2^61.
    """
    hi = jnp.int32(0)
    lo = jnp.int32(0)

    def add(hi, lo, v):
        lo = lo + (v & _MASK30)
        hi = hi + (v >> 30) + ((lo >> 30) & 1)
        return hi, lo & _MASK30

    for k, n in terms:
        k = int(k)
        if k == 0:
            continue
        if not 0 < k < 2**31:
            raise ValueError(f"static traffic multiplier {k} out of range")
        k_hi, k_lo = divmod(k, 1 << 15)
        n = jnp.asarray(n, jnp.int32)
        n_hi = n >> 15
        n_lo = n & _MASK15
        hi, lo = add(hi, lo, jnp.int32(k_lo) * n_lo)
        for m in (jnp.int32(k_hi) * n_lo, jnp.int32(k_lo) * n_hi):
            hi, lo = add(hi, lo, (m & _MASK15) << 15)
            hi = hi + (m >> 15)
        hi = hi + jnp.int32(k_hi) * n_hi
    return Traffic64(hi, lo)


# ==========================================================================
# salted layouts (Fig 2 / Fig 3 data reorganization, re-randomizable)
# ==========================================================================

def linear3_layouts(r: Relation, s: Relation, t: Relation,
                    plan: linear3.Linear3Plan, *, salt: int = 0,
                    rb: str = "b", sb: str = "b", sc: str = "c",
                    tc: str = "c"):
    """R → [hp,u,cap], S → [hp,gp,u,cap], T → [gp,cap] (salted)."""
    hp, u, gp = plan.h_parts, plan.u, plan.g_parts
    r_ids, r_nb = partition.composite_ids(
        r, [(rb, hp, "H"), (rb, u, "h")], salt)
    rg = partition.bucketize_by_ids(r, r_ids, r_nb, plan.r_cap, (hp, u))
    s_ids, s_nb = partition.composite_ids(
        s, [(sb, hp, "H"), (sc, gp, "g"), (sb, u, "h")], salt)
    sg = partition.bucketize_by_ids(s, s_ids, s_nb, plan.s_cap, (hp, gp, u))
    tg = partition.bucketize(t, tc, gp, plan.t_cap, fn="g", salt=salt)
    return rg, sg, tg


def cyclic3_layouts(r: Relation, s: Relation, t: Relation,
                    plan: cyclic3.Cyclic3Plan, *, salt: int = 0,
                    ra: str = "a", rb: str = "b", sb: str = "b",
                    sc: str = "c", tc: str = "c", ta: str = "a"):
    """R → [hp,gp,uh,ug,cap], S → [gp,fp,ug,cap], T → [hp,fp,uh,cap]."""
    hp, gp, uh, ug, fp = (plan.h_parts, plan.g_parts, plan.uh, plan.ug,
                          plan.f_parts)
    r_ids, r_nb = partition.composite_ids(
        r, [(ra, hp, "H"), (rb, gp, "G"), (ra, uh, "h"), (rb, ug, "g")], salt)
    rg = partition.bucketize_by_ids(r, r_ids, r_nb, plan.r_cap,
                                    (hp, gp, uh, ug))
    s_ids, s_nb = partition.composite_ids(
        s, [(sb, gp, "G"), (sc, fp, "f"), (sb, ug, "g")], salt)
    sg = partition.bucketize_by_ids(s, s_ids, s_nb, plan.s_cap, (gp, fp, ug))
    t_ids, t_nb = partition.composite_ids(
        t, [(ta, hp, "H"), (tc, fp, "f"), (ta, uh, "h")], salt)
    tg = partition.bucketize_by_ids(t, t_ids, t_nb, plan.t_cap, (hp, fp, uh))
    return rg, sg, tg


def star3_layouts(r: Relation, s: Relation, t: Relation,
                  plan: star3.Star3Plan, *, salt: int = 0, rb: str = "b",
                  sb: str = "b", sc: str = "c", tc: str = "c"):
    """R → [uh,cap], S → [ch,uh,ug,cap], T → [ug,cap] (salted)."""
    uh, ug, ch = plan.uh, plan.ug, plan.chunks
    rg = partition.bucketize(r, rb, uh, plan.r_cap, fn="h", salt=salt)
    tg = partition.bucketize(t, tc, ug, plan.t_cap, fn="g", salt=salt)
    chunk_ids = jnp.where(
        s.valid,
        (jnp.arange(s.capacity, dtype=jnp.int32) * ch) // s.capacity, 0)
    hb = partition.bucket_ids_for(s, sb, uh, "h", salt)
    gc = partition.bucket_ids_for(s, sc, ug, "g", salt)
    flat = jnp.where(s.valid, (chunk_ids * uh + hb) * ug + gc,
                     jnp.int32(ch * uh * ug))
    sg = partition.bucketize_by_ids(s, flat, ch * uh * ug, plan.s_cap,
                                    (ch, uh, ug))
    return rg, sg, tg


# ==========================================================================
# single-pass fused counts (traceable: jit / shard_map safe)
# ==========================================================================

def linear3_count_fused(r: Relation, s: Relation, t: Relation,
                        plan: linear3.Linear3Plan, *,
                        use_kernel: bool = False, salt: int = 0,
                        rb: str = "b", sb: str = "b", sc: str = "c",
                        tc: str = "c") -> linear3.Linear3Result:
    """Algorithm 1 as ONE fused launch (overflow flagged, not recovered)."""
    rg, sg, tg = linear3_layouts(r, s, t, plan, salt=salt, rb=rb, sb=sb,
                                 sc=sc, tc=tc)
    c = kops.fused_count3_linear(rg.columns[rb], rg.valid, sg.columns[sb],
                                 sg.columns[sc], sg.valid, tg.columns[tc],
                                 tg.valid, use_kernel=use_kernel)
    overflow = rg.overflowed | sg.overflowed | tg.overflowed
    tuples = traffic64([(1, r.n), (1, s.n), (plan.h_parts, t.n)])
    return linear3.Linear3Result(jnp.sum(c), overflow, tuples)


def cyclic3_count_fused(r: Relation, s: Relation, t: Relation,
                        plan: cyclic3.Cyclic3Plan, *,
                        use_kernel: bool = False, salt: int = 0,
                        pair_index: bool = True,
                        ra: str = "a", rb: str = "b", sb: str = "b",
                        sc: str = "c", tc: str = "c",
                        ta: str = "a") -> cyclic3.Cyclic3Result:
    """The §5 grid algorithm as ONE fused launch (sorted (c, a)-pair-index
    probes by default; ``pair_index=False`` for the all-pairs contraction)."""
    rg, sg, tg = cyclic3_layouts(r, s, t, plan, salt=salt, ra=ra, rb=rb,
                                 sb=sb, sc=sc, tc=tc, ta=ta)
    c = kops.fused_count3_cyclic(rg.columns[ra], rg.columns[rb], rg.valid,
                                 sg.columns[sb], sg.columns[sc], sg.valid,
                                 tg.columns[tc], tg.columns[ta], tg.valid,
                                 use_kernel=use_kernel,
                                 pair_index=pair_index)
    overflow = rg.overflowed | sg.overflowed | tg.overflowed
    tuples = traffic64([(1, r.n), (plan.h_parts, s.n),
                        (plan.g_parts, t.n)])
    return cyclic3.Cyclic3Result(jnp.sum(c), overflow, tuples)


def star3_count_fused(r: Relation, s: Relation, t: Relation,
                      plan: star3.Star3Plan, *, use_kernel: bool = False,
                      salt: int = 0, rb: str = "b", sb: str = "b",
                      sc: str = "c", tc: str = "c") -> star3.Star3Result:
    """The §6.5 star join as ONE fused launch."""
    rg, sg, tg = star3_layouts(r, s, t, plan, salt=salt, rb=rb, sb=sb,
                               sc=sc, tc=tc)
    c = kops.fused_count3_star(rg.columns[rb], rg.valid, sg.columns[sb],
                               sg.columns[sc], sg.valid, tg.columns[tc],
                               tg.valid, use_kernel=use_kernel)
    overflow = rg.overflowed | sg.overflowed | tg.overflowed
    tuples = traffic64([(1, r.n), (1, s.n), (1, t.n)])
    return star3.Star3Result(jnp.sum(c), overflow, tuples)


# ==========================================================================
# the engine: fused sweeps + surgical skew recovery
# ==========================================================================

class MultiwayJoinEngine:
    """Executable multiway hash join with per-partition skew recovery.

    Parameters
    ----------
    kind:        "linear" | "cyclic" | "star" — which §4/§5/§6.5 plan.
    use_kernel:  dispatch the fused Pallas kernels (TPU) instead of the
                 fused jnp path (CPU/XLA).
    max_rounds:  recovery rounds before the exact-histogram final round.
    growth:      geometric per-round bucket-capacity growth for re-run
                 shards.

    ``count`` is host-side (it inspects overflow histograms between rounds);
    use the module-level ``*_count_fused`` functions inside jit/shard_map.
    """

    KINDS = ("linear", "cyclic", "star")

    def __init__(self, kind: str = "linear", *, use_kernel: bool = False,
                 max_rounds: int = 3, growth: float = 2.0,
                 base_salt: int = 0):
        if kind not in self.KINDS:
            raise ValueError(f"unknown kind {kind!r}; choose from {self.KINDS}")
        self.kind = kind
        self.use_kernel = use_kernel
        self.max_rounds = max_rounds
        self.growth = growth
        self.base_salt = base_salt

    # -- planning ----------------------------------------------------------

    def default_plan(self, n_r: int, n_s: int, n_t: int, *, m_budget: int,
                     **kw):
        if self.kind == "linear":
            return linear3.default_plan(n_r, n_s, n_t, m_budget=m_budget,
                                        **kw)
        if self.kind == "cyclic":
            return cyclic3.default_plan(n_r, n_s, n_t, m_budget=m_budget,
                                        **kw)
        return star3.default_plan(n_r, n_s, n_t, **kw)

    # -- execution ---------------------------------------------------------

    def count(self, r: Relation, s: Relation, t: Relation, plan=None, *,
              m_budget: int | None = None, binding=None,
              **cols) -> EngineResult:
        """Exact skew-recovered COUNT.  Column names come from ``binding``
        (a ``query.Binding`` — the recovery KindOps are built from it) or
        the legacy per-kind ``rb=/sb=/...`` kwargs."""
        if plan is None:
            if m_budget is None:
                raise ValueError("pass a plan or m_budget")
            plan = self.default_plan(*live_rows(r, s, t), m_budget=m_budget)
        if binding is not None:
            if binding.kind != self.kind:
                raise ValueError(f"binding classified {binding.kind!r}, "
                                 f"engine built for {self.kind!r}")
            ops = binding.kind_ops()
        else:
            ops = recovery.OPS[self.kind](**cols)
        return recovery.run_count_rounds(
            ops, r, s, t, plan, max_rounds=self.max_rounds,
            growth=self.growth, use_kernel=self.use_kernel,
            base_salt=self.base_salt)

    # -- per-R aggregates (linear only) ------------------------------------

    def per_r_counts(self, r: Relation, s: Relation, t: Relation, plan, *,
                     rb: str = "b", sb: str = "b", sc: str = "c",
                     tc: str = "c", key_col: str = "a",
                     binding=None) -> PerRResult:
        """Per-R-tuple counts (Example 1) with skew recovery.  Returns
        flattened (keys, counts, valid) concatenated across rounds."""
        if self.kind != "linear":
            raise ValueError("per_r_counts is a linear-join aggregate")
        if binding is not None:
            ops = binding.kind_ops()
        else:
            ops = recovery.LinearOps(rb=rb, sb=sb, sc=sc, tc=tc)
        return recovery.run_per_r_rounds(
            ops, r, s, t, plan, max_rounds=self.max_rounds,
            growth=self.growth, use_kernel=self.use_kernel,
            base_salt=self.base_salt, key_col=key_col)
