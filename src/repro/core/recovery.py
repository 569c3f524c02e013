"""Shared skew-recovery round engine (the paper's §5 skew handling, unified).

Every multiway kind (linear §4, cyclic §5, star §6.5) recovers from bucket
overflow the same way — only the partition geometry differs.  This module
owns the round loop once; ``engine.MultiwayJoinEngine`` binds it to a kind
via a small :class:`KindOps` adapter.

The recovery-round contract
---------------------------
Per round ``rnd`` (salt = ``base_salt + rnd``):

1. **One hashing pass per relation.**  ``partition.composite_ids`` is called
   exactly once per relation per round; everything else in the round derives
   from those ids:

   * the exact per-bucket histogram (``np.bincount`` of the ids) — used for
     capacity sizing and overflow detection,
   * the salted bucket layout (``partition.bucketize_by_ids`` re-uses the
     ids — no re-hash),
   * the residual mask (the coarse cell of a row is id arithmetic:
     ``ids // inner_buckets`` — no re-hash).

   Earlier revisions re-hashed each relation 2–3× per round (layouts,
   histograms and residual masks each hashed independently); tests pin the
   one-pass property with a call-count probe on ``composite_ids`` /
   ``hashing.hash_bucket``.

2. **Exact partials are kept.**  Coarse cells whose buckets all fit are
   final: their fused partial counts are accumulated and never recomputed.
   Each output tuple is owned by exactly one row of the kind's *driving*
   relation (R for linear/cyclic, S for star), and that row lives in exactly
   one coarse cell per round, so kept partials never double count.

3. **Overflowed cells re-run.**  Rows of the driving relation in overflowed
   cells stay valid for the next round; everything else is masked out.  The
   next round re-partitions them with a fresh salt and geometrically grown
   capacities.

4. **The final round cannot overflow.**  Round ``max_rounds`` sizes every
   capacity from the exact histogram of that round's ids, so
   ``overflowed == False`` is a postcondition, not a hope.

Totals are accumulated host-side in Python ints and returned as
``np.int64`` — the fused kernels produce int32 *per-cell* partials (each
cell must stay below 2^31, which VMEM-bounded bucket capacities guarantee),
but the query total routinely exceeds int32 on large-cardinality joins.

Multi-step plans (``core.plan_ir``) wrap every fused 3-way step in this
round loop independently: a skewed materialized intermediate entering a
fused root is recovered exactly like a skewed base relation, because the
loop only ever sees (Relation, shape plan, KindOps) — it has no notion of
where its inputs came from.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import partition
from repro.core.relation import Relation, live_rows
from repro.core.results import JoinResult, PerRResult  # noqa: F401 (re-export)
from repro.core.spans import span, to_host
from repro.kernels import ops as kops

# Internal alias (see core.results): the recovery loop's scalar result IS
# the unified JoinResult — kept under the engine layer's historical name.
EngineResult = JoinResult


class RelPass(NamedTuple):
    """One relation's single hashing pass for one round."""
    ids: jnp.ndarray             # flat composite bucket id per row
    nb: int                      # number of flat buckets
    hist: np.ndarray             # exact per-bucket histogram, out_shape
    out_shape: tuple


def _align(n: int, align: int = 8) -> int:
    return max(align, int(math.ceil(n / align)) * align)


def grown(plan, growth: float, align: int = 8):
    """Geometric per-round bucket-capacity growth for re-run shards."""
    caps = {f: getattr(plan, f) for f in ("r_cap", "s_cap", "t_cap")}
    caps = {f: int(math.ceil(c * growth / align) * align)
            for f, c in caps.items()}
    return plan._replace(**caps)


def exact_cap(hist: np.ndarray) -> int:
    return _align(max(int(hist.max(initial=0)), 1))


def hash_pass(rel: Relation, specs, out_shape: tuple, salt: int) -> RelPass:
    """THE hashing pass: composite ids + the exact histogram derived from
    them.  Everything else a round needs re-uses the returned ids."""
    ids, nb = partition.composite_ids(rel, specs, salt)
    hist = np.bincount(to_host("hist", ids), minlength=nb + 1)[:nb]
    return RelPass(ids, nb, hist.reshape(out_shape), out_shape)


def layout(rel: Relation, p: RelPass, cap: int) -> partition.Buckets:
    """Bucketize from an existing pass — zero additional hashing."""
    return partition.bucketize_by_ids(rel, p.ids, p.nb, cap, p.out_shape)


def cell_of(p: RelPass, inner: int, n_cells: int) -> np.ndarray:
    """Coarse-cell id per row from composite-id arithmetic (no re-hash).
    Invalid rows land on a clipped cell; callers AND with ``rel.valid``."""
    return np.clip(to_host("cells", p.ids) // inner, 0, n_cells - 1)


# ==========================================================================
# kind adapters
# ==========================================================================

class LinearOps:
    """R(aB) ⋈ S(BC) ⋈ T(Cd): coarse cells are the H(B) partitions; the
    driving relation is R (T is shared by every cell and therefore exact-
    sized from its histogram every round — H-splitting cannot recover it)."""

    kind = "linear"
    driving = "r"

    def __init__(self, rb="b", sb="b", sc="c", tc="c"):
        self.rb, self.sb, self.sc, self.tc = rb, sb, sc, tc

    def specs(self, plan):
        hp, u, gp = plan.h_parts, plan.u, plan.g_parts
        return {
            "r": ([(self.rb, hp, "H"), (self.rb, u, "h")], (hp, u)),
            "s": ([(self.sb, hp, "H"), (self.sc, gp, "g"),
                   (self.sb, u, "h")], (hp, gp, u)),
            "t": ([(self.tc, gp, "g")], (gp,)),
        }

    def size_caps(self, plan, passes, final):
        plan = plan._replace(
            t_cap=max(plan.t_cap, exact_cap(passes["t"].hist)))
        if final:
            plan = plan._replace(r_cap=exact_cap(passes["r"].hist),
                                 s_cap=exact_cap(passes["s"].hist))
        return plan

    def count(self, L, plan, use_kernel):
        return kops.fused_count3_linear(
            L["r"].columns[self.rb], L["r"].valid, L["s"].columns[self.sb],
            L["s"].columns[self.sc], L["s"].valid, L["t"].columns[self.tc],
            L["t"].valid, use_kernel=use_kernel)                  # [hp, u]

    def bad_cells(self, passes, plan):
        return ((passes["r"].hist > plan.r_cap).any(axis=1)
                | (passes["s"].hist > plan.s_cap).any(axis=(1, 2)))  # [hp]

    def good_weight(self, bad):
        return ~bad[:, None]                                      # [hp, u]

    def residual(self, rels, passes, bad, plan):
        hp = plan.h_parts
        r_cell = cell_of(passes["r"], plan.u, hp)
        s_cell = cell_of(passes["s"], plan.g_parts * plan.u, hp)
        return {**rels,
                "r": rels["r"].mask_where(jnp.asarray(bad[r_cell])),
                "s": rels["s"].mask_where(jnp.asarray(bad[s_cell]))}

    def tuples_read(self, rels, plan):
        n_r, n_s, n_t = live_rows(rels["r"], rels["s"], rels["t"])
        return n_r + n_s + plan.h_parts * n_t


class CyclicOps:
    """R(AB) ⋈ S(BC) ⋈ T(CA) triangles: coarse cells are the H(A)×G(B)
    grid; R drives.  An S column / T row overflow taints every cell that
    reads it."""

    kind = "cyclic"
    driving = "r"

    def __init__(self, ra="a", rb="b", sb="b", sc="c", tc="c", ta="a",
                 pair_index=True):
        self.ra, self.rb, self.sb = ra, rb, sb
        self.sc, self.tc, self.ta = sc, tc, ta
        self.pair_index = pair_index

    def specs(self, plan):
        hp, gp, uh, ug, fp = (plan.h_parts, plan.g_parts, plan.uh, plan.ug,
                              plan.f_parts)
        return {
            "r": ([(self.ra, hp, "H"), (self.rb, gp, "G"),
                   (self.ra, uh, "h"), (self.rb, ug, "g")], (hp, gp, uh, ug)),
            "s": ([(self.sb, gp, "G"), (self.sc, fp, "f"),
                   (self.sb, ug, "g")], (gp, fp, ug)),
            "t": ([(self.ta, hp, "H"), (self.tc, fp, "f"),
                   (self.ta, uh, "h")], (hp, fp, uh)),
        }

    def size_caps(self, plan, passes, final):
        if final:
            plan = plan._replace(r_cap=exact_cap(passes["r"].hist),
                                 s_cap=exact_cap(passes["s"].hist),
                                 t_cap=exact_cap(passes["t"].hist))
        return plan

    def count(self, L, plan, use_kernel):
        return kops.fused_count3_cyclic(
            L["r"].columns[self.ra], L["r"].columns[self.rb], L["r"].valid,
            L["s"].columns[self.sb], L["s"].columns[self.sc], L["s"].valid,
            L["t"].columns[self.tc], L["t"].columns[self.ta], L["t"].valid,
            use_kernel=use_kernel,
            pair_index=self.pair_index)               # [hp, gp, uh, ug]

    def bad_cells(self, passes, plan):
        r_bad = (passes["r"].hist > plan.r_cap).any(axis=(2, 3))  # [hp, gp]
        s_bad = (passes["s"].hist > plan.s_cap).any(axis=(1, 2))  # [gp]
        t_bad = (passes["t"].hist > plan.t_cap).any(axis=(1, 2))  # [hp]
        return r_bad | s_bad[None, :] | t_bad[:, None]

    def good_weight(self, bad):
        return ~bad[:, :, None, None]

    def residual(self, rels, passes, bad, plan):
        n_cells = plan.h_parts * plan.g_parts
        r_cell = cell_of(passes["r"], plan.uh * plan.ug, n_cells)
        return {**rels,
                "r": rels["r"].mask_where(
                    jnp.asarray(bad.reshape(-1)[r_cell]))}

    def tuples_read(self, rels, plan):
        n_r, n_s, n_t = live_rows(rels["r"], rels["s"], rels["t"])
        return n_r + plan.h_parts * n_s + plan.g_parts * n_t


class StarOps:
    """Dimension R(aB), fact S(BC), dimension T(Cd): coarse cells are the
    uh×ug PMU grid; the fact relation S drives (each output tuple owns
    exactly one fact row)."""

    kind = "star"
    driving = "s"

    def __init__(self, rb="b", sb="b", sc="c", tc="c"):
        self.rb, self.sb, self.sc, self.tc = rb, sb, sc, tc

    def specs(self, plan):
        return {
            "r": ([(self.rb, plan.uh, "h")], (plan.uh,)),
            "t": ([(self.tc, plan.ug, "g")], (plan.ug,)),
        }

    def s_pass(self, rel, plan, salt):
        """S adds an arrival-order chunk level on top of the hashed
        (h(B), g(C)) pair — composed arithmetically, still ONE hash pass."""
        uh, ug, ch = plan.uh, plan.ug, plan.chunks
        ids2, nb2 = partition.composite_ids(
            rel, [(self.sb, uh, "h"), (self.sc, ug, "g")], salt)
        chunk = jnp.where(
            rel.valid,
            (jnp.arange(rel.capacity, dtype=jnp.int32) * ch) // rel.capacity,
            0)
        nb = ch * nb2
        ids = jnp.where(rel.valid,
                        chunk * nb2 + jnp.clip(ids2, 0, nb2 - 1),
                        jnp.int32(nb))
        hist = np.bincount(to_host("hist", ids), minlength=nb + 1)[:nb]
        return RelPass(ids, nb, hist.reshape(ch, uh, ug), (ch, uh, ug))

    def size_caps(self, plan, passes, final):
        if final:
            plan = plan._replace(r_cap=exact_cap(passes["r"].hist),
                                 s_cap=exact_cap(passes["s"].hist),
                                 t_cap=exact_cap(passes["t"].hist))
        return plan

    def count(self, L, plan, use_kernel):
        return kops.fused_count3_star(
            L["r"].columns[self.rb], L["r"].valid, L["s"].columns[self.sb],
            L["s"].columns[self.sc], L["s"].valid, L["t"].columns[self.tc],
            L["t"].valid, use_kernel=use_kernel)                  # [uh, ug]

    def bad_cells(self, passes, plan):
        r_bad = passes["r"].hist > plan.r_cap                     # [uh]
        t_bad = passes["t"].hist > plan.t_cap                     # [ug]
        s_bad = (passes["s"].hist > plan.s_cap).any(axis=0)       # [uh, ug]
        return r_bad[:, None] | t_bad[None, :] | s_bad

    def good_weight(self, bad):
        return ~bad

    def residual(self, rels, passes, bad, plan):
        uh, ug = plan.uh, plan.ug
        s_cell = to_host("cells", passes["s"].ids) % (uh * ug)
        s_cell = np.clip(s_cell, 0, uh * ug - 1)
        return {**rels,
                "s": rels["s"].mask_where(
                    jnp.asarray(bad.reshape(-1)[s_cell]))}

    def tuples_read(self, rels, plan):
        return sum(live_rows(rels["r"], rels["s"], rels["t"]))


OPS = {"linear": LinearOps, "cyclic": CyclicOps, "star": StarOps}


def ops_from_binding(binding, **kw):
    """Build the KindOps adapter from a ``query.Binding`` — the checked
    column binding replaces the per-kind kwarg soup, so the recovery layer
    and the fused layouts are guaranteed to agree on column roles."""
    return OPS[binding.kind](**binding.col_kwargs(), **kw)


# ==========================================================================
# the round loop
# ==========================================================================

def _round_pass(ops, rels, plan, salt, final):
    """One round's single-hash passes, capacity sizing and layouts."""
    passes = {}
    for key, (specs, out_shape) in ops.specs(plan).items():
        passes[key] = hash_pass(rels[key], specs, out_shape, salt)
    if hasattr(ops, "s_pass"):
        passes["s"] = ops.s_pass(rels["s"], plan, salt)
    plan = ops.size_caps(plan, passes, final)
    caps = {"r": plan.r_cap, "s": plan.s_cap, "t": plan.t_cap}
    layouts = {k: layout(rels[k], passes[k], caps[k]) for k in passes}
    return plan, passes, layouts


def run_count_rounds(ops, r: Relation, s: Relation, t: Relation, plan, *,
                     max_rounds: int = 3, growth: float = 2.0,
                     use_kernel: bool = False,
                     base_salt: int = 0) -> EngineResult:
    """The shared recovery loop: fused sweep, keep exact partials, re-run
    overflowed cells, exact-sized final round (see module docstring).
    Each round runs under a ``repro.recovery.round`` span and each
    residual mask under ``repro.recovery.residual``."""
    rels = {"r": r, "s": s, "t": t}
    total, tuples = 0, 0
    for rnd in range(max_rounds + 1):
        final = rnd == max_rounds
        with span("recovery.round", round=rnd, final=int(final)):
            plan, passes, layouts = _round_pass(ops, rels, plan,
                                                base_salt + rnd, final)
            counts = to_host("counts", ops.count(layouts, plan, use_kernel)
                             ).astype(np.int64)
            bad = ops.bad_cells(passes, plan)
            tuples += ops.tuples_read(rels, plan)
        if final or not bad.any():
            total += int(counts.sum())
            return EngineResult(np.int64(total), jnp.asarray(False),
                                np.int64(tuples), rnd + 1)
        total += int((counts * ops.good_weight(bad)).sum())
        with span("recovery.residual", round=rnd):
            rels = ops.residual(rels, passes, bad, plan)
        plan = grown(plan, growth)
    raise AssertionError("unreachable: final round is exact-sized")


def run_per_r_rounds(ops: LinearOps, r: Relation, s: Relation, t: Relation,
                     plan, *, max_rounds: int = 3, growth: float = 2.0,
                     use_kernel: bool = False, base_salt: int = 0,
                     key_col: str = "a") -> PerRResult:
    """Linear-only per-R-tuple aggregate under the same round contract.
    Emits (keys, counts, valid) aligned with each round's R layout; kept
    slots are those of exact cells (plus everything in the final round)."""
    rels = {"r": r, "s": s, "t": t}
    keys_out, counts_out, valid_out = [], [], []
    rounds, tuples = 0, 0
    for rnd in range(max_rounds + 1):
        final = rnd == max_rounds
        with span("recovery.round", round=rnd, final=int(final)):
            plan, passes, layouts = _round_pass(ops, rels, plan,
                                                base_salt + rnd, final)
            tuples += ops.tuples_read(rels, plan)
            rg = layouts["r"]
            counts = kops.fused_per_r_counts(
                rg.columns[ops.rb], rg.valid, layouts["s"].columns[ops.sb],
                layouts["s"].columns[ops.sc], layouts["s"].valid,
                layouts["t"].columns[ops.tc], layouts["t"].valid,
                use_kernel=use_kernel)                        # [hp, u, Cr]
            bad = ops.bad_cells(passes, plan)
            key = key_col if key_col in rg.columns else ops.rb
            valid = rg.valid
            if bad.any() and not final:
                valid = valid & jnp.asarray(~bad)[:, None, None]
            keys_out.append(rg.columns[key].reshape(-1))
            counts_out.append(
                to_host("counts", counts).astype(np.int64).reshape(-1))
            valid_out.append(valid.reshape(-1))
        rounds = rnd + 1
        if final or not bad.any():
            break
        with span("recovery.residual", round=rnd):
            rels = ops.residual(rels, passes, bad, plan)
        plan = grown(plan, growth)
    keys = jnp.concatenate(keys_out)
    counts = np.concatenate(counts_out)
    valid = jnp.concatenate(valid_out)
    total = int(counts[to_host("valid", valid)].sum())
    return PerRResult(count=np.int64(total), overflowed=jnp.asarray(False),
                      tuples_read=np.int64(tuples), rounds=rounds,
                      keys=keys, counts=counts, valid=valid)
