"""Binary hash join and the cascaded-binary baseline (paper §6.3).

Two execution paths:

* **sorted path** (`join_count`, `join_materialize`, `probe_weight_sum`) —
  exact joins via sort + searchsorted range probes.  O((n+m) log n), static
  shapes, used as the in-framework oracle and for fast aggregates.

* **bucketed path** (`bucketed_join_count`) — the accelerator-shaped
  execution: hash-partition both sides into `[n_buckets, capacity]` grids
  (PMU layout) and run the per-bucket compare kernel from
  ``repro.kernels.ops``.  This is the structure Algorithm 1 builds on and is
  exact as long as no bucket overflows (overflow is returned, never hidden).

The cascade (first join materialized, second join aggregated) reproduces the
paper's binary baseline, including the bounded intermediate buffer whose
overflow models the DRAM/SSD spill cliff.

Device-resident sizing and the staged pipeline
----------------------------------------------
``exact_join_count`` used to be two host ``np.unique`` passes; it is now a
device-side sorted-key histogram: sort the build keys once, ``searchsorted``
the probe keys against them (per-probe segment counts), and reduce those
counts exactly in int64 via the two-limb base-2^15 trick the engine's
``Traffic64`` counters use (x64 stays off framework-wide).  The only
host↔device traffic is the two-scalar total.  The same primitive split into
``stage_join`` (sort + ranges + count, one jitted dispatch) and
``gather_staged`` (prefix-sum offsets + gather-materialize into a
bucketed-capacity buffer, one jitted dispatch) is the plan executor's
compiled binary-step pipeline: a cascade of binary steps never moves a
column to the host.  ``host_join_count`` keeps the old ``np.unique``
histogram as the parity oracle.
"""

from __future__ import annotations

import math
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition
from repro.core.reference import host_join_count  # noqa: F401  (oracle —
#   lives in core.reference now, the one np.unique-allowed module; kept
#   re-exported here because it is THE parity oracle for this module)
from repro.core.relation import SENTINEL, Relation
from repro.core.spans import to_host

_MASK15 = 0x7FFF


def _sum64(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact Σx over non-negative int32 values as two int32 limbs
    ``(hi, lo)`` with ``lo < 2^30`` and ``hi`` in units of 2^30 (the
    ``engine.Traffic64`` representation; ``int(hi) << 30 | lo`` recombines
    host-side).

    x64 is off framework-wide, so the reduction runs in base-2^15 limb
    planes: each plane value stays < 2^15, chunked partial sums of 2^14
    elements stay < 2^30, and carries re-normalize between levels.  Exact
    for totals < 2^61.
    """
    x = x.reshape(-1)
    if x.shape[0] == 0:
        return jnp.int32(0), jnp.int32(0)
    # base-2^15 limb planes of each element (x < 2^31 ⇒ 3 planes)
    planes = [x & _MASK15, (x >> 15) & _MASK15, x >> 30]
    chunk = 1 << 14
    while planes[0].shape[0] > 1:
        n = planes[0].shape[0]
        m = -(-n // chunk)
        pad = m * chunk - n
        carry = None
        nxt = []
        for p in planes:
            s = jnp.sum(jnp.pad(p, (0, pad)).reshape(m, chunk), axis=1)
            if carry is not None:
                s = s + carry            # partial < 2^29 + 2^15 < 2^30
            nxt.append(s & _MASK15)
            carry = s >> 15              # < 2^15: a valid next plane
        nxt.append(carry)
        planes = nxt
    p = [pl.reshape(()) for pl in planes] + [jnp.int32(0)] * 5
    lo = p[0] + (p[1] << 15)
    hi = p[2] + (p[3] << 15) + (p[4] << 30)
    return hi, lo


def _device_count(build: Relation, probe: Relation, *, build_key: str,
                  probe_key: str):
    """Sorted-key histogram count: per-probe segment counts + exact
    two-limb reduction, all on device."""
    _, skeys = partition.key_order(build, build_key)
    lo, hi = match_ranges(skeys, probe.col(probe_key))
    cnt = jnp.where(probe.valid, hi - lo, 0).astype(jnp.int32)
    return _sum64(cnt)


_device_count_jit = jax.jit(_device_count,
                            static_argnames=("build_key", "probe_key"))


def exact_join_count(build: Relation, build_key: str,
                     probe: Relation, probe_key: str) -> int:
    """Exact ``|build ⋈ probe|``, int64-exact without x64: one jitted
    device dispatch (sort + searchsorted segment counts + two-limb
    reduction), one two-scalar transfer.  The plan IR uses this both to
    size materialized intermediates exactly (a materialize step cannot
    overflow) and as the root aggregate of an all-binary cascade —
    ``host_join_count`` is the np.unique oracle it is tested against."""
    hi, lo = to_host("total", _device_count_jit(
        build, probe, build_key=build_key, probe_key=probe_key))
    return (int(hi) << 30) + int(lo)


# --------------------------------------------------------------------------
# sorted-path primitives
# --------------------------------------------------------------------------

def match_ranges(sorted_keys: jnp.ndarray, probe_keys: jnp.ndarray):
    """For each probe key, the [lo, hi) range of equal keys in sorted_keys."""
    lo = jnp.searchsorted(sorted_keys, probe_keys, side="left")
    hi = jnp.searchsorted(sorted_keys, probe_keys, side="right")
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def join_count(build: Relation, build_key: str,
               probe: Relation, probe_key: str) -> jnp.ndarray:
    """Exact number of matching (build, probe) pairs."""
    _, skeys = partition.key_order(build, build_key)
    lo, hi = match_ranges(skeys, probe.col(probe_key))
    cnt = jnp.where(probe.valid, hi - lo, 0)
    return jnp.sum(cnt.astype(jnp.int64) if cnt.dtype == jnp.int64
                   else cnt.astype(jnp.int32)).astype(jnp.int32)


def probe_weight_sum(build: Relation, build_key: str, build_weights: jnp.ndarray,
                     probe_keys: jnp.ndarray, probe_valid: jnp.ndarray) -> jnp.ndarray:
    """For each probe row: sum of weights over matching build rows.

    The workhorse for per-key multiway aggregates: weights flow backwards
    through each join stage (T -> S -> R) without materializing anything.
    """
    order, skeys = partition.key_order(build, build_key)
    w = jnp.where(build.valid, build_weights, 0)[order]
    cw = jnp.concatenate([jnp.zeros((1,), w.dtype), jnp.cumsum(w)])
    lo, hi = match_ranges(skeys, probe_keys)
    out = cw[hi] - cw[lo]
    return jnp.where(probe_valid, out, 0)


class MaterializeResult(NamedTuple):
    rel: Relation            # materialized join, fixed capacity, masked
    total: jnp.ndarray       # true (unclipped) number of result tuples
    overflowed: jnp.ndarray  # () bool — result exceeded out_capacity


def join_materialize(build: Relation, build_key: str,
                     probe: Relation, probe_key: str,
                     out_capacity: int,
                     build_prefix: str = "", probe_prefix: str = "") -> MaterializeResult:
    """Materialize the equi-join into a fixed-capacity Relation.

    Used for the cascaded-binary intermediate I = R ⋈ S (paper §6.3): the
    intermediate is written out (to DRAM in the paper) before the second
    join; ``overflowed`` models the spill condition.
    """
    sbuild, skeys = partition.sort_by_key(build, build_key)
    lo, hi = match_ranges(skeys, probe.col(probe_key))
    cnt = jnp.where(probe.valid, hi - lo, 0).astype(jnp.int32)
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt)])
    total = off[-1]

    slots = jnp.arange(out_capacity, dtype=jnp.int32)
    # probe row owning output slot p: last i with off[i] <= p
    owner = jnp.searchsorted(off, slots, side="right").astype(jnp.int32) - 1
    owner = jnp.clip(owner, 0, probe.capacity - 1)
    rank = slots - off[owner]
    bidx = jnp.clip(lo[owner] + rank, 0, build.capacity - 1)
    ok = slots < total

    cols = {}
    for name, col in sbuild.columns.items():
        cols[build_prefix + name] = jnp.where(ok, col[bidx],
                                              jnp.int32(SENTINEL))
    for name, col in probe.columns.items():
        key = probe_prefix + name
        if key in cols:  # join column appears once
            continue
        cols[key] = jnp.where(ok, col[owner], jnp.int32(SENTINEL))
    return MaterializeResult(Relation(cols, ok), total, total > out_capacity)


# --------------------------------------------------------------------------
# compiled binary-step pipeline (the plan executor's hot path)
# --------------------------------------------------------------------------

class StagedJoin(NamedTuple):
    """Stage 1 of a pipelined binary step, still on device: the sorted
    build side, the per-probe match ranges, and the exact two-limb total.
    ``staged_total`` syncs the two scalars; ``gather_staged`` finishes the
    materialization without re-sorting."""

    sorted_build: Relation     # build side sorted by its join key
    lo: jnp.ndarray            # (probe_cap,) int32 match-range starts
    cnt: jnp.ndarray           # (probe_cap,) int32 per-probe match counts
    total_hi: jnp.ndarray      # () int32, units of 2^30
    total_lo: jnp.ndarray      # () int32, < 2^30


def _stage_core(build: Relation, order: jnp.ndarray, skeys: jnp.ndarray,
                probe: Relation, *, probe_key: str) -> StagedJoin:
    sbuild = build.select(order, jnp.ones_like(order, dtype=bool))
    lo, hi = match_ranges(skeys, probe.col(probe_key))
    cnt = jnp.where(probe.valid, hi - lo, 0).astype(jnp.int32)
    thi, tlo = _sum64(cnt)
    return StagedJoin(sbuild, lo, cnt, thi, tlo)


_stage_jit = jax.jit(_stage_core, static_argnames=("probe_key",))


def stage_join(build: Relation, probe: Relation, *, build_key: str,
               probe_key: str) -> StagedJoin:
    """Stage 1 of a binary step: sort the build side by its key (the shared
    ``partition.stable_order`` program of its capacity), then match ranges
    and the exact total in one jitted dispatch."""
    order, skeys = partition.key_order(build, build_key)
    return _stage_jit(build, order, skeys, probe, probe_key=probe_key)


def staged_total(staged: StagedJoin) -> int:
    """Host-sync the exact join cardinality of a staged step: two int32
    scalars, read by the executor to size the gather.  The executor also
    reads each step's input cardinalities, and the session the
    cardinalities of the base relations."""
    hi, lo = to_host("total", (staged.total_hi, staged.total_lo))
    return (int(hi) << 30) + int(lo)


def bucket_capacity(total: int) -> int:
    """Static materialization capacity for an exact row total: the next
    power of two (>= 64).  Log-bucketing the shape (same idea as
    ``sketches.card_bucket``) means refreshed executions at a similar
    scale hit the SAME compiled gather — at most 2x buffer slack."""
    return max(64, 1 << math.ceil(math.log2(int(total) + 8)))


def _gather_core(sorted_build: Relation, lo: jnp.ndarray, cnt: jnp.ndarray,
                 probe: Relation, *, out_capacity: int,
                 build_prefix: str = "", probe_prefix: str = "") -> Relation:
    """Stage 2: prefix-sum offsets + gather-materialize (one dispatch).
    ``out_capacity`` must cover the staged total (int32 offsets)."""
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt)])
    total = off[-1]
    slots = jnp.arange(out_capacity, dtype=jnp.int32)
    owner = jnp.searchsorted(off, slots, side="right").astype(jnp.int32) - 1
    owner = jnp.clip(owner, 0, probe.capacity - 1)
    rank = slots - off[owner]
    bidx = jnp.clip(lo[owner] + rank, 0, sorted_build.capacity - 1)
    ok = slots < total
    cols = {}
    for name, col in sorted_build.columns.items():
        cols[build_prefix + name] = jnp.where(ok, col[bidx],
                                              jnp.int32(SENTINEL))
    for name, col in probe.columns.items():
        key = probe_prefix + name
        if key in cols:  # join column appears once
            continue
        cols[key] = jnp.where(ok, col[owner], jnp.int32(SENTINEL))
    return Relation(cols, ok)


# the staged buffers are consumed here; donating them lets XLA reuse the
# sorted-build storage for the materialized output
_gather_jit = jax.jit(_gather_core,
                      static_argnames=("out_capacity", "build_prefix",
                                       "probe_prefix"),
                      donate_argnums=(0, 1, 2))


def gather_staged(staged: StagedJoin, probe: Relation, out_capacity: int,
                  *, build_prefix: str = "",
                  probe_prefix: str = "") -> Relation:
    """Finish a staged materialize: one jitted dispatch that donates (and
    so deletes) the staged buffers — ``staged`` is dead afterwards.  XLA
    reuses only buffers shaped like an output (a chained pow2
    intermediate); the rest are freed with ``staged``, so the lowering's
    "not usable" warning is expected and silenced."""
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", "Some donated buffers were not "
                                "usable")
        return _gather_jit(
            staged.sorted_build, staged.lo, staged.cnt, probe,
            out_capacity=out_capacity, build_prefix=build_prefix,
            probe_prefix=probe_prefix)


# --------------------------------------------------------------------------
# cascaded binary baseline:  (R ⋈ S) materialized, then ⋈ T aggregated
# --------------------------------------------------------------------------

class CascadeResult(NamedTuple):
    count: jnp.ndarray          # total 3-way join cardinality (aggregated)
    intermediate_total: jnp.ndarray
    intermediate_overflowed: jnp.ndarray


def cascaded_binary_count(r: Relation, s: Relation, t: Relation,
                          intermediate_capacity: int,
                          rb: str = "b", sb: str = "b", sc: str = "c",
                          tc: str = "c") -> CascadeResult:
    """COUNT(R(AB) ⋈ S(BC) ⋈ T(CD)) as two cascaded binary joins with a
    bounded, materialized intermediate (the paper's baseline plan)."""
    inter = join_materialize(r, rb, s, sb, intermediate_capacity,
                             build_prefix="r_", probe_prefix="s_")
    # second join: aggregate only (final output never materialized, §6)
    w = probe_weight_sum(t, tc, jnp.ones((t.capacity,), jnp.int32),
                         inter.rel.col("s_" + sc), inter.rel.valid)
    return CascadeResult(jnp.sum(w).astype(jnp.int32), inter.total,
                         inter.overflowed)


def cascaded_binary_per_r_counts(r: Relation, s: Relation, t: Relation,
                                 rb: str = "b", sb: str = "b", sc: str = "c",
                                 tc: str = "c") -> jnp.ndarray:
    """Per-R-row 3-way join counts via weight backflow (no materialization).

    w_s = |{t : t.c == s.c}| ;  count_r = Σ_{s : s.b == r.b} w_s.
    Exact; used as the oracle for the per-key (Example 1) aggregate.
    """
    w_s = probe_weight_sum(t, tc, jnp.ones((t.capacity,), jnp.int32),
                           s.col(sc), s.valid)
    c_r = probe_weight_sum(s, sb, w_s, r.col(rb), r.valid)
    return c_r


# --------------------------------------------------------------------------
# bucketed path (accelerator-shaped)
# --------------------------------------------------------------------------

def bucketed_join_count(build: Relation, build_key: str,
                        probe: Relation, probe_key: str,
                        n_buckets: int, build_cap: int, probe_cap: int,
                        use_kernel: bool = False):
    """Hash-partition both sides and count matches per bucket pair.

    Returns (count, overflowed).  Matching keys hash identically, so
    bucket-local exact compares lose nothing (completeness), and cross-bucket
    pairs can never match (soundness) — exactness holds unless a bucket
    overflows, which is reported.
    """
    from repro.kernels import ops as kops

    b = partition.bucketize(build, build_key, n_buckets, build_cap, fn="h")
    p = partition.bucketize(probe, probe_key, n_buckets, probe_cap, fn="h")
    counts = kops.bucket_pair_count(
        b.columns[build_key], b.valid, p.columns[probe_key], p.valid,
        use_kernel=use_kernel)
    return jnp.sum(counts), b.overflowed | p.overflowed
