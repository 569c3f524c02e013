"""Radix hash partitioning (the paper's Fig 2 / Fig 3 data reorganization).

Two static-shape-friendly layouts are provided:

* ``partition_sorted`` — relation sorted by bucket id plus a CSR-style offsets
  array.  This mirrors the paper's partition files ("S_ij partitions are
  ordered first on H(B) and then on g(C)"): composite partitioning is just a
  lexicographic sort on (outer, inner) bucket ids.

* ``bucketize`` — fixed-capacity `[n_buckets, capacity]` grid with per-bucket
  counts and an overflow indicator.  This is the on-chip layout: bucket i is
  the contents of PMU i (or one VMEM tile in the Pallas kernels).  Overflow
  (a bucket exceeding its capacity) is the skew signal; callers either size
  capacity with slack (uniform assumption, §1.2) or re-partition with a salt.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.relation import SENTINEL, Relation

_INT32_MAX = 2**31 - 1


def _check_flat_range(n_slots: int, what: str) -> None:
    """Flat bucket/slot ids are int32 throughout; a silent wrap would scatter
    rows into the wrong buckets.  Fail loudly instead."""
    if n_slots > _INT32_MAX:
        raise ValueError(
            f"{what} = {n_slots} exceeds the int32 id range ({_INT32_MAX}); "
            "use fewer/coarser bucket levels or smaller capacities")


class SortedPartition(NamedTuple):
    rel: Relation            # rows sorted by bucket id (invalid rows last)
    bucket_ids: jnp.ndarray  # (capacity,) int32, n_buckets for invalid rows
    offsets: jnp.ndarray     # (n_buckets + 1,) int32 CSR offsets


class Buckets(NamedTuple):
    columns: dict            # name -> (n_buckets, capacity) int32, sentinel-padded
    valid: jnp.ndarray       # (n_buckets, capacity) bool
    counts: jnp.ndarray      # (n_buckets,) int32 true per-bucket count (pre-clip)
    overflowed: jnp.ndarray  # () bool — any bucket exceeded capacity


def bucket_ids_for(rel: Relation, key_col: str, n_buckets: int, fn: str,
                   salt: int = 0) -> jnp.ndarray:
    """Bucket id per row; invalid rows get id == n_buckets (sorts last)."""
    ids = hashing.hash_bucket(rel.col(key_col), n_buckets, fn, salt)
    return jnp.where(rel.valid, ids, jnp.int32(n_buckets))


def partition_sorted(rel: Relation, key_col: str, n_buckets: int, fn: str = "H",
                     salt: int = 0) -> SortedPartition:
    ids = bucket_ids_for(rel, key_col, n_buckets, fn, salt)
    order = jnp.argsort(ids, stable=True)
    sorted_rel = rel.select(order, jnp.ones_like(order, dtype=bool))
    sorted_ids = ids[order]
    offsets = jnp.searchsorted(sorted_ids, jnp.arange(n_buckets + 1), side="left")
    return SortedPartition(sorted_rel, sorted_ids, offsets.astype(jnp.int32))


def partition_sorted2(rel: Relation, outer_col: str, inner_col: str,
                      n_outer: int, n_inner: int, outer_fn: str = "H",
                      inner_fn: str = "g") -> SortedPartition:
    """Composite two-level partitioning: sort by (outer, inner) bucket pair.

    Bucket id = outer * n_inner + inner, matching the paper's S layout
    (ordered by H(B), then by g(C) within each H(B) partition).
    """
    outer = bucket_ids_for(rel, outer_col, n_outer, outer_fn)
    inner = bucket_ids_for(rel, inner_col, n_inner, inner_fn)
    flat = jnp.where(rel.valid, outer * n_inner + inner,
                     jnp.int32(n_outer * n_inner))
    order = jnp.argsort(flat, stable=True)
    sorted_rel = rel.select(order, jnp.ones_like(order, dtype=bool))
    sorted_ids = flat[order]
    offsets = jnp.searchsorted(
        sorted_ids, jnp.arange(n_outer * n_inner + 1), side="left")
    return SortedPartition(sorted_rel, sorted_ids, offsets.astype(jnp.int32))


def bucketize(rel: Relation, key_col: str, n_buckets: int, capacity: int,
              fn: str = "h", salt: int = 0,
              sentinel: int = SENTINEL) -> Buckets:
    """Scatter rows into a fixed [n_buckets, capacity] grid by the hash of
    ``key_col`` (the one-level :func:`bucketize_by_ids`).

    Rows beyond a bucket's capacity are dropped and flagged via
    ``overflowed`` — the caller must re-partition (bigger capacity or new
    salt).
    """
    ids = _bucket_ids(rel, key_col, n_buckets, fn, salt)
    return bucketize_by_ids(rel, ids, n_buckets, capacity, (n_buckets,),
                            sentinel)


_bucket_ids = jax.jit(bucket_ids_for,
                      static_argnames=("key_col", "n_buckets", "fn", "salt"))


@jax.jit
def stable_order(keys: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stable ascending argsort of ``keys`` and the sorted keys.

    Its own program, keyed only by the key array's shape: a TPU sort of
    ~10^5 keys or more takes 15-20 s to compile, so every layout, recovery
    round and binary step over relations of one capacity shares this one
    compiled sort, and the programs around it compile in about a second.
    """
    order = jnp.argsort(keys, stable=True)
    return order, keys[order]


def bucketize_by_ids(rel: Relation, flat_ids: jnp.ndarray, n_buckets: int,
                     capacity: int, out_shape: tuple,
                     sentinel: int = SENTINEL) -> Buckets:
    """Lay rows out as `[*out_shape, capacity]` by precomputed flat bucket
    ids (invalid rows must carry id == n_buckets).  Generic engine behind the
    composite two/three-level layouts of Fig 2/3.  Implementation: a stable
    sort groups each bucket's rows in arrival order, then slot j of bucket b
    gathers the bucket's j-th row; O(n log n), no dynamic shapes."""
    _check_flat_range(n_buckets * capacity + 1, "n_buckets * capacity")
    order, sorted_ids = stable_order(flat_ids)
    return _gather_buckets(rel, order, sorted_ids, n_buckets=n_buckets,
                           capacity=capacity, out_shape=tuple(out_shape),
                           sentinel=sentinel)


@functools.partial(jax.jit, static_argnames=("n_buckets", "capacity",
                                             "out_shape", "sentinel"))
def _gather_buckets(rel: Relation, order: jnp.ndarray,
                    sorted_ids: jnp.ndarray, *, n_buckets: int,
                    capacity: int, out_shape: tuple,
                    sentinel: int) -> Buckets:
    # a gather, not a scatter: a TPU scatter of a bool plane alone takes
    # ~10 s to compile, the gather well under one
    starts = jnp.searchsorted(sorted_ids, jnp.arange(n_buckets + 1),
                              side="left").astype(jnp.int32)
    counts = starts[1:] - starts[:-1]
    overflowed = jnp.any(counts > capacity)
    slot = jnp.arange(capacity, dtype=jnp.int32)
    valid = slot[None, :] < counts[:, None]          # rows beyond cap drop
    rows = order[jnp.clip(starts[:-1, None] + slot[None, :], 0,
                          sorted_ids.shape[0] - 1)]
    shape = (*out_shape, capacity)
    cols = {name: jnp.where(valid, col[rows], jnp.int32(sentinel)).reshape(shape)
            for name, col in rel.columns.items()}
    return Buckets(cols, valid.reshape(shape), counts.reshape(out_shape),
                   overflowed)


def composite_ids(rel: Relation, specs: list[tuple[str, int, str]],
                  salt: int = 0) -> tuple[jnp.ndarray, int]:
    """Flat composite bucket id from [(column, n_buckets, hash_fn), ...],
    most-significant first.  Invalid rows get id == prod(n_buckets).
    ``salt`` re-randomizes every level (skew-recovery re-partitioning).

    Raises ``ValueError`` when ``prod(n_buckets)`` exceeds the int32 id
    range: ``flat`` accumulates in int32, so a deeper/wider spec (e.g. the
    cyclic four-level layout on a huge plan) would otherwise wrap silently
    and scatter rows into wrong buckets.
    """
    specs = tuple(tuple(spec) for spec in specs)
    total = 1
    for _col, nb, _fn in specs:
        total *= nb
    _check_flat_range(total, f"prod(n_buckets) for specs {specs!r}")
    return _composite_ids(rel, specs, salt, total), total


@functools.partial(jax.jit, static_argnames=("specs", "salt", "total"))
def _composite_ids(rel: Relation, specs, salt: int, total: int):
    flat = jnp.zeros((rel.capacity,), jnp.int32)
    for col, nb, fn in specs:
        ids = bucket_ids_for(rel, col, nb, fn, salt)
        flat = flat * nb + jnp.clip(ids, 0, nb - 1)
    return jnp.where(rel.valid, flat, jnp.int32(total))


def suggest_capacity(n_rows: int, n_buckets: int, slack: float = 2.0,
                     align: int = 8) -> int:
    """Uniform-hash bucket capacity with slack, aligned for TPU lanes."""
    import math

    mean = max(1, math.ceil(n_rows / n_buckets))
    # Poisson tail headroom: mean + slack * sqrt(mean) at minimum.
    cap = max(int(mean * slack), mean + int(slack * math.sqrt(mean)) + 1)
    return int(math.ceil(cap / align) * align)


def key_order(rel: Relation, key_col: str,
              big: int = 0x7FFFFFFF) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stable row order of ``rel`` by the *actual* key (invalid rows last,
    as the ``big`` sentinel) and the sorted keys, via :func:`stable_order`."""
    return stable_order(jnp.where(rel.valid, rel.col(key_col),
                                  jnp.int32(big)))


def sort_by_key(rel: Relation, key_col: str,
                big: int = 0x7FFFFFFF) -> tuple[Relation, jnp.ndarray]:
    """Sort rows by the *actual* key (invalid rows last).  Returns the sorted
    relation and the sorted key array (invalid = big sentinel) for
    searchsorted probes — the exact-join building block."""
    order, skeys = key_order(rel, key_col, big)
    return rel.select(order, jnp.ones_like(order, dtype=bool)), skeys
