"""Fixed-capacity, validity-masked relations (struct-of-arrays).

JAX requires static shapes, and the paper's algorithms never materialize the
final join output (aggregates are folded on the fly, §6).  A Relation is a
dict of equal-length int32 column arrays plus a boolean validity mask; the
capacity is static, the live count `n` is dynamic.  All core algorithms
consume and produce Relations (or aggregates).

Ingest is explicit: :meth:`Relation.append` is the ONE mutation point.  It
compacts live rows, grows capacity along log-bucketed (power-of-two) steps
so refreshed executions keep hitting the same compiled shapes, updates any
cached FM sketches incrementally (sketch insertion is a monotone bitwise
OR, so the incremental update equals a rebuild), bumps a version counter
that cache-like layers key resident state on, and notifies registered
append observers (``on_append``) with the delta — that notification is what
drives :class:`~repro.core.streaming.StandingQuery` delta execution.
Outside ``append`` the instance is immutable: the dataclass is frozen and
``columns`` is a read-only mapping view, so direct array mutation after
construction raises.
"""

from __future__ import annotations

import dataclasses
import types
from typing import Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core.spans import to_host


# The canonical padding sentinel for invalid relation slots.  Every layer
# that fills dead slots (``sentinel_fill``, ``partition.bucketize``,
# ``partition.bucketize_by_ids``) uses THIS constant; the per-side probe
# sentinels in ``kernels.ops`` are derived from it (SENTINEL + 15 + side)
# so no sentinel of any kind can ever equal a live key (keys are ≥ -2^30
# by the data-layer contract) or a sentinel from another side.
SENTINEL = -0x7FFFFFFF


def _log_bucket_capacity(need: int) -> int:
    """Next power-of-two capacity ≥ need (min 64) — the same log-bucketing
    rule as ``binary_join.bucket_capacity``, inlined to keep this module at
    the bottom of the import graph.  Appends that stay within the bucket
    reuse every compiled shape; only a bucket step re-jits."""
    return max(64, 1 << max(0, int(need) - 1).bit_length())


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Relation:
    """Columnar relation with static capacity and a validity mask."""

    columns: Mapping[str, jnp.ndarray]  # each (capacity,) int32
    valid: jnp.ndarray                  # (capacity,) bool

    def __post_init__(self):
        # direct mutation after construction must raise: freeze the column
        # mapping behind a read-only view (the arrays themselves are
        # immutable jax arrays) — ``append`` is the one sanctioned mutator
        if not isinstance(self.columns, types.MappingProxyType):
            object.__setattr__(self, "columns",
                               types.MappingProxyType(dict(self.columns)))

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return tuple(self.columns[n] for n in names) + (self.valid,), names

    @classmethod
    def tree_unflatten(cls, names, leaves):
        *cols, valid = leaves
        return cls(columns=dict(zip(names, cols)), valid=valid)

    # -- introspection -------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.valid.shape[0]

    @property
    def n(self) -> jnp.ndarray:
        """Dynamic number of live tuples."""
        return jnp.sum(self.valid.astype(jnp.int32))

    @property
    def version(self) -> int:
        """Ingest version: bumped by every ``append``.  Cache-like layers
        (the standing-query resident intermediates, service snapshots) key
        the validity of derived state on this counter."""
        return self.__dict__.get("_version", 0)

    def col(self, name: str) -> jnp.ndarray:
        return self.columns[name]

    # -- distinct-count sketches ---------------------------------------------
    def distinct_sketch(self, col: str) -> jnp.ndarray:
        """The column's FM/PCSA register bitmaps (``core.sketches``),
        built on first use and cached on the instance.  ``append`` updates
        the cached sketch incrementally (FM insertion is a bitwise OR, so
        the incremental update is exactly the rebuild), which is what lets
        the planner estimate distinct counts without a host scan even
        under continuous ingest; derived relations (``select``/
        ``mask_where``/pytree reconstruction) start with an empty cache."""
        cache = self.__dict__.get("_sketch_cache")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_sketch_cache", cache)
        sk = cache.get(col)
        if sk is None:
            from repro.core import sketches
            sk = sketches.add(sketches.empty(), self.columns[col],
                              self.valid)
            cache[col] = sk
        return sk

    def distinct_estimate(self, col: str) -> int:
        """FM-sketch distinct-count estimate of a column (>= 1), clipped
        to the column's capacity.  The planner's scan-free replacement
        for host ``np.unique`` passes."""
        from repro.core import sketches
        est = int(round(float(to_host("sketch", sketches.fm_estimate(
            self.distinct_sketch(col))))))
        return max(1, min(est, self.capacity))

    # -- ingest --------------------------------------------------------------
    def on_append(self, callback: Callable) -> None:
        """Register ``callback(relation, delta)`` to run after every
        ``append`` (the standing-query ingest hook)."""
        self.__dict__.setdefault("_observers", []).append(callback)

    def remove_on_append(self, callback: Callable) -> None:
        obs = self.__dict__.get("_observers")
        if obs and callback in obs:
            obs.remove(callback)

    def append(self, cols: Mapping[str, jnp.ndarray] | None = None,
               **col_arrays) -> "Relation":
        """THE ingest mutation point: append a batch of rows in place.

        ``cols`` (or keyword arrays) must cover exactly this relation's
        schema with equal-length arrays.  Live rows are compacted to a
        prefix, capacity grows along power-of-two buckets (so steady
        deltas keep hitting the same compiled shapes), cached FM sketches
        update incrementally, the :attr:`version` counter bumps, and
        ``on_append`` observers fire with the delta — which is what drives
        standing-query delta execution.  Returns the delta as a fresh
        Relation.
        """
        arrs = dict(cols or {})
        arrs.update(col_arrays)
        if set(arrs) != set(self.columns):
            raise ValueError(
                f"append schema mismatch: got {sorted(arrs)}, relation has "
                f"{sorted(self.columns)}")
        arrs = {k: jnp.asarray(v, dtype=jnp.int32) for k, v in arrs.items()}
        lens = {a.shape[0] for a in arrs.values()}
        if len(lens) != 1:
            raise ValueError(f"ragged delta columns: "
                             f"{ {k: v.shape for k, v in arrs.items()} }")
        (k,) = lens
        delta = Relation.from_arrays(**arrs)
        if k == 0:
            return delta
        n0 = int(self.n)
        need = n0 + k
        cap = self.capacity
        new_cap = cap if need <= cap else _log_bucket_capacity(need)
        # compact live rows to a prefix (stable: live order preserved),
        # then write the delta at [n0, n0+k)
        order = jnp.argsort(jnp.where(self.valid, 0, 1).astype(jnp.int32),
                            stable=True)
        pad = new_cap - cap
        new_cols = {}
        for name, col in self.columns.items():
            base = col[order]
            if pad:
                base = jnp.pad(base, (0, pad))
            new_cols[name] = base.at[n0:need].set(arrs[name])
        valid = jnp.arange(new_cap) < need
        object.__setattr__(self, "columns",
                           types.MappingProxyType(new_cols))
        object.__setattr__(self, "valid", valid)
        object.__setattr__(self, "_version", self.version + 1)
        cache = self.__dict__.get("_sketch_cache")
        if cache:
            from repro.core import sketches
            ones = jnp.ones((k,), bool)
            for name, sk in list(cache.items()):
                cache[name] = sketches.add(sk, arrs[name], ones)
        for cb in tuple(self.__dict__.get("_observers", ())):
            cb(self, delta)
        return delta

    # -- construction --------------------------------------------------------
    @classmethod
    def from_arrays(cls, capacity: int | None = None, **cols) -> "Relation":
        """Build from equal-length arrays, optionally padding to `capacity`."""
        arrs = {k: jnp.asarray(v, dtype=jnp.int32) for k, v in cols.items()}
        lens = {a.shape[0] for a in arrs.values()}
        if len(lens) != 1:
            raise ValueError(f"ragged columns: {dict((k, v.shape) for k, v in arrs.items())}")
        (n,) = lens
        cap = capacity or n
        if cap < n:
            raise ValueError(f"capacity {cap} < rows {n}")
        pad = cap - n
        if pad:
            arrs = {k: jnp.pad(a, (0, pad)) for k, a in arrs.items()}
        valid = jnp.arange(cap) < n
        return cls(columns=arrs, valid=valid)

    def select(self, idx: jnp.ndarray, idx_valid: jnp.ndarray) -> "Relation":
        """Gather rows by index (row validity AND idx_valid)."""
        cols = {k: v[idx] for k, v in self.columns.items()}
        return Relation(cols, self.valid[idx] & idx_valid)

    def with_columns(self, **cols) -> "Relation":
        new = dict(self.columns)
        new.update({k: jnp.asarray(v, jnp.int32) for k, v in cols.items()})
        return Relation(new, self.valid)

    def mask_where(self, keep: jnp.ndarray) -> "Relation":
        return Relation(dict(self.columns), self.valid & keep)


def live_rows(*rels: Relation) -> tuple[int, ...]:
    """Live rows of each relation, read to the host in one sync."""
    return tuple(map(int, to_host("rows", tuple(r.n for r in rels))))


def sentinel_fill(rel: Relation, sentinel: int = SENTINEL) -> Relation:
    """Overwrite invalid rows' columns with a sentinel that never equals a
    live key, so masked compare loops need no extra predicate."""
    cols = {
        k: jnp.where(rel.valid, v, jnp.int32(sentinel))
        for k, v in rel.columns.items()
    }
    return Relation(cols, rel.valid)
