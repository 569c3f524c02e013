"""Plain reference for COUNT(*) over an acyclic equality-join graph.

Numpy only; shares no code with the engine.  The join tree is rooted at the
first alias; each row's weight is the product, over its child aliases, of
the summed weights of the child rows that carry the same join key, and the
COUNT is the sum of the root's row weights.  That equals the number of
tuples in the full join, computed in int64 and returned as a Python int.

Controls (each breaks the exactness the configurations state, and so has to
fail the benchmark's exact comparison):

* ``float32`` - the same sums accumulated in float32, exact only up to 2^24;
* ``pow2_rows`` - every input cut to its largest power-of-two prefix of rows,
  as an intermediate sized one log-bucket short would lose rows.
"""

from __future__ import annotations

import numpy as np


def _children(aliases: list[str], predicates: list[tuple[str, str]]):
    """Parent -> [(child, parent column, child column)] for the tree rooted
    at ``aliases[0]``."""
    adj: dict[str, list[tuple[str, str, str]]] = {a: [] for a in aliases}
    for left, right in predicates:
        la, lc = left.split(".")
        ra, rc = right.split(".")
        adj[la].append((ra, lc, rc))
        adj[ra].append((la, rc, lc))
    tree: dict[str, list[tuple[str, str, str]]] = {a: [] for a in aliases}
    seen, stack = {aliases[0]}, [aliases[0]]
    while stack:
        a = stack.pop()
        for b, ac, bc in adj[a]:
            if b not in seen:
                seen.add(b)
                tree[a].append((b, ac, bc))
                stack.append(b)
    if len(seen) != len(aliases) or len(predicates) != len(aliases) - 1:
        raise ValueError("the reference counts connected acyclic queries only")
    return tree


def _summed_by_key(keys: np.ndarray, weights: np.ndarray,
                   probe: np.ndarray) -> np.ndarray:
    """For each probe key, the sum of ``weights`` over rows whose key equals
    it (sort + prefix sums + binary search)."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    csum = np.concatenate([np.zeros(1, weights.dtype),
                           np.cumsum(weights[order], dtype=weights.dtype)])
    lo = np.searchsorted(sk, probe, side="left")
    hi = np.searchsorted(sk, probe, side="right")
    return csum[hi] - csum[lo]


def count(tables: dict[str, dict[str, np.ndarray]], query: dict,
          dtype=np.int64) -> int:
    """COUNT(*) of ``query`` (``{"relations": {alias: table},
    "predicates": [["a.col", "b.col"], ...]}``) over ``tables``."""
    aliases = list(query["relations"])
    tree = _children(aliases, [tuple(p) for p in query["predicates"]])

    def cols(alias):
        return tables[query["relations"][alias]]

    def weights(alias):
        t = cols(alias)
        n = len(next(iter(t.values())))
        w = np.ones(n, dtype)
        for child, pcol, ccol in tree[alias]:
            w = w * _summed_by_key(cols(child)[ccol], weights(child),
                                   t[pcol])
        return w

    total = np.sum(weights(aliases[0]), dtype=dtype)
    return int(total) if dtype == np.int64 else int(np.rint(total))


def control(tables: dict[str, dict[str, np.ndarray]], query: dict,
            kind: str) -> int:
    """The reference with the configuration's guarantee broken (``kind``
    as in the module docstring)."""
    if kind == "float32":
        return count(tables, query, dtype=np.float32)
    if kind == "pow2_rows":
        cut = {}
        for name, t in tables.items():
            n = len(next(iter(t.values())))
            keep = 1 << (n.bit_length() - 1) if n else 0
            cut[name] = {c: v[:keep] for c, v in t.items()}
        return count(cut, query)
    raise ValueError(f"unknown control {kind!r}")
