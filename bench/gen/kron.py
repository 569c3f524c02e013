"""Graph500 Kronecker graph, symmetrized as the GAP Benchmark Suite's ``kron``.

Edges follow the Graph500 reference generator (``kronecker_generator.m``):
each of ``edge_factor * 2**scale`` edges picks one quadrant per level with
probabilities (A, B, C, D), vertex labels are permuted at random, and the
edge list is shuffled.  GAP (arXiv:1508.03619) then symmetrizes the graph
and drops self-loops and duplicate edges; so does :func:`make`.

One relation, ``edges(src, dst)``: each undirected edge appears once in
each direction, so in-degree equals out-degree.  ``symmetrize`` fixes a
row order; :func:`make` permutes it.
"""

from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, rng: np.random.Generator) -> np.ndarray:
    """The raw (2, m) Kronecker edge list, before symmetrizing."""
    n = 1 << scale
    m = edge_factor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), np.int64)
    for level in range(scale):
        i_bit = rng.random(m) > ab
        j_bit = rng.random(m) > np.where(i_bit, c_norm, a_norm)
        ij[0] += i_bit.astype(np.int64) << level
        ij[1] += j_bit.astype(np.int64) << level
    perm = rng.permutation(n)
    ij = perm[ij]
    return ij[:, rng.permutation(m)]


def symmetrize(ij: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Both directions of every edge, without self-loops or duplicates, in
    a random-looking but fixed order (sorted by a multiplicative hash)."""
    u = np.concatenate([ij[0], ij[1]])
    v = np.concatenate([ij[1], ij[0]])
    keep = u != v
    code = np.unique(u[keep] * n + v[keep])
    mixed = (code * 0x9E3779B1) & 0xFFFFFFFF
    code = code[np.argsort(mixed, kind="stable")]
    return (code // n).astype(np.int32), (code % n).astype(np.int32)


def make(cfg: dict, rng: np.random.Generator) -> dict[str, dict[str, np.ndarray]]:
    """``{"edges": {"src": ..., "dst": ...}}``: the graph of
    ``cfg["graph_seed"]``, its rows in an order drawn from ``rng``.

    Every run seed gets the same graph in another row order, so every seed
    does the same work: a graph of another seed has other hubs, hence other
    recovery-round capacities, other compiled programs and a query time
    that differs by several percent."""
    ij = kronecker_edges(cfg["scale"], cfg["edge_factor"], cfg["A"],
                         cfg["B"], cfg["C"],
                         np.random.default_rng(cfg["graph_seed"]))
    src, dst = symmetrize(ij, 1 << cfg["scale"])
    order = rng.permutation(len(src))
    return {"edges": {"src": src[order], "dst": dst[order]}}
