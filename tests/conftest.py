"""Shared fixtures: python set/dict join oracles + relation generators.

The oracles are deliberately naive (dict-of-lists nested loops) — they are
the ground truth every JAX/Pallas path is checked against.
"""

from __future__ import annotations

import dataclasses
import glob
import pathlib
import sys
import warnings
from collections import Counter, defaultdict

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

# Hermetic images may lack hypothesis (a dev dependency); fall back to the
# bundled deterministic shim so property tests still collect and run.  This
# must happen in conftest, before pytest imports any test module.
try:
    import hypothesis  # noqa: F401
except ImportError:
    import _hypothesis_shim
    sys.modules["hypothesis"] = _hypothesis_shim
    sys.modules["hypothesis.strategies"] = _hypothesis_shim.strategies

from repro.core.relation import Relation  # noqa: E402


# --------------------------------------------------------------------------
# data generators
# --------------------------------------------------------------------------

def skewed_keys(rng: np.random.Generator, n: int, d: int, frac: float,
                heavy: int = 1) -> np.ndarray:
    """Adversarial keys: a heavy hitter owning ``frac`` of all rows (a
    single hash bucket must absorb it — no salt can spread one key); the
    remaining rows are uniform over [0, d)."""
    n_heavy = int(n * frac)
    vals = np.concatenate([
        np.full(n_heavy, heavy, np.int32),
        rng.integers(0, d, size=n - n_heavy).astype(np.int32)])
    rng.shuffle(vals)
    return vals


def make_rel(rng: np.random.Generator, n: int, cols: tuple[str, ...],
             d: int, cap_extra: int = 0, zipf: float | None = None):
    """Random relation; returns (Relation, dict of raw numpy columns)."""
    data = {}
    for c in cols:
        if zipf is None:
            data[c] = rng.integers(0, d, size=n).astype(np.int32)
        else:
            v = rng.zipf(zipf, size=n)
            data[c] = (np.minimum(v, d) - 1).astype(np.int32)
    rel = Relation.from_arrays(capacity=n + cap_extra, **data)
    return rel, data


# --------------------------------------------------------------------------
# oracles
# --------------------------------------------------------------------------

def oracle_pair_count(a_keys, b_keys) -> int:
    ca = Counter(a_keys.tolist())
    return sum(ca.get(k, 0) for k in b_keys.tolist())


def oracle_linear3_count(rb, sb, sc, tc) -> int:
    ct = Counter(tc.tolist())
    w = np.array([ct.get(c, 0) for c in sc.tolist()], dtype=np.int64)
    cs = defaultdict(int)
    for b, wi in zip(sb.tolist(), w.tolist()):
        cs[b] += wi
    return int(sum(cs.get(b, 0) for b in rb.tolist()))


def oracle_linear3_per_r(rb, sb, sc, tc) -> np.ndarray:
    ct = Counter(tc.tolist())
    w = np.array([ct.get(c, 0) for c in sc.tolist()], dtype=np.int64)
    cs = defaultdict(int)
    for b, wi in zip(sb.tolist(), w.tolist()):
        cs[b] += wi
    return np.array([cs.get(b, 0) for b in rb.tolist()], dtype=np.int64)


def oracle_cyclic3_count(ra, rb, sb, sc, tc, ta) -> int:
    s_by_b = defaultdict(list)
    for b, c in zip(sb.tolist(), sc.tolist()):
        s_by_b[b].append(c)
    t_by_ca = Counter(zip(tc.tolist(), ta.tolist()))
    total = 0
    for a, b in zip(ra.tolist(), rb.tolist()):
        for c in s_by_b.get(b, ()):
            total += t_by_ca.get((c, a), 0)
    return total


def oracle_distinct_join_pairs(rb, ra, sb, sc, tc, td) -> int:
    """|distinct (a, d) pairs in the linear 3-way join output|."""
    s_by_b = defaultdict(set)
    for b, c in zip(sb.tolist(), sc.tolist()):
        s_by_b[b].add(c)
    t_by_c = defaultdict(set)
    for c, dv in zip(tc.tolist(), td.tolist()):
        t_by_c[c].add(dv)
    pairs = set()
    for a, b in zip(ra.tolist(), rb.tolist()):
        for c in s_by_b.get(b, ()):
            for dv in t_by_c.get(c, ()):
                pairs.add((a, dv))
    return len(pairs)


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


# --------------------------------------------------------------------------
# program spans from a recorded profiler trace
# --------------------------------------------------------------------------

@dataclasses.dataclass
class RecordedSpan:
    name: str
    start: int               # ns, the trace's host clock
    end: int
    args: dict


def read_spans(log_dir) -> list[RecordedSpan]:
    """The ``repro.`` spans of the newest trace under ``log_dir``, in start
    order (outer before inner at equal starts)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    with warnings.catch_warnings():
        # event stats are a builtin type without __module__ (jax 0.9)
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        s = int(e.start_ns)
                        out.append(RecordedSpan(e.name, s,
                                                s + int(e.duration_ns),
                                                dict(e.stats)))
    return sorted(out, key=lambda sp: (sp.start, -sp.end))


@pytest.fixture
def record_spans(tmp_path):
    """``record_spans(fn)`` runs ``fn()`` under the profiler and returns
    its result and the ``repro.`` spans recorded meanwhile."""
    import jax

    def record(fn):
        log_dir = tmp_path / f"trace{len(list(tmp_path.iterdir()))}"
        jax.profiler.start_trace(str(log_dir))
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        return out, read_spans(log_dir)
    return record
