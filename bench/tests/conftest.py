"""CPU tests of the benchmark: generators, references, the trace reduction
and a rehearsal of every traffic loop through the served path.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

# Small sizes for the CPU: the shapes of the configurations, cut in scale.
TINY = {"scale": 9, "sf": 0.002, "m_budget": 512,
        "capacity": {"edges": 1 << 14}}


@pytest.fixture
def tiny_cell():
    import run as bench

    def make(name: str):
        cell = bench.load_cell(name)
        cell.config.update(TINY)
        return cell
    return make
