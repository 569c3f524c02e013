"""The fused join kernels and the radix histogram compile for a TPU v5e.

Each case lowers a kernel with ``interpret=False`` at the widths the plans
of ``chip_smoke.py`` give (linear 3-way over 1.02M edges at m_budget =
rows / 64, the triangle query over 200k edges at m_budget 4096, the star
root over a 6.25M-row intermediate and 37.5k-row dimensions) for a
described, not attached, v5e chip, and checks
that the compiled HLO holds the Mosaic kernel (``tpu_custom_call``).  What
Mosaic refuses here (block shapes off the (8, 128) tiling, scoped VMEM) it
would refuse on the chip.  A compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest-xdist worker imports
this file.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import cyclic3, linear3, star3
from repro.kernels import bucket_join, radix_hist


def _lanes(c):
    return -(-c // 128) * 128


def _tiles(n):
    return -(-n // bucket_join.TILE) * bucket_join.TILE


def _linear_shapes():
    n = 1_017_518
    p = linear3.default_plan(n, n, n, m_budget=n // 64)
    cr, cs, ct = _lanes(p.r_cap), _lanes(p.s_cap), _lanes(p.t_cap)
    u = _tiles(p.u)
    return [(p.h_parts, u, cr), (p.h_parts, p.g_parts, u, cs),
            (p.h_parts, p.g_parts, u, cs), (p.g_parts, ct)]


def _cyclic_shapes():
    n = 200_000
    p = cyclic3.default_plan(n, n, n, m_budget=4096)
    cr, cs, ct = _lanes(p.r_cap), _lanes(p.s_cap), _lanes(p.t_cap)
    hp, gp, uh, ug, fp = p.h_parts, p.g_parts, p.uh, _tiles(p.ug), p.f_parts
    return [(hp, gp, uh, ug, cr)] * 2 + [(gp, fp, ug, cs)] * 2 + \
        [(hp, fp, uh, ct)] * 2


def _star_shapes():
    p = star3.default_plan(37_500, 6_250_000, 37_500)
    cr, cs, ct = _lanes(p.r_cap), _lanes(p.s_cap), _lanes(p.t_cap)
    ug = _tiles(p.ug)
    return [(p.uh, cr), (p.chunks, p.uh, ug, cs), (p.chunks, p.uh, ug, cs),
            (ug, ct)]


CASES = {
    "fused_count3_linear": (bucket_join.fused_count3_linear, _linear_shapes),
    "fused_per_r_counts": (bucket_join.fused_per_r_counts, _linear_shapes),
    "fused_count3_cyclic": (bucket_join.fused_count3_cyclic, _cyclic_shapes),
    "fused_count3_star": (bucket_join.fused_count3_star, _star_shapes),
    "radix_histogram": (
        functools.partial(radix_hist.radix_histogram, n_buckets=4096),
        lambda: [(16 << 20,)]),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described chip is written but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in shapes()]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the operands of the real plans are large: the kernel must stream them,
    # not stage a copy (no temp buffer of operand size)
    mem = compiled.memory_analysis()
    arg_bytes = sum(math.prod(s) * 4 for s in shapes())
    assert mem.temp_size_in_bytes < arg_bytes
