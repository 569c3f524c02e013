"""Flajolet–Martin / PCSA distinct-count sketches (paper Example 1).

The paper's headline query (count of friends-of-friends-of-friends per user)
cannot materialize its output; it folds an FM sketch on the fly and unions
sketches across workers.  Union is an elementwise bitwise OR of register
bitmaps — associative and commutative, so sketches combine across PMUs,
chips and pods with plain reductions.

Faithful FM/PCSA: K register bitmaps; each key sets bit ρ(hash_k(key))-1 in
bitmap k, where ρ is the position of the lowest set bit of the hash.
Estimate = 2^(mean_k R_k) / φ with R_k = index of the lowest ZERO bit of
bitmap k and φ ≈ 0.77351 (Flajolet–Martin 1985).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import hashing

PHI = 0.77351


def card_bucket(n: int, *, per_octave: int = 1) -> int:
    """Log-bucketed cardinality estimate for plan-cache keys.

    Plans are estimate-sized and recovery-correct, so the session cache
    keys on the *scale* of each relation rather than its exact row count:
    ``round(log2(n) * per_octave)``.  Small data drift (a ±5% refresh of
    a served relation, away from a bucket boundary) maps to the same
    bucket and HITS; a 4x resize always moves ≥ ``2 * per_octave``
    buckets and re-plans.  This is the cheap stand-in for keying on an
    FM-sketch cardinality estimate (same idea: a coarse, drift-stable
    summary instead of the exact count).
    """
    n = int(n)
    if n <= 0:
        return -1
    return int(round(math.log2(n) * per_octave))


def empty(n_registers: int = 32) -> jnp.ndarray:
    """Zeroed register bitmaps, one int32 per register."""
    return jnp.zeros((n_registers,), jnp.int32)


def key_bits(keys: jnp.ndarray, reg: int) -> jnp.ndarray:
    """The bitmap contribution 1 << (ρ(hash_reg(key)) - 1) per key."""
    rho = hashing.hash_trailing_zeros(keys, reg)   # in [1, 33]
    shift = jnp.minimum(rho - 1, 31).astype(jnp.uint32)
    return (jnp.uint32(1) << shift).astype(jnp.int32)


@jax.jit
def add(registers: jnp.ndarray, keys: jnp.ndarray,
        valid: jnp.ndarray) -> jnp.ndarray:
    """Fold a batch of keys into the sketch."""
    k = registers.shape[0]
    regs = []
    for i in range(k):
        bits = jnp.where(valid, key_bits(keys, i), 0)
        regs.append(jax.lax.reduce(bits, jnp.int32(0), jax.lax.bitwise_or,
                                   tuple(range(bits.ndim))))
    return registers | jnp.stack(regs)


def merge(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Sketch union (distributive over any sharding of the data)."""
    return a | b


def _lowest_zero_index(x: jnp.ndarray) -> jnp.ndarray:
    """Index of the lowest zero bit of each int32 (32 if none)."""
    y = (~x).astype(jnp.uint32)
    low = y & (jnp.uint32(0) - y)
    idx = hashing._popcount32(low - jnp.uint32(1))
    return jnp.where(y == 0, jnp.int32(32), idx.astype(jnp.int32))


def fm_estimate(registers: jnp.ndarray) -> jnp.ndarray:
    """Distinct-count estimate from register bitmaps."""
    r = _lowest_zero_index(registers).astype(jnp.float32)
    return jnp.exp2(jnp.mean(r)) / PHI
