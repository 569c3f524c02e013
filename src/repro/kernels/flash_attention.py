"""Pallas TPU flash attention (forward + custom-VJP backward).

Why this kernel exists (EXPERIMENTS.md §Perf, dense-train cells): the HLO
trace of the jnp chunked-softmax attention shows ~6 HBM materializations of
the [qc, kc] score tensor per layer per pass — S²·B·H·4 bytes each, ~2 TB
per step for a 1.5B model at 4k — and iterations it-1/it-1b proved that
neither layout restructuring nor remat removes them: score traffic is
irreducible WITHOUT kernel fusion.  This kernel keeps scores in VMEM.

Design (TPU-native, not a CUDA port):
  grid = (batch, q_heads, n_q_chunks)  — embarrassingly parallel programs
  fwd:  q block [qc, D] pinned in VMEM; fori_loop over kv chunks streams
        k/v blocks [kc, D]; online-softmax state (m, l, acc) lives in VMEM
        scratch; one MXU dot per (q,kv) chunk pair each for q·kᵀ and p·v.
  bwd:  recompute-in-backward (two passes): pass 1 re-runs the forward
        loop to rebuild p from (q, k, m, l) and accumulates dv, dp, dq;
        dk accumulated via the transposed products.  No score tensor ever
        reaches HBM in either direction.

GQA: the kv head for q head h is h // (nq // nkv), applied in the
BlockSpec index_map — zero data duplication.

HBM traffic contract (what the roofline substitution accounts):
  fwd:   read q + k·nkc_eff + v·nkc_eff + write o + (m,l stats)
  bwd:   read q,k,v,o,do + write dq,dk,dv  (one recompute pass)
Causality halves the effective kv chunks (programs skip j > i blocks via
fori upper bound).

Validated against ref.flash_reference in interpret mode over
shape/dtype/window sweeps (tests/test_flash_kernel.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0e38


def _load4(ref, h, start, size):
    """Load ref[0, h, start:start+size, :] as a [size, D] block (``h`` is
    dynamic in the dkv kernel; size-1 slices are squeezed afterwards)."""
    return ref[pl.ds(0, 1), pl.ds(h, 1), pl.ds(start, size), :][0, 0]


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                kv_chunk: int, causal: bool, window: int, scale: float):
    qc, d = q_ref.shape[2], q_ref.shape[3]
    t = k_ref.shape[2]
    nkc = t // kv_chunk
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale

    q_pos = qi * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, 1), 0)

    def body(j, carry):
        m, l, acc = carry
        k = _load4(k_ref, 0, j * kv_chunk, kv_chunk).astype(jnp.float32)
        v = _load4(v_ref, 0, j * kv_chunk, kv_chunk).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = j * kv_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_chunk), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - safe)
        corr = jnp.exp(m - safe)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_new = acc * corr + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((qc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((qc, 1), jnp.float32)
    a0 = jnp.zeros((qc, d), jnp.float32)
    if causal:
        # programs skip fully-masked kv blocks: j*kc <= (qi+1)*qc - 1
        upper = jnp.minimum(((qi + 1) * qc - 1) // kv_chunk + 1, nkc)
    else:
        upper = nkc
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    m_ref[0, 0] = m
    l_ref[0, 0] = l


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_chunk", "kv_chunk",
                              "interpret"))
def flash_fwd(q, k, v, *, causal=True, window=0, q_chunk=256,
              kv_chunk=512, interpret=True):
    """q: [B,S,H,D]; k/v: [B,T,KVH,D] -> (o [B,S,H,D], m, l [B,H,S,1])."""
    b, s_len, nq, d = q.shape
    t_len, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    q_chunk = min(q_chunk, s_len)
    kv_chunk = min(kv_chunk, t_len)
    assert s_len % q_chunk == 0 and t_len % kv_chunk == 0, \
        (s_len, q_chunk, t_len, kv_chunk)
    nqc = s_len // q_chunk
    scale = 1.0 / (d ** 0.5)

    # layouts: q -> [B,H,S,D]; k/v -> [B,KVH,T,D]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, kv_chunk=kv_chunk, causal=causal, window=window,
        scale=scale)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(b, nq, nqc),
        in_specs=[
            pl.BlockSpec((1, 1, q_chunk, d),
                         lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, t_len, d),
                         lambda bi, h, qi, g=g: (bi, h // g, 0, 0)),
            pl.BlockSpec((1, 1, t_len, d),
                         lambda bi, h, qi, g=g: (bi, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q_chunk, d),
                         lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, q_chunk, 1),
                         lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, q_chunk, 1),
                         lambda bi, h, qi: (bi, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nq, s_len, d), q.dtype),
            jax.ShapeDtypeStruct((b, nq, s_len, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, nq, s_len, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return o.transpose(0, 2, 1, 3), m, l


# --------------------------------------------------------------------------
# backward (recompute-in-backward, one pass builds dq; one builds dk/dv)
# --------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                   dq_ref, *, kv_chunk: int, causal: bool, window: int,
                   scale: float):
    qc, d = q_ref.shape[2], q_ref.shape[3]
    t = k_ref.shape[2]
    nkc = t // kv_chunk
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    m = m_ref[0, 0]
    l = jnp.maximum(l_ref[0, 0], 1e-30)
    delta = delta_ref[0, 0]                    # Σ_d o·do per q row
    q_pos = qi * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, 1), 0)

    def body(j, dq):
        k = _load4(k_ref, 0, j * kv_chunk, kv_chunk).astype(jnp.float32)
        v = _load4(v_ref, 0, j * kv_chunk, kv_chunk).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = j * kv_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_chunk), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
        p = jnp.where(mask, jnp.exp(s - safe), 0.0) / l
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    if causal:
        upper = jnp.minimum(((qi + 1) * qc - 1) // kv_chunk + 1, nkc)
    else:
        upper = nkc
    dq = jax.lax.fori_loop(0, upper, body,
                           jnp.zeros((qc, d), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, delta_ref,
                    dk_ref, dv_ref, *, q_chunk: int, causal: bool,
                    window: int, scale: float, g: int):
    """One program per (b, kv_head, kv chunk): loops q chunks × the g query
    heads of this kv head, accumulating dk/dv."""
    kc, d = dk_ref.shape[2], dk_ref.shape[3]
    s_total = q_ref.shape[2]
    nqc = s_total // q_chunk
    ki = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    k_pos = ki * kc + jax.lax.broadcasted_iota(jnp.int32, (1, kc), 1)

    def q_loop(it, carry):
        dk, dv = carry
        hq = it // nqc
        qi = it % nqc
        q = _load4(q_ref, hq, qi * q_chunk, q_chunk).astype(jnp.float32) \
            * scale
        do = _load4(do_ref, hq, qi * q_chunk, q_chunk).astype(jnp.float32)
        m = _load4(m_ref, hq, qi * q_chunk, q_chunk)
        l = jnp.maximum(_load4(l_ref, hq, qi * q_chunk, q_chunk), 1e-30)
        delta = _load4(delta_ref, hq, qi * q_chunk, q_chunk)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = qi * q_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (q_chunk, 1), 0)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
        p = jnp.where(mask, jnp.exp(s - safe), 0.0) / l
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros((kc, d), jnp.float32)
    dv0 = jnp.zeros((kc, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, g * nqc, q_loop, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_chunk", "kv_chunk",
                              "interpret"))
def flash_bwd(q, k, v, o, m, l, do, *, causal=True, window=0,
              q_chunk=256, kv_chunk=512, interpret=True):
    b, s_len, nq, d = q.shape
    t_len, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    q_chunk = min(q_chunk, s_len)
    kv_chunk = min(kv_chunk, t_len)
    nqc = s_len // q_chunk
    nkc = t_len // kv_chunk
    scale = 1.0 / (d ** 0.5)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    ot = o.transpose(0, 2, 1, 3)
    delta = jnp.sum(ot.astype(jnp.float32) * dot.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # [B,H,S,1]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, kv_chunk=kv_chunk, causal=causal,
                          window=window, scale=scale),
        grid=(b, nq, nqc),
        in_specs=[
            pl.BlockSpec((1, 1, q_chunk, d),
                         lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, t_len, d),
                         lambda bi, h, qi, g=g: (bi, h // g, 0, 0)),
            pl.BlockSpec((1, 1, t_len, d),
                         lambda bi, h, qi, g=g: (bi, h // g, 0, 0)),
            pl.BlockSpec((1, 1, q_chunk, d),
                         lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, q_chunk, 1),
                         lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, q_chunk, 1),
                         lambda bi, h, qi: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, q_chunk, 1),
                         lambda bi, h, qi: (bi, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_chunk, d),
                               lambda bi, h, qi: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, s_len, d), q.dtype),
        interpret=interpret,
    )(qt, kt, vt, dot, m, l, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, q_chunk=q_chunk, causal=causal,
                          window=window, scale=scale, g=g),
        grid=(b, nkv, nkc),
        in_specs=[
            pl.BlockSpec((1, g, s_len, d),
                         lambda bi, hk, ki, g=g: (bi, hk, 0, 0)),
            pl.BlockSpec((1, 1, kv_chunk, d),
                         lambda bi, hk, ki: (bi, hk, ki, 0)),
            pl.BlockSpec((1, 1, kv_chunk, d),
                         lambda bi, hk, ki: (bi, hk, ki, 0)),
            pl.BlockSpec((1, g, s_len, d),
                         lambda bi, hk, ki, g=g: (bi, hk, 0, 0)),
            pl.BlockSpec((1, g, s_len, 1),
                         lambda bi, hk, ki, g=g: (bi, hk, 0, 0)),
            pl.BlockSpec((1, g, s_len, 1),
                         lambda bi, hk, ki, g=g: (bi, hk, 0, 0)),
            pl.BlockSpec((1, g, s_len, 1),
                         lambda bi, hk, ki, g=g: (bi, hk, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, kv_chunk, d),
                         lambda bi, hk, ki: (bi, hk, ki, 0)),
            pl.BlockSpec((1, 1, kv_chunk, d),
                         lambda bi, hk, ki: (bi, hk, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nkv, t_len, d), k.dtype),
            jax.ShapeDtypeStruct((b, nkv, t_len, d), v.dtype),
        ],
        interpret=interpret,
    )(qt, kt, vt, dot, m, l, delta)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


# --------------------------------------------------------------------------
# custom-VJP wrapper
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_kernel(q, k, v, causal=True, window=0, q_chunk=256,
                           kv_chunk=512, interpret=True):
    o, _, _ = flash_fwd(q, k, v, causal=causal, window=window,
                        q_chunk=q_chunk, kv_chunk=kv_chunk,
                        interpret=interpret)
    return o


def _fa_fwd(q, k, v, causal, window, q_chunk, kv_chunk, interpret):
    o, m, l = flash_fwd(q, k, v, causal=causal, window=window,
                        q_chunk=q_chunk, kv_chunk=kv_chunk,
                        interpret=interpret)
    return o, (q, k, v, o, m, l)


def _fa_bwd(causal, window, q_chunk, kv_chunk, interpret, res, do):
    q, k, v, o, m, l = res
    dq, dk, dv = flash_bwd(q, k, v, o, m, l, do, causal=causal,
                           window=window, q_chunk=q_chunk,
                           kv_chunk=kv_chunk, interpret=interpret)
    return dq, dk, dv


flash_attention_kernel.defvjp(_fa_fwd, _fa_bwd)


def hbm_bytes(cfg, batch: int, seq: int, *, train: bool) -> float:
    """The kernel's HBM traffic contract (per layer, per device inputs):
    fwd reads q,k,v (+stats) and writes o; bwd reads q,k,v,o,do and writes
    dq,dk,dv.  Used by the dry-run's roofline substitution."""
    bt = 2  # bf16
    qo = batch * seq * cfg.n_heads * cfg.head_dim * bt
    kv = batch * seq * cfg.n_kv_heads * cfg.head_dim * bt
    fwd = 2 * qo + 2 * kv + 2 * (batch * seq * cfg.n_heads * 4) * 2
    if not train:
        return fwd
    bwd = 3 * qo + 2 * kv + (qo + 2 * kv)      # q,o,do reads + dq,dk,dv
    return fwd + bwd
