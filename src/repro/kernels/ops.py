"""Public jit'd wrappers around the Pallas kernels (with jnp fallback).

Responsibilities kept out of the kernels so they stay branch-free:
  * sentinel-mask invalid slots with per-side sentinels (so invalid slots can
    never equal anything on the other side),
  * pad capacities to 128-lane multiples (MXU/VPU alignment) and the fused
    kernels' tiled PMU axis to a multiple of 8 rows (Mosaic's block rule),
  * dispatch kernel vs. pure-jnp reference (``use_kernel=False`` is the CPU
    default — interpret-mode Pallas is for validation, not speed),
  * cast/clip results back to caller shapes.

Keys must be > SENT_BASE (= -2^31 + 16); the data generators and the
relational layer guarantee int32 keys ≥ -2^30.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core.relation import SENTINEL
from repro.kernels import bucket_join, radix_hist, ref

# Per-side probe sentinels, derived from the ONE canonical padding sentinel
# (``relation.SENTINEL``, also the fill value of every bucketized layout) so
# the whole constellation lives in [SENTINEL, SENTINEL + 20] — far below the
# ≥ -2^30 key floor — and no two sides can ever false-match each other or a
# padded slot.
SENT_BASE = SENTINEL + 15
_SENT = {"r": SENT_BASE + 1, "s": SENT_BASE + 2, "t": SENT_BASE + 3,
         "a": SENT_BASE + 4, "b": SENT_BASE + 5}
assert len(set(_SENT.values()) | {SENTINEL}) == len(_SENT) + 1

# Largest integer f32 represents exactly (24-bit mantissa).  The fused
# kernels accumulate per-cell partials in int32 on purpose; any compiled
# variant tempted to accumulate in f32 (e.g. to ride the MXU) silently
# loses counts past this — ``analysis.widths`` flags accumulator cells
# whose capacity-product ceiling crosses it.
EXACT_F32_MAX = 1 << 24


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _scan_kernel_gate() -> bool:
    """Interpret flag for the per-bucket scan-driver kernels.  Their one-row
    BlockSpecs do not meet Mosaic's (8, 128) block rule, so they run only
    interpreted; on TPU the fused kernels are the compiled path."""
    if not _interpret():
        raise NotImplementedError(
            "the per-bucket scan-driver kernels (pair_count / count3_* / "
            "per_r_counts) are interpret-only; on TPU use the fused engine "
            "(engine.MultiwayJoinEngine / JoinSession) or use_kernel=False")
    return True


def _mask(keys: jnp.ndarray, valid: jnp.ndarray, side: str) -> jnp.ndarray:
    return jnp.where(valid, keys, jnp.int32(_SENT[side]))


def _pad_lanes(x: jnp.ndarray, side: str, align: int = 128,
               axis: int = -1) -> jnp.ndarray:
    axis = axis % x.ndim
    rem = (-x.shape[axis]) % align
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=_SENT[side])


def _pad_tile(x: jnp.ndarray, side: str, axis: int) -> jnp.ndarray:
    """Pad capacities to 128 lanes and the fused kernels' tiled PMU axis to
    a multiple of ``bucket_join.TILE`` sentinel rows."""
    return _pad_lanes(_pad_lanes(x, side), side, bucket_join.TILE, axis)


def bucket_pair_count(ka, va, kb, vb, *, use_kernel: bool = False):
    ka = _mask(ka, va, "a")
    kb = _mask(kb, vb, "b")
    if use_kernel:
        return bucket_join.pair_count(_pad_lanes(ka, "a"), _pad_lanes(kb, "b"),
                                      interpret=_scan_kernel_gate())
    return ref.bucket_pair_count(ka, kb)


def bucket_count3_linear(rb, rv, sb, sc, sv, tc, tv, *,
                         use_kernel: bool = False):
    rb = _mask(rb, rv, "r")
    sb = _mask(sb, sv, "s")
    sc = _mask(sc, sv, "s")
    tc = _mask(tc, tv, "t")
    if use_kernel:
        return bucket_join.count3_linear(
            _pad_lanes(rb, "r"), _pad_lanes(sb, "s"), _pad_lanes(sc, "s"),
            _pad_lanes(tc, "t"), interpret=_scan_kernel_gate())
    return ref.bucket_count3_linear(rb, sb, sc, tc)


def bucket_per_r_counts(rb, rv, sb, sc, sv, tc, tv, *,
                        use_kernel: bool = False):
    cr = rb.shape[-1]
    rb = _mask(rb, rv, "r")
    sb = _mask(sb, sv, "s")
    sc = _mask(sc, sv, "s")
    tc = _mask(tc, tv, "t")
    if use_kernel:
        out = bucket_join.per_r_counts(
            _pad_lanes(rb, "r"), _pad_lanes(sb, "s"), _pad_lanes(sc, "s"),
            _pad_lanes(tc, "t"), interpret=_scan_kernel_gate())
        return out[:, :cr]
    return ref.bucket_per_r_counts(rb, sb, sc, tc)


def bucket_count3_cyclic(ra, rb, rv, sb, sc, sv, tc, ta, tv, *,
                         use_kernel: bool = False):
    ra = _mask(ra, rv, "r")
    rb = _mask(rb, rv, "r")
    sb = _mask(sb, sv, "s")
    sc = _mask(sc, sv, "s")
    tc = _mask(tc, tv, "t")
    ta = _mask(ta, tv, "t")
    if use_kernel:
        return bucket_join.count3_cyclic(
            _pad_lanes(ra, "r"), _pad_lanes(rb, "r"), _pad_lanes(sb, "s"),
            _pad_lanes(sc, "s"), _pad_lanes(tc, "t"), _pad_lanes(ta, "t"),
            interpret=_scan_kernel_gate())
    return ref.bucket_count3_cyclic(ra, rb, sb, sc, tc, ta)


# --------------------------------------------------------------------------
# fused partition-sweep ops (engine hot path)
# --------------------------------------------------------------------------
#
# One call covers the WHOLE coarse partition sweep instead of one bucket
# row.  ``use_kernel=True`` dispatches to the single-pallas_call fused
# kernels (grid spans the sweep, §6.2 double buffering across partitions);
# the default jnp path is equally fused at the XLA level: the partition
# sweep is batched into one op (or one scan over the streaming dimension
# when the compare tensors would not fit), so the hot path is one launch —
# not h_parts × g_parts of them.

# Full-batch threshold for the compare-based jnp fused paths: largest
# compare tensor (in elements) we are willing to materialize before falling
# back to a scan over the streaming dimension.
_FUSE_BATCH_ELEMS = 1 << 26


def _bucket_multiplicity(table, probes):
    """Per-probe occurrence counts within aligned bucket rows.

    table: [B, Ct] sentinel-masked keys; probes: [B, Cp].  Returns [B, Cp]
    int32 — for each probe, how many equal keys its OWN bucket row holds.

    A sort-merge count: each row's table and probe keys are sorted
    together by (key, slot), where a table entry's slot is -1 and a
    probe's is its index, so inside every run of equal keys the table
    entries come first.  A probe's count is then the running number
    of table entries at the probe minus that number where its run starts.
    A second sort by slot puts the counts back in probe order.  Two sorts
    of Ct + Cp elements per row and no gather: on a TPU a binary search
    per probe pays a gather's latency at every search step.
    """
    b, ct = table.shape
    cp = probes.shape[-1]
    keys = jnp.concatenate([table, probes], axis=-1)
    slot = jnp.concatenate(
        [jnp.full((b, ct), -1, jnp.int32),
         jnp.broadcast_to(jnp.arange(cp, dtype=jnp.int32), (b, cp))],
        axis=-1)
    keys, slot = jax.lax.sort((keys, slot), dimension=1, num_keys=2)
    is_table = (slot < 0).astype(jnp.int32)
    seen = jnp.cumsum(is_table, axis=-1)          # table entries so far
    run_start = jnp.concatenate(
        [jnp.ones((b, 1), bool), keys[:, 1:] != keys[:, :-1]], axis=-1)
    before_run = jax.lax.cummax(
        jnp.where(run_start, seen - is_table, 0), axis=1)
    _, counts = jax.lax.sort((slot, seen - before_run), dimension=1,
                             num_keys=1)
    return counts[:, ct:]


def _fused_linear_ref(rb, sb, sc, tc):
    """rb [hp,u,Cr], sb/sc [hp,gp,u,Cs], tc [gp,Ct] -> [hp,u] int32.

    One fused pass over the whole sweep: every S slot is weighted by its R
    multiplicity (probing the matching (H, h) bucket) times its T
    multiplicity (probing the matching g bucket), then per-(H, h) partial
    sums — identical per-bucket semantics to the scan driver, realized with
    sort-merge counts (``_bucket_multiplicity``) instead of all-pairs
    compares.
    """
    hp, u, cr = rb.shape
    _, gp, _, cs = sb.shape
    _, ct = tc.shape
    # wr: probe R bucket (H, h) with the S keys routed to it
    s_by_r = sb.transpose(0, 2, 1, 3).reshape(hp * u, gp * cs)
    wr = _bucket_multiplicity(rb.reshape(hp * u, cr), s_by_r)
    # wt: probe T bucket g with the S keys streamed against it
    s_by_t = sc.transpose(1, 0, 2, 3).reshape(gp, hp * u * cs)
    wt = _bucket_multiplicity(tc, s_by_t)
    wt = wt.reshape(gp, hp, u, cs).transpose(1, 2, 0, 3).reshape(
        hp * u, gp * cs)
    return jnp.sum(wr * wt, axis=-1).reshape(hp, u)


def _fused_per_r_ref(rb, sb, sc, tc):
    """rb [hp,u,Cr], sb/sc [hp,gp,u,Cs], tc [gp,Ct] -> [hp,u,Cr] int32."""
    hp, u, cr = rb.shape
    _, gp, _, cs = sb.shape
    _, ct = tc.shape
    if hp * gp * u * cs * max(cr, ct) <= _FUSE_BATCH_ELEMS:
        m1 = (sb[..., :, None] == rb[:, None, :, None, :]).astype(jnp.int32)
        wt = jnp.sum(sc[..., :, None] == tc[None, :, None, None, :], axis=-1)
        return jnp.einsum("hgusr,hgus->hur", m1, wt).astype(jnp.int32)

    def g_step(acc, ys):
        sb_j, sc_j, tc_j = ys
        m1 = (sb_j[..., :, None] == rb[..., None, :]).astype(jnp.int32)
        wt = jnp.sum(sc_j[..., :, None] == tc_j[None, None, None, :], axis=-1)
        return acc + jnp.einsum("husr,hus->hur", m1, wt), None

    acc, _ = jax.lax.scan(
        g_step, jnp.zeros((hp, u, cr), jnp.int32),
        (sb.transpose(1, 0, 2, 3), sc.transpose(1, 0, 2, 3), tc))
    return acc


def lex_sort_pairs(tc, ta):
    """Sort each bucket row's (c, a) pairs lexicographically by (c, then a).

    tc/ta: [..., Ct] sentinel-masked keys.  Returns (tc_sorted, ta_sorted) —
    the sorted (c, a)-pair index the cyclic probes range-scan.
    """
    order = jnp.lexsort((ta, tc), axis=-1)
    return (jnp.take_along_axis(tc, order, axis=-1),
            jnp.take_along_axis(ta, order, axis=-1))


def sorted_pair_index(tc, ta, tv):
    """Build the sorted (c, a)-pair index for a grid of T bucket rows:
    sentinel-mask invalid slots, then lex-sort each row by (c, then a).

    tc/ta: [..., Ct] raw keys, tv: [..., Ct] validity.  Built ONCE per
    partitioning and probed many times (``bucket_count3_cyclic_pairidx``)
    — the public entry the scan driver's pair-index path uses.
    """
    return lex_sort_pairs(_mask(tc, tv, "t"), _mask(ta, tv, "t"))


def bucket_count3_cyclic_pairidx(ra, rb, rv, sb, sc, sv, tcs, tas):
    """Per-bucket triangle counts against a pre-built sorted pair index.

    Same contract as ``bucket_count3_cyclic`` except the T side arrives
    as ``sorted_pair_index`` output (already masked + lex-sorted, so no
    validity argument): each S slot finds its T matches with two
    ``searchsorted`` range probes and a prefix-sum table instead of the
    all-pairs compare — O(Ct·Cr + Cs·Cr + Cs·log Ct) per bucket.
    """
    return _pairidx_cell_counts(_mask(ra, rv, "r"), _mask(rb, rv, "r"),
                                _mask(sb, sv, "s"), _mask(sc, sv, "s"),
                                tcs, tas)


def _pairidx_cell_counts(ra, rb, sb, sc, tcs, tas):
    """Per-bucket triangle counts via the sorted (c, a)-pair index.

    ra/rb: [B, Cr], sb/sc: [B, Cs], tcs/tas: [B, Ct] with (tcs, tas)
    lex-sorted per bucket (``lex_sort_pairs``).  Returns [B] int32.

    Instead of the all-pairs contraction Σ (M1ᵀM2) ⊙ M3 (O(Cs·Cr·Ct) per
    bucket), each S slot range-scans the pair index: its T matches are the
    contiguous run tcs ∈ [lo, hi) found by two ``searchsorted`` probes, and
    the per-R a-match counts over that run come from a prefix-sum table —
    O(Ct·Cr + Cs·Cr + Cs·log Ct) per bucket.  Same per-bucket semantics,
    TrieJax-style indexed second-relation probe.
    """
    lo = jax.vmap(lambda t, p: jnp.searchsorted(t, p, side="left"))(tcs, sc)
    hi = jax.vmap(lambda t, p: jnp.searchsorted(t, p, side="right"))(tcs, sc)
    # prefix sums over the sorted T run of per-R a-equality
    m3 = (tas[:, :, None] == ra[:, None, :]).astype(jnp.int32)   # [B, Ct, Cr]
    pre = jnp.pad(jnp.cumsum(m3, axis=1), ((0, 0), (1, 0), (0, 0)))
    # per-(s, r): # t with t.c == s.c and t.a == r.a  (range-sum of prefixes)
    g = (jnp.take_along_axis(pre, hi[:, :, None], axis=1)
         - jnp.take_along_axis(pre, lo[:, :, None], axis=1))     # [B, Cs, Cr]
    e = (sb[:, :, None] == rb[:, None, :]).astype(jnp.int32)     # [B, Cs, Cr]
    return jnp.sum(e * g, axis=(1, 2)).astype(jnp.int32)


def _fused_cyclic_pairidx_ref(ra, rb, sb, sc, tc, ta):
    """Pair-index realization of the fused cyclic sweep (CPU hot path).

    Same shapes/contract as ``_fused_cyclic_ref``; the T stream is lex-sorted
    into a (c, a)-pair index once per bucket, then every (cell, f) step probes
    it with searchsorted range scans instead of all-pairs compares.
    """
    hp, gp, uh, ug, cr = ra.shape
    _, fp, _, cs = sb.shape
    _, _, _, ct = tc.shape
    tcs, tas = lex_sort_pairs(tc, ta)            # [hp, fp, uh, Ct]
    b = gp * uh * ug

    def bcast(x, shape):
        return jnp.broadcast_to(x, shape).reshape((b,) + x.shape[-1:])

    def f_step(acc, ys):
        sb_f, sc_f, tcs_f, tas_f = ys            # [gp,ug,Cs], [hp,uh,Ct]
        s_shape = (gp, uh, ug, cs)
        t_shape = (gp, uh, ug, ct)

        def h_row(xs):
            # one coarse H(A) row at a time: the per-bucket (Ct, Cr)
            # prefix tables of the whole grid would not fit in memory
            ra_i, rb_i, tcs_i, tas_i = xs        # [gp,uh,ug,Cr], [uh,Ct]
            c = _pairidx_cell_counts(
                ra_i.reshape(b, cr), rb_i.reshape(b, cr),
                bcast(sb_f[:, None, :, :], s_shape),
                bcast(sc_f[:, None, :, :], s_shape),
                bcast(tcs_i[None, :, None, :], t_shape),
                bcast(tas_i[None, :, None, :], t_shape))
            return c.reshape(gp, uh, ug)

        return acc + jax.lax.map(h_row, (ra, rb, tcs_f, tas_f)), None

    acc, _ = jax.lax.scan(
        f_step, jnp.zeros((hp, gp, uh, ug), jnp.int32),
        (sb.transpose(1, 0, 2, 3), sc.transpose(1, 0, 2, 3),
         tcs.transpose(1, 0, 2, 3), tas.transpose(1, 0, 2, 3)))
    return acc


def _fused_cyclic_ref(ra, rb, sb, sc, tc, ta):
    """ra/rb [hp,gp,uh,ug,Cr], sb/sc [gp,fp,ug,Cs], tc/ta [hp,fp,uh,Ct]
    -> [hp,gp,uh,ug] int32.  Batched over the coarse grid, scanned over f."""
    hp, gp, uh, ug, cr = ra.shape
    _, fp, _, cs = sb.shape
    _, _, _, ct = tc.shape

    def f_step(acc, ys):
        sb_f, sc_f, tc_f, ta_f = ys      # [gp,ug,Cs], [hp,uh,Ct]
        def flat(x, shape):
            return jnp.broadcast_to(x, shape).reshape(
                (hp * gp * uh * ug,) + x.shape[-1:])
        s_shape = (hp, gp, uh, ug, cs)
        t_shape = (hp, gp, uh, ug, ct)
        c = ref.bucket_count3_cyclic(
            ra.reshape(-1, cr), rb.reshape(-1, cr),
            flat(sb_f[None, :, None, :, :], s_shape),
            flat(sc_f[None, :, None, :, :], s_shape),
            flat(tc_f[:, None, :, None, :], t_shape),
            flat(ta_f[:, None, :, None, :], t_shape))
        return acc + c.reshape(hp, gp, uh, ug), None

    acc, _ = jax.lax.scan(
        f_step, jnp.zeros((hp, gp, uh, ug), jnp.int32),
        (sb.transpose(1, 0, 2, 3), sc.transpose(1, 0, 2, 3),
         tc.transpose(1, 0, 2, 3), ta.transpose(1, 0, 2, 3)))
    return acc


def _fused_star_ref(rb, sb, sc, tc):
    """rb [uh,Cr], sb/sc [ch,uh,ug,Cs], tc [ug,Ct] -> [uh,ug] int32.

    Same sort-merge count as ``_fused_linear_ref``: each fact slot is
    counted against the R bucket of its row and the T bucket of its column.
    """
    uh, cr = rb.shape
    ch, _, ug, cs = sb.shape
    _, ct = tc.shape
    s_by_r = sb.transpose(1, 0, 2, 3).reshape(uh, ch * ug * cs)
    wr = _bucket_multiplicity(rb, s_by_r)
    wr = wr.reshape(uh, ch, ug, cs).transpose(1, 0, 2, 3)   # [ch,uh,ug,cs]
    s_by_t = sc.transpose(2, 0, 1, 3).reshape(ug, ch * uh * cs)
    wt = _bucket_multiplicity(tc, s_by_t)
    wt = wt.reshape(ug, ch, uh, cs).transpose(1, 2, 0, 3)   # [ch,uh,ug,cs]
    return jnp.sum(wr * wt, axis=(0, 3)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def fused_count3_linear(rb, rv, sb, sc, sv, tc, tv, *,
                        use_kernel: bool = False):
    """Fused linear-3 sweep: per-(H, h) bucket counts [hp, u] int32."""
    rb = _mask(rb, rv, "r")
    sb = _mask(sb, sv, "s")
    sc = _mask(sc, sv, "s")
    tc = _mask(tc, tv, "t")
    if use_kernel:
        u = rb.shape[1]
        return bucket_join.fused_count3_linear(
            _pad_tile(rb, "r", 1), _pad_tile(sb, "s", 2),
            _pad_tile(sc, "s", 2), _pad_lanes(tc, "t"),
            interpret=_interpret())[:, :u]
    return _fused_linear_ref(rb, sb, sc, tc)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def fused_per_r_counts(rb, rv, sb, sc, sv, tc, tv, *,
                       use_kernel: bool = False):
    """Fused per-R-slot counts [hp, u, Cr] int32 (Example 1 aggregate)."""
    cr = rb.shape[-1]
    rb = _mask(rb, rv, "r")
    sb = _mask(sb, sv, "s")
    sc = _mask(sc, sv, "s")
    tc = _mask(tc, tv, "t")
    if use_kernel:
        u = rb.shape[1]
        out = bucket_join.fused_per_r_counts(
            _pad_tile(rb, "r", 1), _pad_tile(sb, "s", 2),
            _pad_tile(sc, "s", 2), _pad_lanes(tc, "t"),
            interpret=_interpret())
        return out[:, :u, :cr]
    return _fused_per_r_ref(rb, sb, sc, tc)


_CYCLIC_SWAP = "pallas-all-pairs (pair_index swapped out)"


def cyclic_kernel(use_kernel: bool, pair_index: bool) -> str:
    """Which sweep ``fused_count3_cyclic`` runs for these flags on this
    backend.  The one documented swap: the pair-index kernel's binary-search
    gathers do not lower to Mosaic, so compiled TPU runs the all-pairs MXU
    kernel when ``pair_index=True`` asks for the pair index (and warns)."""
    if not use_kernel:
        return "jnp-pair-index" if pair_index else "jnp-all-pairs"
    if not pair_index:
        return "pallas-all-pairs"
    return "pallas-pair-index" if _interpret() else _CYCLIC_SWAP


@functools.partial(jax.jit, static_argnames=("use_kernel", "pair_index"))
def fused_count3_cyclic(ra, rb, rv, sb, sc, sv, tc, ta, tv, *,
                        use_kernel: bool = False, pair_index: bool = True):
    """Fused cyclic sweep: per-cell counts [hp, gp, uh, ug] int32.

    ``pair_index=True`` (default) probes a sorted (c, a)-pair index of the T
    stream with searchsorted range scans — the indexed backend that takes the
    cyclic CPU path past the all-pairs compare bottleneck.  Set False for the
    all-pairs contraction (the MXU-shaped formulation).
    """
    ra = _mask(ra, rv, "r")
    rb = _mask(rb, rv, "r")
    sb = _mask(sb, sv, "s")
    sc = _mask(sc, sv, "s")
    tc = _mask(tc, tv, "t")
    ta = _mask(ta, tv, "t")
    kernel = cyclic_kernel(use_kernel, pair_index)
    if kernel == _CYCLIC_SWAP:
        warnings.warn("pair_index=True has no compiled Pallas kernel: the "
                      "all-pairs MXU kernel runs instead", stacklevel=2)
    if kernel == "pallas-pair-index":
        tcs, tas = lex_sort_pairs(_pad_lanes(tc, "t"), _pad_lanes(ta, "t"))
        return bucket_join.fused_count3_cyclic_pairidx(
            _pad_lanes(ra, "r"), _pad_lanes(rb, "r"), _pad_lanes(sb, "s"),
            _pad_lanes(sc, "s"), tcs, tas, interpret=_interpret())
    if use_kernel:
        ug = ra.shape[3]
        return bucket_join.fused_count3_cyclic(
            _pad_tile(ra, "r", 3), _pad_tile(rb, "r", 3),
            _pad_tile(sb, "s", 2), _pad_tile(sc, "s", 2),
            _pad_lanes(tc, "t"), _pad_lanes(ta, "t"),
            interpret=_interpret())[..., :ug]
    if pair_index:
        return _fused_cyclic_pairidx_ref(ra, rb, sb, sc, tc, ta)
    return _fused_cyclic_ref(ra, rb, sb, sc, tc, ta)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def fused_count3_star(rb, rv, sb, sc, sv, tc, tv, *,
                      use_kernel: bool = False):
    """Fused star sweep: per-PMU counts [uh, ug] int32."""
    rb = _mask(rb, rv, "r")
    sb = _mask(sb, sv, "s")
    sc = _mask(sc, sv, "s")
    tc = _mask(tc, tv, "t")
    if use_kernel:
        ug = tc.shape[0]
        return bucket_join.fused_count3_star(
            _pad_lanes(rb, "r"), _pad_tile(sb, "s", 2),
            _pad_tile(sc, "s", 2), _pad_tile(tc, "t", 0),
            interpret=_interpret())[:, :ug]
    return _fused_star_ref(rb, sb, sc, tc)


@functools.partial(jax.jit, static_argnames=("n_buckets", "use_kernel"))
def radix_histogram(keys, valid, *, n_buckets: int, use_kernel: bool = False):
    """Histogram of hash_bucket(keys) over live rows."""
    from repro.core import hashing

    if use_kernel:
        # pad the stream to the tile size with a sentinel whose bucket we
        # compute and subtract afterwards.
        tile = 1024
        n = keys.shape[0]
        padded = jnp.where(valid, keys, jnp.int32(_SENT["s"]))
        rem = (-n) % tile
        if rem:
            padded = jnp.pad(padded, (0, rem), constant_values=_SENT["s"])
        hist = radix_hist.radix_histogram(padded, n_buckets=n_buckets,
                                          interpret=_interpret())
        n_invalid = (padded.shape[0] - jnp.sum(valid)).astype(jnp.int32)
        sent_bucket = hashing.hash_bucket(
            jnp.full((1,), _SENT["s"], jnp.int32), n_buckets, "H")[0]
        return hist.at[sent_bucket].add(-n_invalid)
    ids = jnp.where(valid, hashing.hash_bucket(keys, n_buckets, "H"),
                    jnp.int32(n_buckets))
    return ref.radix_histogram(keys, ids, n_buckets)


def fm_registers(ra, rv, rb, sb, sc, sv, tc, td, tv, *, n_registers: int = 32,
                 use_kernel: bool = False):
    """FM sketch registers over implicit joined (a, d) pairs (ref path only;
    the matmul inside dominates and is already MXU-shaped under jit)."""
    del use_kernel
    ra = _mask(ra, rv, "r")
    rb = _mask(rb, rv, "r")
    sb = _mask(sb, sv, "s")
    sc = _mask(sc, sv, "s")
    tc = _mask(tc, tv, "t")
    td = _mask(td, tv, "t")
    return ref.fm_registers(ra, rb, sb, sc, tc, td, n_registers)
