#!/usr/bin/env python3
"""Run the join engine's main path once on a TPU and check every answer.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # (2, 2) mesh: execute_sharded only

One process drives the chip; all data is made from ``--seed`` by the repo's
own generators (``examples/analytics_3way.friends_graph``,
``repro.data.relations.gen_relation``).  Phases, each checked exactly
against a plain numpy/scipy reference that shares no code with the engine:

  (a) linear 3-way, the paper's Example 1: friends-of-friends-of-friends
      over three aliases of the friends relation, as a COUNT and per user
      (``per_r``); the total passes 2^31,
  (b) a 5-relation star (fact table x 4 dimensions) through the N-way plan
      IR: binary materialize steps (donated gathers) feeding a fused root,
  (c) the triangle query (cyclic),
  (d) a standing query through ``launch.join_service.JoinService``: watch
      (a)'s query, ingest deltas into each relation, and compare the
      snapshot with a from-scratch ``execute``.

(a)-(c) run on the default jnp path and on the compiled Pallas kernels
(``use_kernel=True``); the kernel path must reach Mosaic (``interpret`` is
False and the fused root's compiled HLO holds ``tpu_custom_call``).
``--chips 4`` builds a ("row", "col") = (2, 2) mesh of the four devices and
runs ``JoinSession.execute_sharded`` for the linear, cyclic and star queries,
each compared with the one-chip ``execute`` and the numpy reference.

Lines starting with ``[info]`` are informational; timings on them are host
wall-clock seconds.  The last line is ``{"ok": true, "device": {...}}``.
Without a TPU, or on any failed check, the script exits nonzero and prints
no result.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "examples"))

import numpy as np  # noqa: E402

FUSED_KERNELS = ("fused_count3_linear", "fused_per_r_counts",
                 "fused_count3_cyclic", "fused_count3_star")


def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


class Failure(Exception):
    """A phase's answer disagreed with its reference."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise Failure(msg)


# --------------------------------------------------------------------------
# plain references (numpy / scipy only)
# --------------------------------------------------------------------------

def ref_fofof(src, dst, n_users):
    """COUNT of f1.dst = f2.src, f2.dst = f3.src: Σ over f2 edges (x, y) of
    indeg(x) · outdeg(y)."""
    indeg = np.bincount(dst, minlength=n_users).astype(np.int64)
    outdeg = np.bincount(src, minlength=n_users).astype(np.int64)
    return int(np.sum(indeg[src] * outdeg[dst]))


def ref_fofof_per_user(src, dst, n_users):
    """Paths per f1 source user: Σ over f1 edges (a, b) of Σ over f2 edges
    (b, y) of outdeg(y)."""
    outdeg = np.bincount(src, minlength=n_users).astype(np.int64)
    two = np.zeros(n_users, np.int64)
    np.add.at(two, src, outdeg[dst])
    per = np.zeros(n_users, np.int64)
    np.add.at(per, src, two[dst])
    return per


def ref_star(fact_keys, dim_keys, key_range):
    """Σ over fact rows of Π_i (# dimension-i rows with the row's key i)."""
    want = np.ones(len(fact_keys[0]), np.int64)
    for fk, dk in zip(fact_keys, dim_keys):
        want *= np.bincount(dk, minlength=key_range).astype(np.int64)[fk]
    return int(want.sum())


def ref_triangles(src, dst, n_users):
    """Oriented triangle count with edge multiplicities: trace(A^3)."""
    from scipy import sparse
    a = sparse.csr_matrix((np.ones(len(src), np.int64), (src, dst)),
                          shape=(n_users, n_users))
    return int((a @ a).multiply(a.T).sum())


# --------------------------------------------------------------------------
# instrumentation
# --------------------------------------------------------------------------

class CompileCounters:
    """JAX monitoring events: persistent-cache hits/misses and backend
    compile seconds, read per phase."""

    def __init__(self):
        from jax import monitoring
        self.hits = self.misses = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self):
        return self.hits, self.misses, self.compile_s


class KernelCalls:
    """Records the argument shapes of every fused Pallas call (made while
    the jitted ``ops`` wrappers trace), so the smoke can show the dispatch
    chose the compiled kernel (``interpret=False``) and re-lower it for
    its HLO."""

    def __init__(self):
        from repro.kernels import bucket_join
        self.calls = {}
        self._fns = {}
        for name in FUSED_KERNELS:
            fn = getattr(bucket_join, name)
            self._fns[name] = fn
            setattr(bucket_join, name, self._wrap(name, fn))

    def _wrap(self, name, fn):
        def wrapped(*args, **kw):
            import jax
            self.calls[name] = ([jax.ShapeDtypeStruct(a.shape, a.dtype)
                                 for a in args], kw)
            return fn(*args, **kw)
        return wrapped

    def reset(self):
        self.calls.clear()

    def check_compiled(self, names):
        for name in names:
            check(name in self.calls,
                  f"use_kernel=True never reached bucket_join.{name}")
            args, kw = self.calls[name]
            check(kw.get("interpret") is False,
                  f"{name} ran with interpret={kw.get('interpret')!r}")
            hlo = self._fns[name].lower(*args, **kw).compile().as_text()
            check("tpu_custom_call" in hlo,
                  f"{name}: compiled HLO holds no tpu_custom_call")
            info(f"{name}: interpret=False, compiled HLO has "
                 "tpu_custom_call")


def rel_bytes(rels) -> int:
    seen, total = set(), 0
    for rel in rels:
        if id(rel) in seen:
            continue
        seen.add(id(rel))
        total += rel.valid.nbytes + sum(c.nbytes for c in
                                        rel.columns.values())
    return total


def block(res) -> None:
    import jax
    if res.per_r is not None:
        jax.block_until_ready((res.per_r.keys, res.per_r.valid))


def timed_execute(label, session, query, counters, **kw):
    """Cold execute (compiles) then warm execute; prints both on an
    informational line and returns the warm result."""
    h0, m0, c0 = counters.snapshot()
    t0 = time.perf_counter()
    cold = session.execute(query, **kw)
    block(cold)
    cold_s = time.perf_counter() - t0
    h1, m1, c1 = counters.snapshot()
    t1 = time.perf_counter()
    res = session.execute(query, **kw)
    block(res)
    warm_s = time.perf_counter() - t1
    check(int(cold.count) == int(res.count),
          f"{label}: cold {int(cold.count)} != warm {int(res.count)}")
    info(f"{label}: cold_wall_s={cold_s:.3f} warm_wall_s={warm_s:.3f} "
         f"backend_compile_s={c1 - c0:.3f} cache_hits={h1 - h0} "
         f"cache_misses={m1 - m0}")
    return res


def describe(label, res, rels):
    info(f"{label}: count={int(res.count)} overflowed={res.overflowed} "
         f"rounds={res.rounds} kind={res.kind} strategy={res.strategy} "
         f"rows={[int(r.n) for r in rels]} "
         f"resident_bytes={rel_bytes(rels)}")
    check(not res.overflowed, f"{label}: overflowed")


# --------------------------------------------------------------------------
# data
# --------------------------------------------------------------------------

def friends(users, per_user, seed):
    from analytics_3way import friends_graph
    src, dst = friends_graph(users, per_user, seed=seed)
    return src, dst


def friends_relation(src, dst, spare: int = 0):
    """The friends relation at a power-of-two capacity with room for
    ``spare`` ingested rows, so (a) and (d) share every compiled shape and
    (d)'s appends never grow it."""
    from repro.core import Relation
    cap = 1 << (len(src) + spare - 1).bit_length()
    return Relation.from_arrays(capacity=cap, src=src, dst=dst)


def spare_rows(args) -> int:
    return 3 * args.deltas * args.delta_rows


def star_data(args):
    from repro.data.relations import RelGenConfig, gen_relation
    seed = args.seed * 10
    fact = gen_relation(RelGenConfig(
        n=args.fact_rows, d=args.key_range,
        columns=("k1", "k2", "k3", "k4"), seed=seed))
    dims = {f"d{i}": gen_relation(RelGenConfig(
        n=args.dim_rows, d=args.key_range, columns=(f"k{i}", "x"),
        seed=seed + i)) for i in (1, 2, 3, 4)}
    return fact, dims


def linear_query(f1, f2, f3):
    from repro.core import Query
    return Query(relations={"f1": f1, "f2": f2, "f3": f3},
                 predicates=[("f1.dst", "f2.src"), ("f2.dst", "f3.src")])


def triangle_query(f):
    from repro.core import Query
    return Query(relations={"f1": f, "f2": f, "f3": f},
                 predicates=[("f1.dst", "f2.src"), ("f2.dst", "f3.src"),
                             ("f3.dst", "f1.src")])


def star_query(fact, dims):
    from repro.core import Query
    return Query(relations={"fact": fact, **dims},
                 predicates=[(f"fact.k{i}", f"d{i}.k{i}")
                             for i in (1, 2, 3, 4)])


# m_budget = rows / div per query shape: the triangle query's all-pairs
# buckets cost their size squared, so it takes finer buckets
LINEAR_DIV, CYCLIC_DIV, STAR_DIV = 64, 400, 64


def m_budget(n_rows: int, div: int) -> int:
    return max(4096, n_rows // div)


# --------------------------------------------------------------------------
# one-chip phases
# --------------------------------------------------------------------------

def phase_linear(args, use_kernel, users, counters, kcalls):
    from repro.core import JoinSession
    tag = "kernel" if use_kernel else "jnp"
    src, dst = friends(users, args.friends, args.seed)
    f = friends_relation(src, dst, spare_rows(args))
    q = linear_query(f, f, f)
    sess = JoinSession(m_budget=m_budget(len(src), LINEAR_DIV),
                       use_kernel=use_kernel)
    want = ref_fofof(src, dst, users)
    res = timed_execute(f"(a) linear {tag}", sess, q, counters)
    describe(f"(a) linear {tag}", res, [f])
    check(int(res.count) == want,
          f"(a) linear {tag}: count {int(res.count)} != reference {want}")
    if kcalls is not None:
        kcalls.check_compiled(["fused_count3_linear"])
    res = timed_execute(f"(a) linear per_r {tag}", sess, q, counters,
                        per_r=True, key_col="src")
    describe(f"(a) linear per_r {tag}", res, [f])
    pr = res.per_r
    valid = np.asarray(pr.valid)
    keys = np.asarray(pr.keys)[valid]
    counts = np.asarray(pr.counts)[valid]
    got = np.zeros(users, np.int64)
    np.add.at(got, keys, counts)
    want_per = ref_fofof_per_user(src, dst, users)
    check(int(res.count) == want,
          f"(a) per_r {tag}: total {int(res.count)} != reference {want}")
    check(np.array_equal(got, want_per),
          f"(a) per_r {tag}: per-user counts differ from reference at "
          f"{int(np.sum(got != want_per))} users")
    if kcalls is not None:
        kcalls.check_compiled(["fused_per_r_counts"])
    info(f"(a) linear {tag}: edges={len(src)} users={users} "
         f"count={want} (> 2^31: {want > 2**31}) matches reference")


def phase_star(args, use_kernel, counters, kcalls):
    from repro.core import JoinSession
    tag = "kernel" if use_kernel else "jnp"
    fact, dims = star_data(args)
    q = star_query(fact, dims)
    sess = JoinSession(m_budget=m_budget(args.fact_rows, STAR_DIV),
                       use_kernel=use_kernel)
    want = ref_star([np.asarray(fact.col(f"k{i}")) for i in (1, 2, 3, 4)],
                    [np.asarray(dims[f"d{i}"].col(f"k{i}"))
                     for i in (1, 2, 3, 4)], args.key_range)
    res = timed_execute(f"(b) star {tag}", sess, q, counters)
    rels = [fact, *dims.values()]
    describe(f"(b) star {tag}", res, rels)
    ops = [st.op for st in res.steps]
    info(f"(b) star {tag}: plan steps={ops} "
         f"step_rows={[st.rows for st in res.steps]}")
    check("binary" in ops and "fused3" in ops,
          f"(b) star {tag}: plan {ops} lacks a binary step or a fused root")
    check(int(res.count) == want,
          f"(b) star {tag}: count {int(res.count)} != reference {want}")
    if kcalls is not None:
        roots = [n for n in FUSED_KERNELS if n in kcalls.calls]
        check(bool(roots), f"(b) star {tag}: no fused kernel ran")
        kcalls.check_compiled(roots)


def phase_cyclic(args, use_kernel, counters, kcalls):
    from repro.core import JoinSession, Relation
    from repro.kernels import ops
    tag = "kernel" if use_kernel else "jnp"
    src, dst = friends(args.tri_users, args.tri_friends, args.seed + 1)
    f = Relation.from_arrays(src=src, dst=dst)
    q = triangle_query(f)
    sess = JoinSession(m_budget=m_budget(len(src), CYCLIC_DIV),
                       use_kernel=use_kernel)
    info(f"(c) cyclic {tag}: cyclic kernel = "
         f"{ops.cyclic_kernel(use_kernel, pair_index=True)}")
    want = ref_triangles(src, dst, args.tri_users)
    res = timed_execute(f"(c) cyclic {tag}", sess, q, counters)
    describe(f"(c) cyclic {tag}", res, [f])
    check(int(res.count) == want,
          f"(c) cyclic {tag}: count {int(res.count)} != reference {want}")
    if kcalls is not None:
        kcalls.check_compiled(["fused_count3_cyclic"])


def phase_service(args, counters):
    from repro.core import JoinSession
    from repro.launch.join_service import JoinService
    users = args.users
    src, dst = friends(users, args.friends, args.seed)
    rels = {nm: friends_relation(src, dst, spare_rows(args))
            for nm in ("f1", "f2", "f3")}
    arrays = {nm: [src, dst] for nm in rels}
    q = linear_query(rels["f1"], rels["f2"], rels["f3"])
    mb = m_budget(len(src), LINEAR_DIV)
    svc = JoinService(max_queue=16, wave_size=4, m_budget=mb)
    t0 = time.perf_counter()
    fut = svc.watch("smoke", q)
    svc.run_until_idle()
    sq = fut.result()
    info(f"(d) service: watch count={sq.count} "
         f"wall_s={time.perf_counter() - t0:.3f}")
    rng = np.random.default_rng(args.seed + 7)
    for i in range(args.deltas):
        for nm, rel in rels.items():
            ds = rng.integers(0, users, args.delta_rows).astype(np.int32)
            dd = rng.integers(0, users, args.delta_rows).astype(np.int32)
            t1 = time.perf_counter()
            fut = svc.ingest("smoke", rel, {"src": ds, "dst": dd})
            svc.run_until_idle()
            fut.result()
            rec = sq.delta_rounds[-1]
            check(not rec.overflowed, f"(d) delta {i} {nm} overflowed")
            arrays[nm] = [np.concatenate([arrays[nm][0], ds]),
                          np.concatenate([arrays[nm][1], dd])]
            info(f"(d) delta {i} into {nm}: +{rec.delta_rows} rows "
                 f"count_delta={rec.count_delta} rounds={rec.rounds} "
                 f"replanned={rec.replanned} "
                 f"wall_s={time.perf_counter() - t1:.3f}")
    fut = svc.snapshot("smoke", sq)
    svc.run_until_idle()
    snap = fut.result()
    scratch = JoinSession(m_budget=mb).execute(q)
    (s1, d1), (s2, d2), (s3, d3) = (arrays[n] for n in ("f1", "f2", "f3"))
    indeg = np.bincount(d1, minlength=users).astype(np.int64)
    outdeg = np.bincount(s3, minlength=users).astype(np.int64)
    want = int(np.sum(indeg[s2] * outdeg[d2]))
    describe("(d) service snapshot", snap, list(rels.values()))
    info(f"(d) service: snapshot={int(snap.count)} "
         f"from_scratch={int(scratch.count)} reference={want} "
         f"metrics_waves={svc.metrics().get('waves')}")
    check(int(snap.count) == int(scratch.count) == want,
          f"(d) service: snapshot {int(snap.count)}, from-scratch "
          f"{int(scratch.count)}, reference {want} differ")


def run_one_chip(args, counters):
    kcalls = KernelCalls()
    info("cuts (scale only; query shapes kept): at 16M / 4M / 1.6M rows the "
         "executes of (a)-(d) alone took over 1000 s on a v5e, past the "
         "1200 s limit once compiles are added; (a)/(d) friends graph "
         f"{args.users} users x {args.friends} (suggested 1M x 16; ~1M "
         "edges, and 64 friends per user keeps the total past 2^31), (b) "
         f"fact {args.fact_rows} rows (suggested 4M), (c) {args.tri_users} "
         f"users x {args.tri_friends} (suggested 100k x 16), (d) "
         f"{args.deltas} ingest round(s) of {args.delta_rows} rows into "
         "each relation")
    for use_kernel in (False, True):
        kc = kcalls if use_kernel else None
        kcalls.reset()
        phase_linear(args, use_kernel, args.users, counters, kc)
        kcalls.reset()
        phase_star(args, use_kernel, counters, kc)
        kcalls.reset()
        phase_cyclic(args, use_kernel, counters, kc)
        memory(f"after {'kernel' if use_kernel else 'jnp'} phases")
    phase_service(args, counters)
    memory("after (d)")


def memory(label):
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    info(f"{label}: bytes_in_use={stats.get('bytes_in_use')} "
         f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def star3_query(fact, d1, d2):
    """The 3-relation star (fact x 2 dimensions) of (b)'s shape."""
    from repro.core import Query
    return Query(relations={"fact": fact, "d1": d1, "d2": d2},
                 predicates=[("fact.k1", "d1.k1"), ("fact.k2", "d2.k2")])


def four_chip_cases(args):
    """kind -> (query builder, relations, reference count, m_budget,
    execute_sharded grid options).  The device-local joins run the compiled
    all-pairs kernels, whose work grows with bucket size squared, so the
    linear and triangle queries take fine local grids."""
    from repro.core import Relation
    src, dst = friends(args.users, args.friends, args.seed)
    tsrc, tdst = friends(args.tri_users, args.tri_friends, args.seed + 1)
    fact, dims = star_data(args)
    d1, d2 = dims["d1"], dims["d2"]
    return {
        "linear": (lambda f: linear_query(f, f, f),
                   [Relation.from_arrays(src=src, dst=dst)],
                   ref_fofof(src, dst, args.users),
                   m_budget(len(src), LINEAR_DIV),
                   dict(local_u=64, local_g=64)),
        "cyclic": (triangle_query, [Relation.from_arrays(src=tsrc, dst=tdst)],
                   ref_triangles(tsrc, tdst, args.tri_users),
                   m_budget(len(tsrc), CYCLIC_DIV),
                   dict(local_uh=64, local_ug=64,
                        local_f=max(1, len(tsrc) // 2048))),
        "star": (star3_query, [fact, d1, d2],
                 ref_star([np.asarray(fact.col("k1")),
                           np.asarray(fact.col("k2"))],
                          [np.asarray(d1.col("k1")),
                           np.asarray(d2.col("k2"))], args.key_range),
                 m_budget(args.fact_rows, STAR_DIV), {}),
    }


def run_four_chips(args, counters):
    import jax

    from repro.compat import make_mesh
    from repro.core import JoinSession, distributed
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = make_mesh((2, 2), ("row", "col"))
    n_dev = mesh.devices.size

    def place(rel):
        return distributed.shard_relation(
            distributed.pad_to_multiple(rel, n_dev), mesh, "row", "col")

    def spread(label, rel):
        shards = rel.valid.addressable_shards
        on = sorted({s.device.id for s in shards})
        rows = [int(s.data.sum()) for s in shards]
        info(f"{label}: sharding={rel.valid.sharding} devices={on} "
             f"rows_per_shard={rows}")
        check(len(on) == n_dev and min(rows) > 0,
              f"{label}: rows {rows} on devices {on} only")

    for kind, (mk, rels, want, mb, grid) in four_chip_cases(args).items():
        c0 = counters.snapshot()[2]
        t0 = time.perf_counter()
        one = JoinSession(m_budget=mb, use_kernel=True).execute(mk(*rels))
        one_s = time.perf_counter() - t0
        placed = [place(r) for r in rels]
        spread(f"{kind} placed", placed[0])
        t1 = time.perf_counter()
        res = JoinSession(m_budget=mb, use_kernel=True).execute_sharded(
            mk(*placed), mesh, "row", "col", **grid)
        sharded_s = time.perf_counter() - t1
        c1 = counters.snapshot()[2]
        info(f"{kind} sharded (use_kernel=True, {grid}): kind={res.kind} "
             f"count={int(res.count)} one_chip={int(one.count)} "
             f"reference={want} rounds={res.rounds} "
             f"one_chip_wall_s={one_s:.3f} sharded_wall_s={sharded_s:.3f} "
             f"backend_compile_s={c1 - c0:.3f}")
        check(not res.overflowed, f"{kind} sharded overflowed")
        check(int(res.count) == int(one.count) == want,
              f"{kind} sharded {int(res.count)} / one-chip "
              f"{int(one.count)} / reference {want} differ")
    for dev in devs[:n_dev]:
        stats = dev.memory_stats()
        info(f"device {dev.id}: peak_bytes_in_use="
             f"{(stats or {}).get('peak_bytes_in_use')}")
        check(stats is None or stats.get("peak_bytes_in_use", 0) > 0,
              f"device {dev.id} held no data")


# --------------------------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--users", type=int, default=15_900,
                    help="friends-graph users for (a) and (d)")
    ap.add_argument("--friends", type=int, default=64)
    ap.add_argument("--tri-users", type=int, default=12_500)
    ap.add_argument("--tri-friends", type=int, default=16)
    ap.add_argument("--fact-rows", type=int, default=1_000_000)
    ap.add_argument("--dim-rows", type=int, default=37_500)
    ap.add_argument("--key-range", type=int, default=15_000)
    ap.add_argument("--deltas", type=int, default=1)
    ap.add_argument("--delta-rows", type=int, default=10_000)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform!r}); "
              "refusing to run on another backend", file=sys.stderr)
        return 1
    info(f"compile cache: {enable_compile_cache()}")
    info(f"jax {jax.__version__}; devices={jax.devices()}")
    counters = CompileCounters()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args, counters)
    else:
        run_one_chip(args, counters)
    hits, misses, compile_s = counters.snapshot()
    stats = dev.memory_stats() or {}
    info(f"total wall_s={time.perf_counter() - t0:.3f} "
         f"backend_compile_s={compile_s:.3f} cache_hits={hits} "
         f"cache_misses={misses} "
         f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
