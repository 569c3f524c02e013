"""Fused engine vs scan-based driver on the Fig 4 workload shapes.

Measures the tentpole claim of the engine PR: sweeping the H(B)×g(C)
partition grid as ONE fused launch (``core.engine.*_count_fused``) beats the
nested-``lax.scan`` per-bucket-row drivers (``core.linear3`` etc.) — the
same partitioning, the same per-bucket math, only the launch structure
differs.  Shapes are the paper's Fig 4 workloads (e,f: linear self-join;
g,h,i: star; plus the §5 triangle query), scaled to CPU-benchable sizes with
the partition counts preserved (tens of coarse partitions, so the scan
driver pays hundreds of sequential steps).

Both sides run the compiled XLA path (``use_kernel=False``) so the
comparison is launch-structure vs launch-structure, not interpreter
overhead.  Results go to BENCH_engine.json (CI uploads it every run —
the perf trajectory record).

    PYTHONPATH=src python benchmarks/engine_bench.py [--quick] [--repeats N]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import cyclic3, engine, linear3, star3  # noqa: E402
from repro.core.query import Query  # noqa: E402
from repro.core.relation import Relation  # noqa: E402
from repro.core.session import JoinSession  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.perfmodel import Calibration, calibrate  # noqa: E402

OUT = pathlib.Path("BENCH_engine.json")
CAL_OUT = pathlib.Path(calibrate.CALIBRATION_FILE)


def _rel(rng, n, cols, d):
    return Relation.from_arrays(
        **{c: rng.integers(0, d, size=n).astype(np.int32) for c in cols})


def _time(fn, *args, repeats: int) -> float:
    """Best-of-N wall time in ms for an already-jitted callable."""
    jax.block_until_ready(fn(*args))          # compile + warm caches
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def bench_linear(rng, n, d, m_budget, u, repeats):
    r = _rel(rng, n, ("a", "b"), d)
    s = _rel(rng, n, ("b", "c"), d)
    t = _rel(rng, n, ("c", "d"), d)
    plan = linear3.default_plan(n, n, n, m_budget=m_budget, u=u, slack=3.0)
    scan_fn = jax.jit(lambda a, b, c: linear3.linear3_count(a, b, c, plan))
    fused_fn = jax.jit(
        lambda a, b, c: engine.linear3_count_fused(a, b, c, plan))
    scan_ms = _time(scan_fn, r, s, t, repeats=repeats)
    fused_ms = _time(fused_fn, r, s, t, repeats=repeats)
    c0, c1 = int(scan_fn(r, s, t).count), int(fused_fn(r, s, t).count)
    return {"n": n, "d": d, "h_parts": plan.h_parts, "g_parts": plan.g_parts,
            "u": plan.u, "scan_ms": scan_ms, "fused_ms": fused_ms,
            "speedup": scan_ms / fused_ms, "count_scan": c0,
            "count_fused": c1, "match": c0 == c1}


def bench_cyclic(rng, n, d, m_budget, repeats):
    """Cyclic (triangle) query: the fused path now probes a sorted
    (c, a)-pair index of T (searchsorted range scans) instead of the
    all-pairs contraction — the backend that unsticks the ~1x cyclic CPU
    number.  The scan driver defaults to the pair index too now, so the
    GATED ``speedup`` pins ``pair_index=False`` to keep its historical
    all-pairs-scan-baseline semantics (the committed ratio stays
    comparable); the pair-index scan is recorded separately
    (``scan_pairidx_ms`` / ``speedup_vs_pairidx_scan``, not gated)."""
    r = _rel(rng, n, ("a", "b"), d)
    s = _rel(rng, n, ("b", "c"), d)
    t = _rel(rng, n, ("c", "a"), d)
    plan = cyclic3.default_plan(n, n, n, m_budget=m_budget, uh=4, ug=4,
                                slack=3.0)
    scan_fn = jax.jit(lambda a, b, c: cyclic3.cyclic3_count(
        a, b, c, plan, pair_index=False))
    scan_pi_fn = jax.jit(
        lambda a, b, c: cyclic3.cyclic3_count(a, b, c, plan))
    fused_fn = jax.jit(
        lambda a, b, c: engine.cyclic3_count_fused(a, b, c, plan))
    allpairs_fn = jax.jit(
        lambda a, b, c: engine.cyclic3_count_fused(a, b, c, plan,
                                                   pair_index=False))
    scan_ms = _time(scan_fn, r, s, t, repeats=repeats)
    scan_pi_ms = _time(scan_pi_fn, r, s, t, repeats=repeats)
    fused_ms = _time(fused_fn, r, s, t, repeats=repeats)
    allpairs_ms = _time(allpairs_fn, r, s, t, repeats=repeats)
    c0, c1 = int(scan_fn(r, s, t).count), int(fused_fn(r, s, t).count)
    c2 = int(allpairs_fn(r, s, t).count)
    c3 = int(scan_pi_fn(r, s, t).count)
    return {"n": n, "d": d, "h_parts": plan.h_parts, "g_parts": plan.g_parts,
            "f_parts": plan.f_parts, "scan_ms": scan_ms,
            "scan_pairidx_ms": scan_pi_ms,
            "fused_ms": fused_ms, "fused_allpairs_ms": allpairs_ms,
            "speedup": scan_ms / fused_ms,
            "speedup_vs_pairidx_scan": scan_pi_ms / fused_ms,
            "count_scan": c0, "count_fused": c1,
            "match": c0 == c1 == c2 == c3}


def bench_star(rng, n_dim, n_fact, d, chunks, repeats):
    r = _rel(rng, n_dim, ("a", "b"), d)
    s = _rel(rng, n_fact, ("b", "c"), d)
    t = _rel(rng, n_dim, ("c", "d"), d)
    plan = star3.default_plan(n_dim, n_fact, n_dim, uh=8, ug=8,
                              chunks=chunks, slack=3.0)
    scan_fn = jax.jit(lambda a, b, c: star3.star3_count(a, b, c, plan))
    fused_fn = jax.jit(
        lambda a, b, c: engine.star3_count_fused(a, b, c, plan))
    scan_ms = _time(scan_fn, r, s, t, repeats=repeats)
    fused_ms = _time(fused_fn, r, s, t, repeats=repeats)
    c0, c1 = int(scan_fn(r, s, t).count), int(fused_fn(r, s, t).count)
    return {"n_dim": n_dim, "n_fact": n_fact, "d": d, "chunks": chunks,
            "scan_ms": scan_ms, "fused_ms": fused_ms,
            "speedup": scan_ms / fused_ms, "count_scan": c0,
            "count_fused": c1, "match": c0 == c1}


def bench_session_cache(rng, n, d, m_budget, repeats):
    """The declarative front door's plan cache: a cold ``execute`` pays
    classification + strategy/shape sizing (incl. a host-side distinct
    estimate), a warm one skips straight to the fused engine.  Gated on
    cached-plan behavior (warm must re-plan nothing), recorded as cold vs
    warm PLANNING milliseconds (execution time is identical by
    construction and noisy, so it is excluded from the gate)."""
    r = _rel(rng, n, ("a", "b"), d)
    s = _rel(rng, n, ("b", "c"), d)
    t = _rel(rng, n, ("c", "d"), d)
    q = Query(relations={"r": r, "s": s, "t": t},
              predicates=[("r.b", "s.b"), ("s.c", "t.c")])
    sess = JoinSession(m_budget=m_budget)
    cold = sess.execute(q)
    warm_plan_ms = float("inf")
    warm_hits = True
    for _ in range(max(repeats, 2)):
        w = sess.execute(q)
        warm_hits &= w.cache_hit
        warm_plan_ms = min(warm_plan_ms, w.plan_s * 1e3)
    return {"n": n, "d": d, "kind": cold.kind, "strategy": cold.strategy,
            "cold_plan_ms": cold.plan_s * 1e3,
            "warm_plan_ms": warm_plan_ms,
            "plan_speedup": cold.plan_s * 1e3 / max(warm_plan_ms, 1e-6),
            "count": int(cold.count), "warm_cache_hits": warm_hits,
            "match": warm_hits and int(w.count) == int(cold.count)}


def _chain4_query(rng, n, d):
    rels = {f"r{i + 1}": _rel(rng, n, cols, d)
            for i, cols in enumerate((("a", "b"), ("b", "c"), ("c", "d"),
                                      ("d", "e")))}
    preds = [("r1.b", "r2.b"), ("r2.c", "r3.c"), ("r3.d", "r4.d")]
    return Query(relations=rels, predicates=preds)


def bench_cascade_4way(rng, n, d, m_budget, repeats):
    """The N-way plan IR on a 4-relation chain, with Appendix-A time-model
    calibration closed into a loop:

    1. measure BOTH roots through the same executor — forced ``"3way"``
       (hybrid: binary materialize + fused recovery-wrapped root) gives
       ``fused_root_s``, forced ``"cascade"`` gives ``binary_tail_s`` (the
       two binary steps standing in for the root),
    2. read the UNCALIBRATED model totals off the default plan's root
       ``TimedChoice`` (``model_t3_s`` / ``model_tc_s``) — these four
       numbers are what ``perfmodel.calibration_from_bench`` re-anchors
       the constants from (they are committed in BENCH_engine.json),
    3. re-plan with that measured calibration and time the calibrated
       default.  The calibrated pick is the measured-faster root, so
       ``ir_vs_binary = allbinary_ms / ir_ms`` is >= 1.0 up to timer noise
       — when the calibrated planner picks the cascade itself the two
       plans are IDENTICAL and the ratio is exactly 1.0 by construction
       (recorded with ``same_plan``).  check_bench_regression.py gates
       ``ir_vs_binary >= 1.0``; ``match`` gates exact count agreement."""
    q = _chain4_query(rng, n, d)
    sess = JoinSession(m_budget=m_budget)
    cold = sess.execute(q)                      # decompose + compile
    model_t3_s = cold.plan.root.choice.t_3way_s
    model_tc_s = cold.plan.root.choice.t_cascade_s
    fused = sess.execute(q, strategy="3way")
    binary = sess.execute(q, strategy="cascade")
    fused_root_s = binary_tail_s = binary_ms = fused_ms = float("inf")
    for _ in range(max(repeats, 2)):
        wf = sess.execute(q, strategy="3way")
        fused_ms = min(fused_ms, wf.exec_s * 1e3)
        fused_root_s = min(fused_root_s, sum(
            s.exec_s for s in wf.step_stats if s.op == "fused3"))
        wb = sess.execute(q, strategy="cascade")
        binary_ms = min(binary_ms, wb.exec_s * 1e3)
        binary_tail_s = min(binary_tail_s, sum(
            s.exec_s for s in wb.step_stats[-2:]))

    cal = Calibration(
        fused3_scale=fused_root_s / max(model_t3_s, 1e-12),
        cascade_scale=binary_tail_s / max(model_tc_s, 1e-12),
        source="bench:cascade_4way (in-process)")
    csess = JoinSession(m_budget=m_budget, calibration=cal)
    calib = csess.execute(q)                    # calibrated re-plan
    # a calibrated cascade pick IS the forced-cascade plan (only the root
    # step's recorded TimedChoice differs) — the ratio is 1.0 by
    # construction, not worth measuring against timer jitter
    same_plan = calib.strategy == binary.strategy == "cascade"
    ir_ms = float("inf")
    for _ in range(max(repeats, 2)):
        w = csess.execute(q)
        ir_ms = min(ir_ms, w.exec_s * 1e3)
    ir_vs_binary = (1.0 if same_plan
                    else binary_ms / max(ir_ms, 1e-9))
    return {"n": n, "d": d, "n_relations": 4,
            "steps": len(calib.plan.steps),
            "fused3_steps": len(calib.plan.fused3_steps),
            "strategy": calib.strategy,
            "model_strategy": cold.strategy,
            "ir_ms": ir_ms, "allbinary_ms": binary_ms,
            "forced3way_ms": fused_ms,
            "ir_vs_binary": ir_vs_binary, "same_plan": same_plan,
            "fused_root_s": fused_root_s, "binary_tail_s": binary_tail_s,
            "model_t3_s": model_t3_s, "model_tc_s": model_tc_s,
            "fused3_scale": cal.fused3_scale,
            "cascade_scale": cal.cascade_scale,
            "count": int(calib.count),
            "match": (int(cold.count) == int(binary.count)
                      == int(fused.count) == int(calib.count)
                      and not cold.overflowed and not binary.overflowed
                      and not calib.overflowed
                      and len(cold.plan.steps) >= 2)}


def _tree6_query(rng, n, d):
    """Six relations, five edges, TWO independent branches meeting at a
    shared sink: r1-r2-r3 (chain) and r4-r5 (chain) both join r6.  The
    branches share no relation, so the overlapped executor can have one
    branch's gather in flight while it stages the other."""
    rels = {"r1": _rel(rng, n, ("a", "b"), d),
            "r2": _rel(rng, n, ("b", "c"), d),
            "r3": _rel(rng, n, ("c", "d"), d),
            "r4": _rel(rng, n, ("e", "f"), d),
            "r5": _rel(rng, n, ("f", "g"), d),
            "r6": _rel(rng, n, ("d", "g"), d)}
    preds = [("r1.b", "r2.b"), ("r2.c", "r3.c"), ("r4.f", "r5.f"),
             ("r3.d", "r6.d"), ("r5.g", "r6.g")]
    return Query(relations=rels, predicates=preds)


def _tree6_oracle(q) -> int:
    """Exact count of the 6-relation tree by numpy/dict weight backflow:
    per-row weights flow from the leaves (r1, r4) to the sink (r6)."""
    from collections import Counter, defaultdict

    def rows(name, col):
        rel = q.relations[name]
        return np.asarray(rel.col(col))[np.asarray(rel.valid)]

    def flow(keys, weights, probe):
        acc = defaultdict(int)
        for k, w in zip(keys.tolist(), weights.tolist()):
            acc[k] += w
        return np.array([acc.get(k, 0) for k in probe.tolist()], np.int64)

    w2 = np.array([Counter(rows("r1", "b").tolist()).get(k, 0)
                   for k in rows("r2", "b").tolist()], np.int64)
    w3 = flow(rows("r2", "c"), w2, rows("r3", "c"))
    w5 = np.array([Counter(rows("r4", "f").tolist()).get(k, 0)
                   for k in rows("r5", "f").tolist()], np.int64)
    w6 = (flow(rows("r3", "d"), w3, rows("r6", "d"))
          * flow(rows("r5", "g"), w5, rows("r6", "g")))
    return int(w6.sum())


def bench_plan_pipeline_6way(rng, n, d, m_budget, repeats):
    """The overlapped DAG executor on a 6-relation tree with two
    independent branches: the overlapped walk is timed (per-step device
    time comes from a profiler trace's ``repro.plan.step`` spans).  Gated
    on exact agreement with a numpy backflow oracle."""
    q = _tree6_query(rng, n, d)
    sess = JoinSession(m_budget=m_budget)
    cold = sess.execute(q)                      # decompose + compile
    exec_ms = float("inf")
    for _ in range(max(repeats, 2)):
        w = sess.execute(q)
        exec_ms = min(exec_ms, w.exec_s * 1e3)
    oracle = _tree6_oracle(q)
    return {"n": n, "d": d, "n_relations": 6,
            "steps": len(cold.plan.steps),
            "fused3_steps": len(cold.plan.fused3_steps),
            "strategy": cold.strategy,
            "exec_ms": exec_ms,
            "count": int(cold.count), "oracle_count": oracle,
            "match": (int(cold.count) == oracle == int(w.count)
                      and not cold.overflowed
                      and len(cold.plan.steps) >= 4)}


def bench_execute_many(rng, n, d, m_budget, batch, repeats):
    """JoinSession.execute_many warm-cache amortization: a batch of
    structurally identical 4-way queries plans ONCE — every query after
    the first is a plan-cache hit (log-bucketed cardinality keys), so
    per-query planning cost collapses.  Gated on cache behavior + exact
    counts (match)."""
    q = _chain4_query(rng, n, d)
    sess = JoinSession(m_budget=m_budget)
    results = sess.execute_many([q] * batch)
    counts = {int(r.count) for r in results}
    cold_plan_ms = results[0].plan_s * 1e3
    warm_plan_ms = min(r.plan_s for r in results[1:]) * 1e3
    for _ in range(max(repeats - 1, 1)):
        again = sess.execute_many([q] * batch)
        warm_plan_ms = min(warm_plan_ms,
                           min(r.plan_s for r in again) * 1e3)
    return {"n": n, "d": d, "batch": batch,
            "cold_plan_ms": cold_plan_ms, "warm_plan_ms": warm_plan_ms,
            "plan_amortization": cold_plan_ms / max(warm_plan_ms, 1e-6),
            "warm_cache_hits": all(r.cache_hit for r in results[1:]),
            "count": int(results[0].count),
            "match": (len(counts) == 1
                      and all(r.cache_hit for r in results[1:]))}


def bench_streaming_ingest(rng, n, d, m_budget, delta_frac, deltas,
                           repeats):
    """Standing-query delta execution vs full re-execution at ingest.

    A watched linear 3-way query absorbs delta batches (``delta_frac`` of
    the base size, rotating over R/S/T) through the delta plan — resident
    intermediates + family-masked siblings — while the oracle side
    re-executes the whole query from scratch at the final state.  One
    warm-up ingest per relation compiles the delta shapes and is excluded
    from timing.  Gated on exact count match and the per-round
    ``overflowed == False`` recovery contract."""
    k = max(1, int(n * delta_frac))
    rels = {"R": _rel(rng, n, ("a", "b"), d),
            "S": _rel(rng, n, ("b", "c"), d),
            "T": _rel(rng, n, ("c", "e"), d)}
    schema = {"R": ("a", "b"), "S": ("b", "c"), "T": ("c", "e")}
    q = Query(rels, [("R.b", "S.b"), ("S.c", "T.c")])
    sq = JoinSession(m_budget=m_budget).watch(q)
    names = list(rels)

    def ingest(i):
        name = names[i % 3]
        batch = {c: rng.integers(0, d, k).astype(np.int32)
                 for c in schema[name]}
        t0 = time.perf_counter()
        rels[name].append(batch)
        return (time.perf_counter() - t0) * 1e3

    for i in range(3):                      # warm-up: compile delta shapes
        ingest(i)
    delta_ms = min(ingest(3 + i) for i in range(deltas))
    overflow_free = all(not r.overflowed for r in sq.delta_rounds)
    standing = int(sq.snapshot().count)

    oracle_sess = JoinSession(m_budget=m_budget)
    full = oracle_sess.execute(q)           # compile + plan at final state
    full_ms = float("inf")
    for _ in range(max(repeats, 2)):
        t0 = time.perf_counter()
        full = oracle_sess.execute(q)
        full_ms = min(full_ms, (time.perf_counter() - t0) * 1e3)
    sq.close()
    return {"n": n, "d": d, "delta_rows": k, "deltas": deltas,
            "delta_ms": delta_ms, "full_ms": full_ms,
            "speedup": full_ms / max(delta_ms, 1e-6),
            "count": standing, "overflow_free": overflow_free,
            "match": standing == int(full.count) and overflow_free}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI sizes (smaller relations, fewer repeats)")
    ap.add_argument("--repeats", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    repeats = args.repeats or (2 if args.quick else 4)
    scale = 1 if args.quick else 2
    rng = np.random.default_rng(20260726)

    shapes = {}
    print(f"engine_bench: backend={jax.default_backend()} "
          f"quick={args.quick}")
    # Fig 4(e,f): linear self-join, |R|=|S|=|T|, tens of coarse partitions
    shapes["fig4ef_linear"] = bench_linear(
        rng, n=24000 * scale, d=4096 * scale, m_budget=1024 * scale, u=16,
        repeats=repeats)
    # §5 triangle query on a random graph
    shapes["cyclic_triangles"] = bench_cyclic(
        rng, n=6000 * scale, d=512 * scale, m_budget=512 * scale,
        repeats=repeats)
    # Fig 4(h,i): star schema — small dimensions, streamed fact
    shapes["fig4hi_star"] = bench_star(
        rng, n_dim=2000 * scale, n_fact=120000 * scale, d=2048 * scale,
        chunks=8, repeats=repeats)
    # declarative session: cold vs warm plan-cache execute
    shapes["session_plan_cache"] = bench_session_cache(
        rng, n=24000 * scale, d=4096 * scale, m_budget=1024 * scale,
        repeats=repeats)
    # N-way plan IR: 4-relation chain, calibrated default vs all-binary
    shapes["cascade_4way"] = bench_cascade_4way(
        rng, n=12000 * scale, d=2048 * scale, m_budget=1024 * scale,
        repeats=repeats)
    # overlapped DAG dispatch: 6-relation tree, two independent branches
    shapes["plan_pipeline_6way"] = bench_plan_pipeline_6way(
        rng, n=8000 * scale, d=1024 * scale, m_budget=1024 * scale,
        repeats=repeats)
    # batched execution over the plan cache
    shapes["session_execute_many"] = bench_execute_many(
        rng, n=12000 * scale, d=2048 * scale, m_budget=1024 * scale,
        batch=6, repeats=repeats)
    # standing-query ingest: delta plans vs from-scratch re-execution
    shapes["streaming_ingest"] = bench_streaming_ingest(
        rng, n=24000 * scale, d=4096 * scale, m_budget=1024 * scale,
        delta_frac=0.01, deltas=max(repeats * 2, 4), repeats=repeats)

    for name, row in shapes.items():
        if "delta_ms" in row:
            print(f"  {name}: delta {row['delta_ms']:.1f} ms "
                  f"({row['delta_rows']} rows), full re-execute "
                  f"{row['full_ms']:.1f} ms, speedup "
                  f"{row['speedup']:.1f}x, match={row['match']}")
        elif "scan_ms" in row:
            print(f"  {name}: scan {row['scan_ms']:.1f} ms, "
                  f"fused {row['fused_ms']:.1f} ms, "
                  f"speedup {row['speedup']:.2f}x, match={row['match']}")
        elif "ir_ms" in row:
            print(f"  {name}: ir {row['ir_ms']:.1f} ms "
                  f"({row['steps']} steps, {row['fused3_steps']} fused), "
                  f"all-binary {row['allbinary_ms']:.1f} ms, "
                  f"ir_vs_binary {row['ir_vs_binary']:.2f}x, "
                  f"match={row['match']}")
        elif "exec_ms" in row:
            print(f"  {name}: exec {row['exec_ms']:.1f} ms overlapped "
                  f"({row['steps']} steps), match={row['match']}")
        else:
            print(f"  {name}: cold plan {row['cold_plan_ms']:.2f} ms, "
                  f"warm plan {row['warm_plan_ms']:.3f} ms, "
                  f"cache hits={row['warm_cache_hits']}")

    best = max(s["speedup"] for name, s in shapes.items()
               if "speedup" in s and name != "streaming_ingest")
    cyc = shapes["cyclic_triangles"]["speedup"]
    cache = shapes["session_plan_cache"]
    ok = best >= 2.0 and all(s["match"] for s in shapes.values())
    # the exit gate uses a noise-tolerant 2x floor (shared CI runners
    # jitter); the measured value and the 3x claim go in the JSON record,
    # and check_bench_regression.py guards the trajectory against the
    # committed baseline ratio
    cyc_ok = cyc >= 2.0
    report = {
        "backend": jax.default_backend(),
        "quick": bool(args.quick),
        "repeats": repeats,
        "shapes": shapes,
        "claim_fused_ge_2x": {
            "ok": ok, "best_speedup": best,
            "detail": "fused engine >= 2x over scan driver on at least one "
                      "Fig 4 shape, counts exactly equal",
        },
        "claim_cyclic_pairidx_ge_3x": {
            "ok": cyc >= 3.0, "speedup": cyc,
            "detail": "cyclic fused path with the sorted (c,a)-pair-index "
                      "backend >= 3x over the cyclic scan driver",
        },
        "claim_session_plan_cache": {
            "ok": bool(cache["warm_cache_hits"]),
            "cold_plan_ms": cache["cold_plan_ms"],
            "warm_plan_ms": cache["warm_plan_ms"],
            "detail": "warm JoinSession.execute hits the plan cache "
                      "(skips classification + sizing entirely)",
        },
        "claim_nway_plan_ir": {
            "ok": bool(shapes["cascade_4way"]["match"]
                       and shapes["session_execute_many"]["match"]),
            "steps": shapes["cascade_4way"]["steps"],
            "fused3_steps": shapes["cascade_4way"]["fused3_steps"],
            "plan_amortization":
                shapes["session_execute_many"]["plan_amortization"],
            "detail": "a 4-relation chain decomposes into a multi-step "
                      "plan with a fused 3-way root whose count equals "
                      "the all-binary cascade exactly, and execute_many "
                      "amortizes planning over the cache",
        },
        "claim_streaming_delta_ge_5x": {
            "ok": bool(shapes["streaming_ingest"]["speedup"] >= 5.0
                       and shapes["streaming_ingest"]["match"]),
            "speedup": shapes["streaming_ingest"]["speedup"],
            "overflow_free": shapes["streaming_ingest"]["overflow_free"],
            "detail": "standing-query delta execution (resident "
                      "intermediates + family-masked siblings) >= 5x "
                      "faster than from-scratch re-execution at a 1% "
                      "delta, exact counts, overflowed == False every "
                      "delta round",
        },
        "claim_calibrated_plan_never_loses": {
            "ok": bool(shapes["cascade_4way"]["ir_vs_binary"] >= 1.0
                       and shapes["cascade_4way"]["match"]
                       and shapes["plan_pipeline_6way"]["match"]),
            "ir_vs_binary": shapes["cascade_4way"]["ir_vs_binary"],
            "calibrated_strategy": shapes["cascade_4way"]["strategy"],
            "detail": "with the time model calibrated from measured "
                      "per-root seconds, the session's default plan is "
                      "never slower than the forced all-binary cascade "
                      "(the overlapped device-resident executor runs "
                      "both), and the 6-relation DAG walk matches the "
                      "numpy oracle exactly",
        },
    }
    OUT.write_text(json.dumps(report, indent=2))
    # refresh the committed calibration snapshot from THIS report, so
    # calibration_from_file never reads constants staler than the latest
    # committed bench record (the carried ROADMAP follow-up)
    cal = calibrate.refresh_calibration_file(report, CAL_OUT)
    print(f"  calibration -> {CAL_OUT} (fused3 {cal.fused3_scale:.3g}, "
          f"cascade {cal.cascade_scale:.3g}, {cal.source})")
    cache_ok = bool(cache["warm_cache_hits"])
    nway_ok = bool(report["claim_nway_plan_ir"]["ok"])
    cal_ok = bool(report["claim_calibrated_plan_never_loses"]["ok"])
    print(f"[{'PASS' if ok else 'FAIL'}] best fused speedup {best:.2f}x; "
          f"[{'PASS' if cyc_ok else 'FAIL'}] cyclic pair-index {cyc:.2f}x; "
          f"[{'PASS' if cache_ok else 'FAIL'}] session plan cache; "
          f"[{'PASS' if nway_ok else 'FAIL'}] N-way plan IR; "
          f"[{'PASS' if cal_ok else 'FAIL'}] calibrated plan "
          f">= cascade -> {OUT}")
    return 0 if (ok and cyc_ok and cache_ok and nway_ok and cal_ok) else 1


if __name__ == "__main__":
    raise SystemExit(main())
