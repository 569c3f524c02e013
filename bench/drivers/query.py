"""Closed-loop query traffic: one client sends the cell's query to
``launch.join_service.JoinService``, waits for its count, and sends it
again.

    {"op": "query", "query": "<a query of the config>", "warmup": 1}

The relations hold the generated tables at the config's ``capacity``; the
service runs its pump thread.  Every answer of the window is compared
exactly with the reference count of the tables.
"""

from __future__ import annotations

import time

from harness import Req, log

from repro.core import Query, Relation
from repro.launch.join_service import JoinService

TENANT = "bench"


class State:
    def __init__(self, svc, query, tables: dict, qspec: dict):
        self.svc = svc
        self.query = query
        self.tables = tables
        self.qspec = qspec


def relations(cfg: dict, tables: dict) -> dict:
    caps = cfg.get("capacity", {})
    return {name: Relation.from_arrays(capacity=caps.get(name), **cols)
            for name, cols in tables.items()}


def make_query(qspec: dict, rels: dict) -> Query:
    return Query(relations={a: rels[t] for a, t in qspec["relations"].items()},
                 predicates=[tuple(p) for p in qspec["predicates"]])


def open(cell, devices, tables: dict, seed: int) -> State:
    cfg, traffic = cell.config, cell.traffic
    qspec = cfg["queries"][traffic["query"]]
    query = make_query(qspec, relations(cfg, tables))
    svc = JoinService(max_queue=64, wave_size=8, m_budget=cfg["m_budget"])
    svc.start()
    try:
        for _ in range(traffic.get("warmup", 1)):
            svc.submit(TENANT, query).result()
    except BaseException:
        svc.stop()
        raise
    return State(svc, query, tables, qspec)


def window(state: State, seconds: float, tracer) -> list[Req]:
    """One client: send the query, wait for its count, repeat."""
    start = time.perf_counter()
    tracer.start(start)
    deadline = start + seconds
    out: list[Req] = []
    while not out or time.perf_counter() < deadline:
        req = Req(t0=time.perf_counter(), traced=tracer.on)
        with tracer.span("query"):
            try:
                req.result = state.svc.submit(TENANT, state.query).result()
            except Exception as e:  # noqa: BLE001 - a failed answer is counted
                req.error = f"{type(e).__name__}: {e}"
        req.t1 = time.perf_counter()
        out.append(req)
        tracer.after(req.t1)
    tracer.close()
    return out


def close(state: State) -> None:
    state.svc.stop()


def check(state: State, ref, reqs: list[Req]) -> dict:
    """Every answer against the reference count of the state's tables."""
    want = ref.count(state.tables, state.qspec)
    gap = max((abs(int(r.result.count) - want) for r in reqs
               if r.result is not None), default=0)
    unanswered = sum(1 for r in reqs if r.result is None)
    log(f"check: {len(reqs) - unanswered} answers, reference count {want}")
    return {"count_gap": {"value": gap, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0}}
