"""Fused root's share of its roofline, in percent: the least time the root
could take over the device time it took.

The least time reads, once, the key columns that each input of the plan's
fused 3-way steps joins on: input rows x join columns x 4 bytes, at the
chip's HBM bandwidth (``peaks.json``).  That is the same work whatever
implements the root.  The time taken is the device time of the fused root
modules in the traced queries, all recovery rounds together."""

import trace_reduce

KEY_BYTES = 4


def root_bytes(res, alias_rows: dict[str, int]) -> int:
    """Key bytes that the fused steps of ``res.plan`` have to read once:
    each role's input rows times the key columns bound to that role."""
    rows = dict(alias_rows)
    rows.update({st.out: int(st.rows) for st in res.steps})
    total = 0
    for step in res.plan.steps:
        if step.op != "fused3":
            continue
        for role, name in step.roles:
            cols = {col for kwarg, col in step.cols if kwarg[0] == role}
            total += rows[name] * len(cols) * KEY_BYTES
    return total


def read(run):
    if not run.peaks:
        return None
    root_ms = trace_reduce.layer_ms_per_request(run, "fused root")
    if root_ms is None:
        return None
    qrel = run.cell.config["queries"][run.cell.traffic["query"]]["relations"]
    alias_rows = {a: run.base_rows[t] for a, t in qrel.items()}
    traced = run.traced
    least_s = (sum(root_bytes(r.result, alias_rows) for r in traced)
               / len(traced) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (root_ms / 1e3)
