"""Planner: mean ``QueryResult.plan_s`` of the window's answers, in ms.  The
session times decomposition, sizing and the plan-cache lookup itself."""


def read(run):
    res = [r.result for r in run.answered
           if getattr(r.result, "plan_s", None) is not None]
    if not res:
        return None
    return 1e3 * sum(r.plan_s for r in res) / len(res)
