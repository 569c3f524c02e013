"""Recovery: mean ``QueryResult.rounds`` (skew-recovery rounds, summed over
the plan's fused steps) of the window's answers."""


def read(run):
    res = [r.result for r in run.answered
           if getattr(r.result, "rounds", None) is not None]
    if not res:
        return None
    return sum(int(r.rounds) for r in res) / len(res)
