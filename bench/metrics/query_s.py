"""Window seconds per answered query: from the first send to the last answer,
over the queries answered (a query ends when its count is on the host)."""


def read(run):
    if not run.answered:
        return None
    return run.window_s / len(run.answered)
