"""Queries answered in the window per second of the window."""


def read(run):
    answered = sum(len(r.rows) for r in run.requests if r.answered)
    return answered / (run.end - run.start) if answered else None
