"""recall@10 of every answer of the window against the exact reference
(the comparison that decides ``correct``)."""


def read(run):
    return run.recall if run.k == 10 else None
