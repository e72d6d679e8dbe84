"""Share of dispatched query slots that were padding, in %: the
scheduler's ``padded_slots`` over ``batched_rows + padded_slots``
(``ServeStats``), over the window."""


def read(run):
    b = run.stats["buckets"].values()
    rows = sum(v["batched_rows"] for v in b)
    pad = sum(v["padded_slots"] for v in b)
    return 100.0 * pad / (rows + pad) if rows + pad else None
