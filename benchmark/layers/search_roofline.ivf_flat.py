"""Share of the chip's roofline the ivf_flat search reached, in %: the least
time of the work the window's batches require (``work/ivf_flat.py``, the
larger of operations over the bf16 peak and bytes over HBM bandwidth)
over the device's busy time in the traced window."""


def read(run):
    if run.family != "ivf_flat" or not run.work or not run.trace \
            or not run.trace["busy_s"]:
        return None
    return 100.0 * run.work["seconds"] / run.trace["busy_s"]
