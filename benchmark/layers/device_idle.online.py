"""Share of the traced window in which the device ran no operation, in
%: 1 - busy / window from the profiler's trace."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
